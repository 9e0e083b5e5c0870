"""The trace reducer on a small trace recorded on a TPU v5e (three steps
of a jitted paged-attention call and a matmul, 10 ms of host waiting
after each, ``bench.*`` host spans; ``data/tiny.xplane.pb``).  Busy time
is checked against a sweep over the raw events written independently
here, the kernel's time against the sum of its own events.

A Pallas kernel's custom call takes its op name from the function it is
traced in: ``%step.1`` in this trace, ``%paged_attention.N`` in the
serving step."""
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).parent / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(DATA))


@pytest.fixture(scope="module")
def tr():
    return TR.load(str(DATA))


def _sweep_busy(events, lo, hi):
    """Busy seconds of [lo, hi] by an endpoint sweep (a second method)."""
    pts = []
    for a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(pts):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_loads_device_ops_and_host_spans(tr):
    assert len(tr.ops) == 1
    names = {n for n, _, _ in tr.spans}
    assert {"bench.traced", "bench.engine.step", "bench.wait"} <= names
    assert sum(n == "bench.engine.step" for n, _, _ in tr.spans) == 3


def test_busy_and_idle_match_a_sweep_over_raw_events(raw, tr):
    s = TR.summarize(tr)
    lo, hi = TR.window(tr)
    assert s.window_s == pytest.approx(hi - lo)
    evs = []
    for plane in raw.planes:
        if plane.name.startswith(TR.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == TR.OPS_LINE:
                    evs += [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
    assert evs
    assert s.busy_s == pytest.approx(_sweep_busy(evs, lo, hi), rel=1e-9)
    assert 0 < s.busy_s < s.window_s


def test_gaps_are_named_by_the_host_span(tr):
    s = TR.summarize(tr)
    # the three 10 ms sleeps are the longest idle gaps of the window
    waits = [g for g in s.gaps if g[0] == "wait"]
    assert len(waits) >= 3
    assert all(g[1] >= 0.009 for g in waits[:3])
    assert s.gaps[0][1] >= s.gaps[-1][1]


KERNEL = "%step"


def test_kernel_time_is_the_sum_of_its_events(raw, tr):
    s = TR.summarize(tr)
    lo, hi = TR.window(tr)
    want = 0.0
    for plane in raw.planes:
        for line in plane.lines:
            if plane.name.startswith(TR.DEVICE_PLANE) and \
                    line.name == TR.OPS_LINE:
                for e in line.events:
                    if e.name.startswith(KERNEL + ".") and \
                            "tpu_custom_call" in e.name:
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        want += max(0.0, min(b, hi) - max(a, lo))
    got = TR.kernel_seconds(s, KERNEL)
    assert got > 0
    assert got == pytest.approx(want)
    assert TR.kernel_seconds(s, "no_such_kernel") == 0.0
