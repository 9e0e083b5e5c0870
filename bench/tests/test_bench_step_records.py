"""The readers of the program's step records (``bench/step_records.py``
and the metrics ``step_host_share.*`` / ``step_compiles.*``): records
paired with the run's steps by order, summed over the window's steps
alone, and nothing read where the pairing cannot be trusted."""
import collections
from types import SimpleNamespace

import pytest

from bench import harness, step_records
from bench.harness import Run, StepRec
from bench.tests import cpu_run, smoke
from repro.serving import telemetry as T

READERS = ["step_host_share.chat", "step_host_share.batch",
           "step_compiles.chat", "step_compiles.batch"]


def _run(spans):
    """A run of 10 s whose steps span ``spans`` (t0, t1)."""
    run = Run({}, {}, {}, None, 10.0, {}, 4)
    run.steps = [StepRec(a, b) for a, b in spans]
    return run


def _rec(wall, readback, compiles=0):
    return SimpleNamespace(wall_s=wall, self_s={T.STEP: wall - readback,
                                                T.READBACK: readback},
                           compiles=compiles)


def _registered(monkeypatch, *tels):
    monkeypatch.setattr(T, "registered", lambda: list(tels))


def _tel(recs, n_steps=None):
    return SimpleNamespace(records=collections.deque(recs),
                           n_steps=len(recs) if n_steps is None
                           else n_steps)


# warm-up, three window steps, one past the window's end
SPANS = [(-1.0, -0.5), (0.0, 1.0), (1.0, 2.0), (2.0, 3.0), (9.5, 10.5)]


def test_records_pair_with_steps_by_order(monkeypatch):
    recs = [_rec(1.0, 0.0, 5), _rec(1.0, 0.5, 1), _rec(2.0, 1.0, 0),
            _rec(1.0, 0.25, 2), _rec(4.0, 4.0, 7)]
    _registered(monkeypatch, _tel(recs))
    run = _run(SPANS)
    assert step_records.window_records(run) == recs[1:4]
    assert harness.reader("step_host_share.chat")(run) == pytest.approx(
        100.0 * (4.0 - 1.75) / 4.0)
    assert harness.reader("step_compiles.batch")(run) == 3


def test_only_the_records_kept_are_read(monkeypatch):
    # the ring dropped the warm-up step's record: the rest still pair by
    # their ordinal, the i-th record with the i-th step
    recs = [_rec(1.0, 0.5, 1), _rec(2.0, 1.0, 0), _rec(1.0, 0.25, 2),
            _rec(4.0, 4.0, 7)]
    _registered(monkeypatch, _tel(recs, n_steps=5))
    run = _run(SPANS)
    assert step_records.compiles(run) == 3
    # ... and nothing is read once the window's first step is gone
    _registered(monkeypatch, _tel(recs[1:], n_steps=5))
    assert step_records.compiles(run) is None
    assert step_records.host_share(run) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_when_the_pairing_fails(monkeypatch, name):
    read = harness.reader(name)
    run = _run(SPANS)
    recs = [_rec(1.0, 0.5, 1) for _ in SPANS]
    _registered(monkeypatch, _tel(recs[:-1]))           # counts differ
    assert read(run) is None
    _registered(monkeypatch, _tel(recs), _tel(recs))    # two engines
    assert read(run) is None
    _registered(monkeypatch)                            # none
    assert read(run) is None
    _registered(monkeypatch, _tel(recs))
    assert read(run) is not None


def test_nothing_is_read_from_a_program_without_telemetry(monkeypatch):
    monkeypatch.setattr(step_records, "_telemetry", lambda: None)
    assert step_records.host_share(_run(SPANS)) is None
    assert step_records.compiles(_run(SPANS)) is None


def test_whole_run_reads_the_engine_it_built(monkeypatch):
    """A smoke run in a process whose registry holds only its engine:
    every step of the run loop has its record, so both readers read."""
    monkeypatch.setattr(T, "_registry",
                        collections.deque(maxlen=T.REGISTRY_SIZE))
    extra = [m for m in harness.load_benchmark()["per_layer"]
             if m["name"] in ("step_host_share.batch",
                              "step_compiles.batch")]
    plain = harness.metrics_of
    monkeypatch.setattr(harness, "metrics_of",
                        lambda b, c, t: plain(b, c, t) + extra)
    out = cpu_run.run(smoke.INTERNLM, seconds=3.0)
    share = out["metrics"]["step_host_share.batch"]["value"]
    assert 0.0 < share < 100.0
    assert out["metrics"]["step_compiles.batch"]["value"] >= 0
