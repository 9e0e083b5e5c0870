"""The end-to-end reductions: percentiles over every sample, rates over
the whole window, and requests due in the window but not yet served
counted at their wait so far."""
import pytest

from bench import readers, stats
from bench.harness import ReqRec, Run, StepRec
from bench.model import Shape
from bench.traffic.source import Arrival

SHAPE = Shape(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
              vocab=16, eps=1e-6, rope_theta=1e4, d_ff=32)


def _run(reqs, steps=(), seconds=10.0):
    run = Run({}, {}, {}, SHAPE, seconds, {"bf16_flops": 1e12,
                                           "hbm_bytes_per_s": 1e11}, 4)
    run.reqs = list(reqs)
    run.steps = list(steps)
    return run


def _req(at, token_t, left=None):
    return ReqRec(Arrival(at, [1, 2], 4), None, at, 2, left_waiting_t=left,
                  token_t=list(token_t))


def test_percentile_is_over_all_samples():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == pytest.approx(50.5)
    assert stats.percentile(vals, 90) == pytest.approx(90.1)
    assert stats.percentile([], 90) is None
    assert stats.percentile([3.0], 95) == 3.0


def test_rate_is_over_the_whole_window():
    assert stats.rate(120, 60.0) == 2.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_ttft_counts_unserved_requests_at_their_wait():
    run = _run([_req(-1.0, [0.5]),          # due before the window
                _req(1.0, [1.5, 2.0]),
                _req(2.0, [4.0]),
                _req(9.0, [])])             # no token by the window's end
    assert sorted(readers.ttft_s(run)) == pytest.approx([0.5, 1.0, 2.0])


def test_itl_takes_gaps_inside_the_window():
    run = _run([_req(-1.0, [-0.5, 0.25, 0.5]),
                _req(1.0, [1.5, 2.0, 3.0, 10.5])])
    assert sorted(readers.itl_s(run)) == pytest.approx([0.25, 0.5, 1.0])
    assert readers.tokens_in_window(run) == 5


def test_processed_tokens_count_prefill_of_window_steps():
    run = _run([_req(1.0, [1.5, 2.0, 10.5])],
               [StepRec(-0.5, -0.1, prefill_tokens=32),
                StepRec(0.1, 0.6, prefill_tokens=32),
                StepRec(9.8, 10.2, prefill_tokens=7)])
    assert readers.processed_in_window(run) == 32 + 2
    from bench.harness import reader
    assert reader("processed_tok_s.batch")(run) == pytest.approx(3.4)


def test_queue_wait_and_recovery():
    run = _run([_req(1.0, [2.0], left=1.5), _req(2.0, [], left=None)])
    assert sorted(readers.queue_wait_s(run)) == pytest.approx([0.5, 8.0])
    fault = StepRec(5.0, 5.1)
    run.steps = [StepRec(4.0, 4.9, tokens=3), fault,
                 StepRec(5.1, 5.4, tokens=0), StepRec(5.4, 5.9, tokens=2)]
    run.fault_step = fault
    assert readers.recovery_s(run) == pytest.approx(0.9)
    run.fault_step = None
    assert readers.recovery_s(run) is None


def test_step_mfu_and_occupancy_over_window_steps():
    steps = [StepRec(-0.5, 0.1, requests_served=4, flops=10 ** 12),
             StepRec(0.1, 0.6, requests_served=2, flops=10 ** 11),
             StepRec(0.6, 1.1, requests_served=4, flops=10 ** 11)]
    run = _run([], steps)
    assert readers.step_mfu(run) == pytest.approx(20.0)
    from bench.harness import reader
    assert reader("batch_occupancy")(run) == pytest.approx(75.0)


def test_checks_compare_each_named_number_with_its_limit():
    import numpy as np
    from bench.harness import _checks
    cfg = {"check": {"max_logit_gap": 0.5, "mean_logit_gap": 0.1,
                     "min_tokens": 5, "min_tokens.after": 2}}
    seqs = [([], 1, None, "before"), ([], 1, None, "after")]
    gaps = [np.array([0.0, 0.2, 0.0]), np.array([0.0, 0.6])]
    c = _checks(cfg, seqs, gaps, True)
    assert c["max_logit_gap"] == {"value": 0.6, "limit": 0.5, "ok": False}
    assert c["mean_logit_gap"]["value"] == pytest.approx(0.16)
    assert c["mean_logit_gap"]["ok"] is False
    assert c["tokens_checked"] == {"value": 5, "limit": 5, "ok": True}
    assert c["tokens_checked.after"]["value"] == 2
    assert _checks(cfg, [], [], True)["max_logit_gap"]["ok"] is False


def test_checks_hold_each_phase_to_its_own_limit():
    """A gap that opens only after the revive fails the ``.after`` mean,
    though the mean over the whole sample would hide it."""
    import numpy as np
    from bench.harness import _checks
    cfg = {"check": {"mean_logit_gap.before": 0.1,
                     "mean_logit_gap.after": 0.1,
                     "min_tokens.before": 4, "min_tokens.after": 2}}
    seqs = [([], 1, None, "before")] * 3 + [([], 1, None, "after")]
    gaps = [np.zeros(100)] * 3 + [np.array([0.0, 0.5, 0.4])]
    c = _checks(cfg, seqs, gaps, True)
    assert np.concatenate(gaps).mean() < 0.1
    assert c["mean_logit_gap.before"]["ok"] is True
    assert c["mean_logit_gap.after"]["value"] == pytest.approx(0.3)
    assert c["mean_logit_gap.after"]["ok"] is False
    assert c["tokens_checked.before"]["value"] == 300
    assert c["tokens_checked.after"] == {"value": 3, "limit": 2, "ok": True}
    # a fault that never fired leaves the after phase empty: not correct
    c = _checks(cfg, seqs[:3], gaps[:3], True)
    assert c["tokens_checked.after"]["ok"] is False
    assert c["mean_logit_gap.after"]["ok"] is False
    # a mix with no fault has no after phase to compare
    assert set(_checks(cfg, seqs[:3], gaps[:3], False)) == {
        "mean_logit_gap.before", "tokens_checked.before"}
