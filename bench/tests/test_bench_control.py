"""The control of the correctness check at a size a test run holds: the
reference with its products rounded to float8, put through the harness's
own comparison, comes out not correct on the same served requests where
the bfloat16 program comes out correct.  (The cells' limits are set from
the chip's readings at their own sizes, ``bench/control.py``; at smoke
size the check's limits are set the same way from CPU readings,
``smoke.SMOKE_GAP``.)"""
import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("seed", [99, 5])
def test_float8_control_is_not_correct_where_bfloat16_is(seed):
    b, c, m = smoke.cell(smoke.INTERNLM)
    out = harness.run_cell(smoke.INTERNLM, seed, 3.0, False, bench=b, cfg=c,
                           mix=m, platform="cpu", control=True)
    assert out["correct"] is True, out["checks"]
    assert out["control"]["correct"] is False, out["control"]
    assert set(out["control"]["checks"]) == set(out["checks"])
    gap = out["control"]["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] == smoke.SMOKE_GAP
