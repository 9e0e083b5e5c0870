"""Randomized fault-sequence fuzzing of the whole serving+recovery stack.

For arbitrary (seeded) schedules of device failures — any component, any
step, mid-step or boundary — the system must either finish every request
or degrade gracefully, and the host-side invariants must hold afterwards:

  * every non-failed request finished with exactly max_new_tokens,
  * block accounting consistent (all blocks freed once traffic drains),
  * expert-map runtime arrays consistent with slot liveness,
  * no executor serves while its device is dead.

This is the paper's reliability claim under test, beyond the
single-failure scenarios of Figure 5.
"""
import dataclasses

import numpy as np
import pytest

# randomized end-to-end engine runs: tier-2 only
pytestmark = pytest.mark.slow

from repro.configs import get_smoke_config
from repro.core.fault_codes import ErrorType, Severity
from repro.core.weights import RecoveryPolicy
from repro.serving.engine import EngineConfig, InferenceEngine

SEEDS = [0, 1, 2]


def build_engine(tmp_path, seed):
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=4,
                                     num_redundant_experts=4, top_k=2))
    ec = EngineConfig(mode="disaggregated", num_dp=3, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=96,
                      workdir=str(tmp_path),
                      policy=RecoveryPolicy(min_ep_for_missing=2))
    return cfg, InferenceEngine(cfg, ec)


@pytest.mark.parametrize("seed", SEEDS)
def test_random_fault_schedule(tmp_path, seed):
    rng = np.random.default_rng(seed)
    cfg, eng = build_engine(tmp_path / f"s{seed}", seed)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(4, 12)))),
                       max_new_tokens=int(rng.integers(4, 10)))
            for _ in range(6)]

    # random faults: 1-2 failures on random devices; never kill the last
    # attention rank (out of scope for ReviveMoE: whole-service loss)
    n_faults = int(rng.integers(1, 3))
    victims = rng.choice([1, 2, 3, 4], size=n_faults, replace=False)
    for v in victims:
        eng.injector.schedule(
            int(rng.integers(2, 8)), int(v),
            severity=Severity(int(rng.integers(3, 7))),
            error_type=ErrorType.HBM_ECC,
            component="moe" if v >= 3 else "attn",
            mid_step=bool(rng.integers(0, 2)))

    eng.run(max_steps=300)

    # every request completed despite the failures
    for r in reqs:
        assert r.state.value == "finished", (seed, r.req_id, r.state)
        assert len(r.output_tokens) == r.max_new_tokens

    # block accounting drained on every surviving executor
    for ex in eng.dp_executors:
        if ex.alive and ex.cache is not None:
            assert ex.block_manager.num_allocated == 0, (
                seed, ex.physical_id, ex.block_manager.num_allocated)
            assert ex.scheduler.num_requests == 0

    # expert runtime arrays consistent with the map's slot liveness
    if eng.expert_map is not None:
        emap = eng.expert_map
        rt = eng.runtime
        l2p = np.asarray(rt.logical_to_physical)
        count = np.asarray(rt.replica_count)
        for e in range(cfg.moe.num_experts):
            for i in range(count[e]):
                slot = l2p[e, i]
                assert emap.slot_alive[slot], (seed, e, slot)
                assert emap.slot_logical[slot] == e

    # dead devices never appear in the serving path
    for ex in eng.dp_executors:
        if not ex.device_alive:
            assert not ex.process_alive or ex.cache is None


@pytest.mark.parametrize("seed", [7])
def test_two_sequential_moe_failures(tmp_path, seed):
    """Second failure after a role switch: the switched rank's experts are
    covered again; losing the OTHER MoE rank must still recover."""
    rng = np.random.default_rng(seed)
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=4,
                                     num_redundant_experts=0, top_k=2))
    ec = EngineConfig(mode="disaggregated", num_dp=4, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=96,
                      workdir=str(tmp_path),
                      policy=RecoveryPolicy(min_ep_for_missing=2))
    eng = InferenceEngine(cfg, ec)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab_size, 8)), 20)
            for _ in range(6)]
    eng.injector.schedule(3, 4, severity=Severity.L6, component="moe")
    eng.injector.schedule(8, 5, severity=Severity.L6, component="moe")
    eng.run(max_steps=300)
    assert len(eng.reports) == 2
    assert all(r.state.value == "finished" for r in reqs)
    assert all(eng.shard_alive)  # both failures ended with every shard back
