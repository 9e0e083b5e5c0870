"""End-to-end recovery integration tests (the paper's §3 pipeline).

Each test builds a small real engine, injects a hardware failure, and
checks both the recovery mechanics and that every request still finishes
with its tokens preserved.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.fault_codes import ErrorType, Severity
from repro.core.weights import MoERecoveryKind, RecoveryPolicy
from repro.serving.engine import EngineConfig, InferenceEngine


def small_moe_cfg(redundant=2, experts=4):
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=experts,
                                     num_redundant_experts=redundant,
                                     top_k=2))


def submit_all(eng, cfg, n=4, prompt_len=8, max_new=8):
    rng = np.random.default_rng(0)
    return [eng.submit(list(rng.integers(0, cfg.vocab_size, prompt_len)),
                       max_new) for _ in range(n)]


@pytest.fixture(scope="module")
def disagg():
    """Shared engine for the disaggregated scenarios (built once)."""
    cfg = small_moe_cfg(redundant=2)
    ec = EngineConfig(mode="disaggregated", num_dp=3, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir="/tmp/repro_test_disagg")
    return cfg, ec


def test_attention_failure_migrates_and_finishes(disagg, tmp_path):
    cfg, ec = disagg
    ec = dataclasses.replace(ec, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=5)
    eng.injector.schedule(3, 1, severity=Severity.L5,
                          error_type=ErrorType.DRIVER_HANG,
                          component="attn", mid_step=True)
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    assert len(eng.reports) == 1
    rep = eng.reports[0]
    assert rep.scenario == "attn"
    assert rep.migrated >= 1
    # the failed executor is isolated
    failed = next(ex for ex in eng.dp_executors if ex.physical_id == 1)
    assert not failed.alive
    # tokens preserved through migration: every migrated request kept
    # its prompt and its decoded prefix
    migrated = [r for r in reqs if r.migrations > 0]
    assert migrated
    for r in migrated:
        assert len(r.output_tokens) == r.max_new_tokens


@pytest.mark.slow
def test_moe_failure_role_switch(disagg, tmp_path):
    cfg, ec = disagg
    ec = dataclasses.replace(ec, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=4)
    # fail MoE rank 0 (pid = num_dp): its unreplicated experts force a
    # role switch (redundant covers only experts 0,1 of 4)
    eng.injector.schedule(3, 3, severity=Severity.L6, component="moe")
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    assert rep.moe_plan is not None
    assert rep.moe_plan.kind is MoERecoveryKind.ROLE_SWITCH
    # donor DP rank now hosts the failed EP rank's experts
    assert all(eng.shard_alive)
    # graph was precompiled for the failure scenario -> cached hit
    assert rep.compile_source == "precompiled"
    assert rep.timings.get("generator", 0) > 0  # weight reload from disk


def test_moe_failure_missing_experts_masks_routing(tmp_path):
    cfg = small_moe_cfg(redundant=0)
    ec = EngineConfig(mode="disaggregated", num_dp=2, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path),
                      policy=RecoveryPolicy(allow_role_switch=False,
                                            min_ep_for_missing=2))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=3)
    eng.injector.schedule(3, 3, severity=Severity.L6, component="moe")
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    assert rep.moe_plan.kind is MoERecoveryKind.MISSING_EXPERTS
    mask = np.asarray(eng.runtime.expert_mask)
    assert (~mask).sum() == 2      # EP rank 1's experts are masked
    # inference continued: the engine serves with the degraded expert set


@pytest.mark.slow
def test_collocated_failure_runs_both_paths(tmp_path):
    cfg = small_moe_cfg(redundant=4, experts=4)  # fully replicated
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=8, num_blocks=64, workdir=str(tmp_path),
                      policy=RecoveryPolicy(allow_role_switch=False))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=4)
    eng.injector.schedule(3, 1, severity=Severity.L6,
                          component="attn+moe", mid_step=True)
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    # collocated failure = attention migration AND expert recovery
    assert rep.migrated >= 1
    assert rep.moe_plan.kind is MoERecoveryKind.REDUNDANT_EXPERTS


@pytest.mark.slow
def test_benign_fault_is_ignored(tmp_path):
    cfg = small_moe_cfg()
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=8, num_blocks=64, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=2)
    eng.injector.schedule(2, 0, severity=Severity.L1,
                          error_type=ErrorType.OVER_TEMP, component="attn")
    eng.run(max_steps=100)
    assert all(r.state.value == "finished" for r in reqs)
    # L1 -> logged only; the device was never isolated
    assert all(ex.alive for ex in eng.dp_executors)
    reps = [r for r in eng.reports if r.scenario != "benign"]
    assert not reps


@pytest.mark.slow
def test_block_log_rolls_back_on_mid_step_failure(tmp_path):
    cfg = small_moe_cfg()
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=4, num_blocks=64, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=4, prompt_len=7, max_new=6)
    # fail device 1 mid-step while device 0 is also mid-step: device 0's
    # in-flight block ops must be rolled back (§3.3)
    eng.injector.schedule(2, 1, severity=Severity.L6,
                          component="attn+moe", mid_step=True)
    eng.run(max_steps=120)
    rep = eng.reports[0]
    assert rep.blocks_rolled_back > 0
    assert all(r.state.value == "finished" for r in reqs)
    # block accounting consistent on the survivor
    survivor = eng.dp_executors[0]
    assert survivor.block_manager.num_allocated == 0  # all finished+freed


@pytest.mark.slow
def test_heartbeat_detection_path(tmp_path):
    """A device that dies silently (no annotation) is caught by the
    heartbeat monitor after timeout_steps."""
    cfg = small_moe_cfg(redundant=4, experts=4)
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=8, num_blocks=64, workdir=str(tmp_path),
                      heartbeat_timeout_steps=2,
                      policy=RecoveryPolicy(allow_role_switch=False))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=3)
    # silent death: mark the device dead without any annotation
    eng.run(max_steps=2)
    victim = eng.dp_executors[1]
    victim.device_alive = False   # hardware hang, no fault code
    eng.run(max_steps=150)
    assert any(r.event.error_type is ErrorType.HEARTBEAT_TIMEOUT
               for r in eng.reports)
    assert all(r.state.value == "finished" for r in reqs)


@pytest.mark.slow
def test_background_role_switch(tmp_path):
    """§4.3: mask lost experts now (downtime = missing-experts level),
    restore full integrity via a deferred role switch while serving."""
    cfg = small_moe_cfg(redundant=0)
    ec = EngineConfig(mode="disaggregated", num_dp=3, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path),
                      policy=RecoveryPolicy(background_role_switch=True,
                                            min_ep_for_missing=2))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=4, max_new=16)
    eng.injector.schedule(3, 3, severity=Severity.L6, component="moe")
    eng.run(max_steps=200)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    assert rep.moe_plan.kind is MoERecoveryKind.ROLE_SWITCH
    assert rep.moe_plan.background
    # downtime excludes the weight reload (it happened in the background)
    assert rep.timings.get("generator", 0.0) == 0.0
    assert rep.timings.get("role_switch", 0.0) == 0.0
    # the background switch completed and restored full integrity
    assert eng.background_reports
    assert eng.background_reports[0]["restored_experts"] == 2
    assert eng.expert_map.coverage() == 1.0
    import numpy as np
    assert bool(np.asarray(eng.runtime.expert_mask).all())


@pytest.mark.slow
def test_dense_ffn_tp_group_rebalance(tmp_path):
    """§3.4: kimi-style first-k dense layers — losing an MoE device's
    dense-FFN shard (without role switch) compromises its TP group and
    rebalances token routing over the healthy groups."""
    cfg = get_smoke_config("kimi-k2-1t-a32b")   # first_k_dense = 1
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=4,
                                     num_redundant_experts=4, top_k=2,
                                     first_k_dense=1, dense_d_ff=256))
    ec = EngineConfig(mode="disaggregated", num_dp=2, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path),
                      policy=RecoveryPolicy(allow_role_switch=False,
                                            min_ep_for_missing=2))
    eng = InferenceEngine(cfg, ec)
    assert eng.dense_groups is not None
    reqs = submit_all(eng, cfg, n=3)
    eng.injector.schedule(3, 2, severity=Severity.L6, component="moe")
    eng.run(max_steps=150)
    assert all(r.state.value == "finished" for r in reqs)
    g = eng.dense_groups
    assert g.num_healthy() == g.num_groups - 1
    w = g.routing_weights()
    assert abs(sum(w) - 1.0) < 1e-9 and 0.0 in w
    assert any("dense-FFN TP group" in a for a in eng.reports[0].actions)


@pytest.mark.slow
def test_straggler_detection_and_isolation(tmp_path):
    """Slowdown handling (the paper's §6 future work, implemented): a
    device that silently slows 10x is detected by the straggler detector
    and isolated like a failed device; its sequences migrate."""
    cfg = small_moe_cfg(redundant=4, experts=4)
    ec = EngineConfig(mode="disaggregated", num_dp=3, num_moe=2,
                      max_batch=2, max_seq=96,
                      block_size=8, num_blocks=96, workdir=str(tmp_path),
                      policy=RecoveryPolicy(allow_role_switch=False))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=6, max_new=24)
    eng.run(max_steps=5)
    victim = eng.dp_executors[1]
    victim.simulated_slowdown_s = 1.0   # 10x+ the healthy step time
    eng.run(max_steps=250)
    assert all(r.state.value == "finished" for r in reqs)
    straggler_reports = [r for r in eng.reports
                         if "straggler" in r.event.detail]
    assert straggler_reports, [r.event for r in eng.reports]
    assert not victim.alive             # isolated
    assert straggler_reports[0].event.severity.name == "L4"


@pytest.mark.slow
def test_replica_rebalancing_follows_usage(tmp_path):
    """§3.4/§4.3: redundant replica slots re-point at the hottest experts
    (with weights copied), and the re-placement changes which failures
    are covered by redundancy."""
    cfg = small_moe_cfg(redundant=2, experts=4)   # replicas of 0,1 initially
    # 3 MoE ranks: bases on ranks 0-1, replica slots on rank 2 — so the
    # anti-affinity constraint can place any expert's replica
    ec = EngineConfig(mode="disaggregated", num_dp=2, num_moe=3,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    emap = eng.expert_map
    assert sorted(emap.replicas_of(0)) != [0]     # 0 starts replicated
    assert emap.replicas_of(3) == [3]             # 3 does not
    # usage says experts 3 and 2 are hottest
    moves = eng.rebalance_experts({0: 1, 1: 2, 2: 90, 3: 100})
    assert moves
    assert len(emap.replicas_of(3)) == 2
    assert len(emap.replicas_of(2)) == 2
    assert emap.replicas_of(0) == [0]
    # weights in the re-pointed slots are true copies
    for logical in (2, 3):
        slots = emap.replicas_of(logical)
        per = emap.slots_per_rank
        owners = [eng._shard_owner(emap.rank_of_slot(s)) for s in slots]
        for key in owners[0].shard:
            a = owners[0].shard[key][:, slots[0] % per]
            b = owners[1].shard[key][:, slots[1] % per]
            np.testing.assert_array_equal(a, b)
    # a failure hitting expert 3's base slot is now covered by redundancy
    rank_of_base3 = emap.rank_of_slot(3)
    emap.fail_rank(rank_of_base3)
    assert 3 not in emap.fully_lost()
    # serving still works end-to-end after the rebalance
    reqs = submit_all(eng, cfg, n=2)
    eng.run(max_steps=80)
    assert all(r.state.value == "finished" for r in reqs)


def test_dense_arch_attention_recovery(tmp_path):
    """Non-MoE architectures get the attention-side ReviveMoE paths:
    migration + block-log rollback + cached compile (DESIGN.md §4)."""
    cfg = get_smoke_config("internlm2-20b")
    ec = EngineConfig(mode="disaggregated", num_dp=3, max_batch=2,
                      max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    assert eng.expert_map is None and not eng.moe_executors
    reqs = submit_all(eng, cfg, n=4, max_new=10)
    eng.injector.schedule(3, 1, severity=Severity.L6, component="attn",
                          mid_step=True)
    eng.run(max_steps=150)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    assert rep.scenario == "attn"
    assert rep.migrated >= 1
    assert rep.compile_source == "precompiled"


@pytest.mark.slow
def test_hybrid_arch_serving_and_recovery(tmp_path):
    """Jamba-family serving: Mamba state + windowed attention caches ride
    the same executor machinery; recovery re-prefills state like KV
    (DESIGN.md §4: Mamba state is rank-local like KV)."""
    cfg = get_smoke_config("jamba-1.5-large-398b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    ec = EngineConfig(mode="disaggregated", num_dp=2, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=3, max_new=10)
    eng.injector.schedule(3, 1, severity=Severity.L6, component="attn",
                          mid_step=True)
    eng.run(max_steps=150)
    assert all(r.state.value == "finished" for r in reqs)
    assert eng.reports and eng.reports[0].migrated >= 1


@pytest.mark.slow
def test_ssm_arch_serving_and_recovery(tmp_path):
    """Attention-free falcon-mamba: no KV blocks to roll back, state
    rollback is the (free) discard of the uncommitted cache pytree."""
    cfg = get_smoke_config("falcon-mamba-7b")
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=8, num_blocks=64, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    reqs = submit_all(eng, cfg, n=3, max_new=10)
    eng.injector.schedule(3, 0, severity=Severity.L6, component="attn",
                          mid_step=True)
    eng.run(max_steps=150)
    assert all(r.state.value == "finished" for r in reqs)
    assert eng.reports and eng.reports[0].scenario == "attn"


def test_fused_moe_path_survives_fail_rank_and_mask(tmp_path):
    """ReviveMoE §3.4 on the fused Pallas pipeline: a failed expert rank
    (``fail_rank`` drops its replicas) plus ``mask_experts`` on the fully
    lost experts are pure MoERuntime mutations — the fused MoE step keeps
    serving from the same compiled graphs with zero fresh compilation."""
    cfg = small_moe_cfg(redundant=0)
    ec = EngineConfig(mode="disaggregated", num_dp=2, num_moe=2,
                      max_batch=2, max_seq=64, block_size=8, num_blocks=64,
                      workdir=str(tmp_path), moe_impl="fused",
                      policy=RecoveryPolicy(allow_role_switch=False,
                                            min_ep_for_missing=2))
    eng = InferenceEngine(cfg, ec)
    assert eng.cfg.moe_fused          # EngineConfig override took effect
    reqs = submit_all(eng, cfg, n=3)
    eng.injector.schedule(3, 3, severity=Severity.L6, component="moe")
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    rep = eng.reports[0]
    assert rep.moe_plan.kind is MoERecoveryKind.MISSING_EXPERTS
    # fail_rank dropped the dead rank's slots; mask_experts hides them
    mask = np.asarray(eng.runtime.expert_mask)
    assert (~mask).sum() == 2
    # zero recompiles: the post-failure graph came from the precompiled
    # cache and no real compilation happened during recovery
    assert rep.compile_source == "precompiled"
    assert rep.timings.get("compile", 0.0) < 0.01


def _leaves(params):
    # copies: a live zero-copy view of a CPU buffer would block donation
    import jax
    return [np.array(leaf) for leaf in jax.tree_util.tree_leaves(params)]


def test_bf16_restart_and_role_switch_reload_bit_exact(disagg, tmp_path):
    """The engine serves bfloat16 weights, and both disk paths bring them
    back bit-exact: a restart (full reinit) from ``weights.npz``, and a
    role switch that reloads one rank's shard file into the bank."""
    import jax
    import jax.numpy as jnp
    from repro.serving.weights_util import is_expert_leaf, split_experts
    cfg, ec = disagg
    ec = dataclasses.replace(ec, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    want = _leaves(eng.params)
    want_shards = split_experts(eng.params, eng.ep_size)
    before = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    assert all(a.dtype == jnp.bfloat16 for a in want)
    reqs = submit_all(eng, cfg, n=4)
    eng.injector.schedule(3, 3, severity=Severity.L6, component="moe")
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    assert eng.reports[0].moe_plan.kind is MoERecoveryKind.ROLE_SWITCH
    assert all(eng.shard_alive)
    # the donor reloaded ep-rank 0 from its shard file, bit-exact ...
    owner = eng._shard_owner(0)
    assert owner.physical_id != 3
    assert owner.shard is not eng.shards[0]
    assert set(owner.shard) == set(want_shards[0])
    for k, w in want_shards[0].items():
        assert owner.shard[k].dtype == w.dtype == jnp.bfloat16
        np.testing.assert_array_equal(owner.shard[k], w)
    # ... and wrote it into the device bank in place (old leaves donated)
    assert all(old.is_deleted() for path, old in before
               if is_expert_leaf(path))
    for a, b in zip(_leaves(eng.params), want):
        np.testing.assert_array_equal(a, b)
    eng.full_reinit()
    for a, b in zip(_leaves(eng.params), want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_collocated_revive_zeroes_dead_slice_in_place(tmp_path):
    """A revive changes only the dead rank's slice of the device expert
    bank, in place: the old bank buffers are donated (deleted), every
    other weight is the very same array, the dead slice reads zero and
    the live slice is untouched."""
    import jax
    from repro.serving.weights_util import is_expert_leaf
    cfg = small_moe_cfg(redundant=2)
    ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2, max_seq=64,
                      block_size=8, num_blocks=64, workdir=str(tmp_path))
    eng = InferenceEngine(cfg, ec)
    before = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    want = _leaves(eng.params)
    reqs = submit_all(eng, cfg, n=4)
    eng.injector.schedule(3, 1, severity=Severity.L6, component="moe",
                          mid_step=True)
    eng.run(max_steps=120)
    assert all(r.state.value == "finished" for r in reqs)
    assert eng.shard_alive == [True, False]
    after = jax.tree_util.tree_leaves(eng.params)
    per = eng.expert_map.slots_per_rank
    for (path, old), new, w in zip(before, after, want):
        if is_expert_leaf(path):
            assert old.is_deleted()
            new = np.asarray(new)
            np.testing.assert_array_equal(new[:, :per], w[:, :per])
            assert not np.asarray(new[:, per:], np.float32).any()
        else:
            assert new is old
