"""Fleet control plane tests: router, spares, arbiter, and the seeded
cross-instance migration replay guarantee.

Exact replay precondition: the fleet is weight-identical (shared
checkpoint) and the MoE runs drop-free (capacity >= offered load), so a
token is a pure function of (seed, prefix, position) — batch
composition, executor, and *instance* all cancel out.  With capacity
dropping, replay after migration is best-effort (already-emitted tokens
are still never changed).
"""
import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.fault_codes import ErrorType, Severity
from repro.fleet import (CostModel, DiurnalTraffic, InstanceState,
                         PoissonTraffic, RecoveryArbiter, TraceTraffic,
                         build_fleet)
from repro.fleet.traffic import Arrival
from repro.serving.engine import EngineConfig
from repro.serving.sampling import SamplingParams


def fleet_cfg():
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    # drop-free MoE: the precondition for exact cross-instance replay
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=4,
                                     num_redundant_experts=2, top_k=2,
                                     capacity_factor=8.0,
                                     min_capacity=64))


def fleet_ecfg(workdir, **kw):
    base = dict(mode="disaggregated", num_dp=2, num_moe=2, max_batch=2,
                max_seq=64, block_size=8, num_blocks=64, workdir=workdir,
                # replayed and streamed migrations are compared token for
                # token with unmigrated runs: float32 weights
                dtype="float32")
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def shared_workdir(tmp_path_factory):
    # one workdir for every fleet in this module: all engines share the
    # same weights checkpoint + on-disk compile cache (weight-identical
    # fleet, fast warmup)
    return str(tmp_path_factory.mktemp("fleet"))


PROMPT = list(np.random.default_rng(3).integers(0, 512, 9))


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_cross_instance_migration_exact_replay(shared_workdir,
                                               temperature):
    """Acceptance: a request migrated across instances mid-generation
    produces the exact token sequence of an unmigrated run."""
    sp = SamplingParams(temperature=temperature, top_p=0.9, seed=5)
    ecfg = fleet_ecfg(shared_workdir, sampling=sp)
    cfg = fleet_cfg()

    ref_fleet = build_fleet(cfg, ecfg, instances=1)
    ref = ref_fleet.submit(PROMPT, 14)
    ref_fleet.run(max_ticks=120)
    assert ref.state.value == "finished"

    fleet = build_fleet(cfg, ecfg, instances=2)
    req = fleet.submit(PROMPT, 14)
    for _ in range(5):
        fleet.tick()
    mid = len(req.output_tokens)
    assert 0 < mid < 14, "fault must land mid-generation"
    src = req.instance_id
    fleet.lose_instance(src, "test: host loss mid-generation")
    fleet.run(max_ticks=250)

    assert req.state.value == "finished"
    assert req.cross_instance_migrations == 1
    assert req.instance_id != src
    assert req.output_tokens == ref.output_tokens
    # the arbiter knew revive was impossible for a lost instance
    dec = fleet.arbiter.decisions[-1]
    assert dec.policy in ("restart", "spare")
    assert "impossible" in dec.reason or "forced" in dec.reason


def test_router_least_loaded_admission_and_drain(shared_workdir):
    fleet = build_fleet(fleet_cfg(), fleet_ecfg(shared_workdir),
                        instances=2)
    r1 = fleet.submit(PROMPT, 4)
    r2 = fleet.submit(PROMPT, 4)
    assert {r1.instance_id, r2.instance_id} == {0, 1}
    # a draining instance accepts no new work
    fleet.instances[0].state = InstanceState.DRAINING
    r3 = fleet.submit(PROMPT, 4)
    assert r3.instance_id == 1
    fleet.instances[0].state = InstanceState.SERVING
    fleet.run(max_ticks=120)
    assert all(r.state.value == "finished" for r in (r1, r2, r3))
    # TTFT metrics recorded on the virtual clock
    assert len(fleet.ttfts()) == 3
    assert all(t >= 0 for t in fleet.ttfts())


def test_straggler_soft_signal_drains_instance(shared_workdir):
    """Satellite: StragglerDetector output flows engine.health() ->
    arbiter soft pass -> proactive drain (no spare available)."""
    # soft_patience=1 so the proactive path wins the race against the
    # engine's own hard straggler isolation (patience 2 engine steps);
    # num_dp=3 because with 2 ranks a straggler drags the fleet median
    # up and the ratio rule mathematically cannot fire
    fleet = build_fleet(fleet_cfg(),
                        fleet_ecfg(shared_workdir, num_dp=3, max_batch=1),
                        instances=2, spares=0, soft_patience=1)
    # traffic on every rank of both engines so step-time samples
    # accumulate fleet-wide
    reqs = [fleet.submit(PROMPT, 24) for _ in range(6)]
    for _ in range(6):
        fleet.tick()
    victim = fleet.instances[0].engine.dp_executors[1]
    victim.simulated_slowdown_s = 1.0
    for _ in range(30):
        fleet.tick()
        if any(d.proactive for d in fleet.arbiter.decisions):
            break
    soft = [d for d in fleet.arbiter.decisions if d.proactive]
    assert soft, "soft signal never reached the arbiter"
    assert soft[0].instance_id == 0
    assert "straggler" in soft[0].reason
    # no spare -> the instance drains instead of substituting
    assert fleet.instances[0].state in (InstanceState.DRAINING,
                                        InstanceState.SERVING)
    fleet.run(max_ticks=400)
    assert all(r.state.value == "finished" for r in reqs)


@pytest.mark.slow
def test_spare_substitution_on_device_fault(shared_workdir):
    """A forced-spare arbitration: device fault -> live migration to a
    pre-warmed standby, wounded instance decommissioned."""
    fleet = build_fleet(fleet_cfg(), fleet_ecfg(shared_workdir),
                        instances=2, spares=1, force_policy="spare")
    assert fleet.spares.available == 1
    reqs = [fleet.submit(PROMPT, 12) for _ in range(4)]
    # MoE device on instance 0 dies mid-step at its engine step 3
    fleet.instances[0].engine.injector.schedule(
        3, 2, severity=Severity.L6, error_type=ErrorType.HBM_ECC,
        component="moe", mid_step=True)
    fleet.run(max_ticks=300)
    assert all(r.state.value == "finished" for r in reqs)
    assert fleet.instances[0].state is InstanceState.DEAD
    assert fleet.spares.available == 0 and fleet.spares.activations == 1
    spare_ids = [iid for iid in fleet.instances if iid >= 1000]
    assert spare_ids, "spare never joined the serving set"
    migrated = [r for r in reqs if r.cross_instance_migrations > 0]
    assert migrated
    assert any(d.policy == "spare" for d in fleet.arbiter.decisions)


@pytest.mark.slow
def test_open_loop_traffic_all_finish(shared_workdir):
    traffic = PoissonTraffic(200.0, 512, prompt_len=6, max_new_tokens=6,
                             seed=1, limit=10)
    fleet = build_fleet(fleet_cfg(), fleet_ecfg(shared_workdir),
                        instances=2, traffic=traffic)
    fleet.run(max_ticks=400)
    assert traffic.exhausted
    assert len(fleet.requests) == 10
    assert fleet.unfinished == 0


def test_arbiter_cost_model_decisions():
    """Pure cost-model arithmetic: no engines involved."""
    cm = CostModel({"engine": 0.1, "generator": 2.0, "xccl": 0.01,
                    "read_cache": 0.02, "compile": 0.5},
                   spare_opportunity_cost_s=10.0)
    # seeds: restart ~2.63s, revive ~0.03s
    assert cm.est_revive_s() < 0.1 < cm.est_restart_s()
    arb = RecoveryArbiter(cm)
    inst = SimpleNamespace(iid=7, load=3,
                           engine=SimpleNamespace(all_requests=[]))
    dec = arb.decide(inst, None, spare_available=True)
    assert dec.policy == "revive"          # cheapest by far
    dec = arb.decide(inst, None, spare_available=True, instance_lost=True)
    assert dec.policy != "revive"
    dec = arb.decide(inst, None, spare_available=False,
                     instance_lost=True)
    assert dec.policy == "restart"
    # measurements replace seeds: an expensive revive flips the decision
    cm.observe_revive({"total_s": 50.0})
    cm.observe_restart(0.2)
    dec = arb.decide(inst, None, spare_available=False)
    assert dec.policy == "restart"
    # forced policy wins when feasible
    arb2 = RecoveryArbiter(cm, force_policy="spare")
    assert arb2.decide(inst, None, spare_available=True).policy == "spare"
    assert arb2.decide(inst, None,
                       spare_available=False).policy != "spare"
    with pytest.raises(ValueError):
        RecoveryArbiter(cm, force_policy="bogus")


def test_cost_model_stream_and_quality_pricing():
    """Satellite: the cost model prices spare substitution on its real
    mechanics (KV blocks streamed vs tokens re-prefilled) and revive on
    stall *plus* degraded quality (masked-expert fraction)."""
    cm = CostModel({"engine": 1.0}, per_token_prefill_s=1e-3,
                   per_block_stream_s=1e-5,
                   degraded_quality_weight_s=2.0,
                   spare_opportunity_cost_s=0.0)
    # streaming 1024 tokens as 64 blocks is ~three orders cheaper than
    # re-prefilling them
    replay = cm.est_spare_s(1024, 0)
    stream = cm.est_spare_s(0, 64)
    assert stream < replay / 100
    # streamed-cost estimate is ~flat in prefix length, replay is linear
    assert cm.est_spare_s(0, 256) - cm.est_spare_s(0, 64) < 0.01 * (
        cm.est_spare_s(4096, 0) - cm.est_spare_s(1024, 0))
    # degraded quality: half the experts masked adds a real stall-
    # equivalent term to revive
    assert cm.quality_cost_s(0.0) == 0.0
    assert cm.quality_cost_s(0.5) == pytest.approx(1.0)
    # measurement feedback discounts both migration terms from the swap
    cm.observe_spare(0.5, tokens=100, streamed_blocks=100)
    assert cm.spare_swap.value == pytest.approx(0.5 - 0.1 - 1e-3)


def test_arbiter_prices_degraded_quality_into_revive():
    """A fault whose experts have no surviving replica makes revive pay
    the quality term; with full redundancy it doesn't."""
    cm = CostModel({"engine": 0.1}, degraded_quality_weight_s=50.0,
                   spare_opportunity_cost_s=10.0)
    cm.observe_revive({"total_s": 0.02})
    cm.observe_restart(0.5)
    arb = RecoveryArbiter(cm)
    ev = SimpleNamespace(rank=3)

    def inst(mask_frac):
        return SimpleNamespace(
            iid=1, load=2,
            engine=SimpleNamespace(
                all_requests=[],
                streamable_split=lambda: (0, 0),
                predict_masked_fraction=lambda rank: mask_frac,
                ecfg=SimpleNamespace(block_size=8)))

    covered = arb.decide(inst(0.0), ev, spare_available=False)
    assert covered.policy == "revive"
    degraded = arb.decide(inst(0.5), ev, spare_available=False)
    assert degraded.policy == "restart"      # quality term flipped it
    assert "masked" in degraded.reason
    assert degraded.est_cost["revive"] > covered.est_cost["revive"]


@pytest.mark.slow
def test_spare_pool_background_replenish(shared_workdir):
    """Satellite (ROADMAP a): after an activation the pool rebuilds a
    standby in the background instead of shrinking; KV-block streaming
    keeps the migrated request token-exact with zero recompute."""
    from repro.core.fault_codes import ErrorType, Severity
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=5)
    ecfg = fleet_ecfg(shared_workdir, sampling=sp)
    cfg = fleet_cfg()
    ref_fleet = build_fleet(cfg, ecfg, instances=1)
    ref = ref_fleet.submit(PROMPT, 14)
    ref_fleet.run(max_ticks=150)

    fleet = build_fleet(cfg, ecfg, instances=2, spares=1,
                        force_policy="spare", replenish_spares=True)
    req = fleet.submit(PROMPT, 14)
    for _ in range(5):
        fleet.tick()
    assert 0 < len(req.output_tokens) < 14
    eng = fleet.instances[req.instance_id].engine
    eng.injector.schedule(eng.step_no + 1, 3, severity=Severity.L6,
                          error_type=ErrorType.HBM_ECC, component="moe",
                          mid_step=True)
    fleet.run(max_ticks=300)
    assert req.state.value == "finished"
    assert req.output_tokens == ref.output_tokens
    # streamed takeover: the prefix was never re-prefilled
    assert req.cross_instance_migrations == 1
    assert req.recomputed_tokens == 0
    # the pool self-healed: one activation, one background replenishment
    assert fleet.spares.activations == 1
    assert fleet.spares.replenishments == 1
    assert fleet.spares.available == fleet.spares.target_size == 1
    assert any("replenished" in line for line in fleet.log)


def test_traffic_sources_deterministic():
    a = PoissonTraffic(100.0, 512, seed=9, limit=5)
    b = PoissonTraffic(100.0, 512, seed=9, limit=5)
    got_a = a.due(10.0)
    got_b = b.due(10.0)
    assert [x.at_s for x in got_a] == [x.at_s for x in got_b]
    assert [x.prompt_tokens for x in got_a] == [
        x.prompt_tokens for x in got_b]
    assert a.exhausted
    tr = TraceTraffic([Arrival(0.5, (1, 2), 4), Arrival(0.1, (3,), 4)])
    assert [x.at_s for x in tr.due(0.2)] == [0.1]
    assert [x.at_s for x in tr.due(9.0)] == [0.5]
    assert tr.exhausted
    with pytest.raises(ValueError):
        PoissonTraffic(0.0, 512)


def test_traffic_lognormal_lengths_seeded_and_heavy_tailed():
    """length_dist='lognormal' turns the configured prompt/output shape
    into medians of seeded heavy-tailed draws (campaign realism): same
    seed -> identical stream, lengths spread around the median with a
    real upper tail, clamps honored, fixed path untouched."""
    import numpy as np

    def stream(seed=9):
        t = PoissonTraffic(200.0, 512, seed=seed, limit=400,
                           prompt_len=8, max_new_tokens=8,
                           length_dist="lognormal", length_sigma=0.75,
                           max_prompt_len=64, max_output_len=48)
        return t.due(1e9)

    got = stream()
    same = stream()
    assert [(a.at_s, a.prompt_tokens, a.max_new_tokens) for a in got] \
        == [(a.at_s, a.prompt_tokens, a.max_new_tokens) for a in same]

    plens = np.array([len(a.prompt_tokens) for a in got])
    outs = np.array([a.max_new_tokens for a in got])
    for xs, cap in ((plens, 64), (outs, 48)):
        assert xs.min() >= 1 and xs.max() <= cap
        assert 6 <= np.median(xs) <= 10          # median ~ configured 8
        assert xs.max() >= 3 * np.median(xs)     # heavy upper tail
        assert len(set(xs.tolist())) > 5         # not a fixed shape

    # fixed path: no heavy-tail draws, shapes exactly as configured
    fixed = PoissonTraffic(200.0, 512, seed=9, limit=50,
                           prompt_len=(4, 8), max_new_tokens=6)
    for a in fixed.due(1e9):
        assert len(a.prompt_tokens) in (4, 8)
        assert a.max_new_tokens == 6

    # diurnal variant inherits the knobs
    d = DiurnalTraffic(50.0, 512, amplitude=0.5, period_s=10.0, seed=4,
                       limit=100, length_dist="lognormal")
    dlens = {len(a.prompt_tokens) for a in d.due(1e9)}
    assert len(dlens) > 3

    with pytest.raises(ValueError):
        PoissonTraffic(1.0, 512, length_dist="gauss")
    with pytest.raises(ValueError):
        PoissonTraffic(1.0, 512, length_dist="lognormal",
                       length_sigma=0.0)


def test_engine_config_validation_raises_value_error():
    """Satellite: config validation survives `python -O` (ValueError,
    not assert) and names the offending field."""
    with pytest.raises(ValueError, match="EngineConfig.mode"):
        EngineConfig(mode="sharded")
    with pytest.raises(ValueError, match="EngineConfig.num_dp"):
        EngineConfig(num_dp=0)
    with pytest.raises(ValueError, match="EngineConfig.num_moe"):
        EngineConfig(num_moe=-1)
    with pytest.raises(ValueError, match="EngineConfig.block_size"):
        EngineConfig(block_size=0)
    with pytest.raises(ValueError, match="heartbeat_timeout_steps"):
        EngineConfig(heartbeat_timeout_steps=0)
    with pytest.raises(ValueError, match="EngineConfig.moe_impl"):
        EngineConfig(moe_impl="turbofused")
    EngineConfig(moe_impl="fused")          # valid value still accepted
