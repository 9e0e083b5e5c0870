"""Continuous-batching admission pipeline: batched chunked prefill,
shared-prefix block cache with COW, row-level pool undo, sliding-window
block release, and budgeted requeue — the PR-4 invariants.

Token parity is the backbone: chunked prefill (prompt tokens as virtual
decode slots over the paged pools), whole-prompt serial prefill, and
prefix-cache-accelerated prefill must all continue the identical
position-seeded token stream.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.block_log import BlockLog, BlockManager
from repro.models.model import Model
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.request import Request
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import LocalScheduler


def _engine(tmp_path, name="internlm2-20b", sub="e", **over):
    cfg = get_smoke_config(name)
    if name == "qwen2-moe-a2.7b":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=4, num_redundant_experts=2, top_k=2,
            capacity_factor=8.0, min_capacity=64))
    if over.pop("windowed", False):
        cfg = dataclasses.replace(cfg, sliding_window=16)
    # token-exact comparisons across computation orders (chunked vs
    # serial, cached vs cold) need float32 weights
    over.setdefault("dtype", "float32")
    ec = EngineConfig(mode="collocated", num_dp=1, max_batch=4,
                      max_seq=over.pop("max_seq", 64), block_size=8,
                      num_blocks=64, workdir=str(tmp_path / sub),
                      sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                              seed=3),
                      **over)
    return cfg, InferenceEngine(cfg, ec)


def _serve(eng, cfg, prompts, max_new=8):
    reqs = [eng.submit(list(p), max_new) for p in prompts]
    eng.run(max_steps=400)
    assert all(r.state.value == "finished" for r in reqs), \
        [r.state for r in reqs]
    return [list(r.output_tokens) for r in reqs]


def _mixed_prompts(cfg, seed=1):
    rng = np.random.default_rng(seed)
    sysp = list(rng.integers(0, cfg.vocab_size, 20))
    return [list(rng.integers(0, cfg.vocab_size, 45)),   # long
            sysp + list(rng.integers(0, cfg.vocab_size, 5)),
            sysp + list(rng.integers(0, cfg.vocab_size, 9)),
            list(rng.integers(0, cfg.vocab_size, 3))]    # short


def test_chunked_equals_serial_token_parity(tmp_path):
    """Acceptance: the chunked token-budget admission pipeline produces
    exactly the tokens of the one-whole-prefill-per-step baseline, with
    and without the shared-prefix cache, on a mixed long/short workload
    (long prompts span several chunks and interleave with decodes)."""
    cfg, chunked = _engine(tmp_path, sub="c")
    _, nocache = _engine(tmp_path, sub="n", prefix_cache=False)
    _, serial = _engine(tmp_path, sub="s", admission="serial")
    prompts = _mixed_prompts(cfg)
    a = _serve(chunked, cfg, prompts)
    b = _serve(nocache, cfg, prompts)
    c = _serve(serial, cfg, prompts)
    assert a == b == c
    stats = chunked.prefill_stats()
    assert stats["prefill_chunks"] >= 2         # the 45-tok prompt chunked
    assert stats["prefill_tokens_cached"] > 0   # shared prefix hit


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "minicpm3-4b"])
def test_chunked_parity_across_archs(tmp_path, arch):
    """Same three-way parity on MoE (GQA) and MLA (fused latent pool)."""
    cfg, chunked = _engine(tmp_path, arch, sub="c")
    _, serial = _engine(tmp_path, arch, sub="s", admission="serial")
    prompts = _mixed_prompts(cfg)
    assert _serve(chunked, cfg, prompts) == _serve(serial, cfg, prompts)


def test_shared_prefix_cache_hits_and_cow_divergence(tmp_path):
    """A finished request's prompt blocks stay content-addressable: a
    later request sharing 2.5 blocks of prefix reuses the 2 full blocks
    by digest and COW-copies the half-shared divergence block — 20 of
    its prompt tokens skip prefill compute — while producing exactly the
    tokens of a cache-cold engine."""
    cfg, eng = _engine(tmp_path, sub="c")
    _, cold = _engine(tmp_path, sub="f", prefix_cache=False)
    rng = np.random.default_rng(7)
    sysp = list(rng.integers(0, cfg.vocab_size, 20))     # 2.5 blocks
    pa = sysp + list(rng.integers(0, cfg.vocab_size, 6))
    pb = sysp + list(rng.integers(0, cfg.vocab_size, 7))
    # serve A to completion first so its blocks are parked in the cache
    out_a = _serve(eng, cfg, [pa])
    assert eng.prefill_stats()["prefill_tokens_cached"] == 0
    assert eng.dp_executors[0].block_manager.num_cached > 0
    out_b = _serve(eng, cfg, [pb])
    stats = eng.prefill_stats()
    # blocks 0,1 full-match (16) + 4-token COW at the divergence block
    assert stats["prefill_tokens_cached"] == 20
    assert stats["prefix_cache_hits"] == 2      # the two full blocks
    assert _serve(cold, cfg, [pa]) == out_a
    assert _serve(cold, cfg, [pb]) == out_b
    # drained: every shared block was released exactly once
    ex = eng.dp_executors[0]
    assert ex.block_manager.num_allocated == 0
    ex.scheduler.check_consistent()


def test_fault_during_chunked_prefill_replays_exactly(tmp_path):
    """A mid-step device loss while a long prompt is mid-chunk rolls the
    step back (row-level pool undo) and replays the request elsewhere —
    the token stream must equal the no-fault reference."""
    from repro.core.fault_codes import ErrorType, Severity

    def build(sub):
        cfg = get_smoke_config("internlm2-20b")
        ec = EngineConfig(mode="collocated", num_dp=2, max_batch=2,
                          max_seq=96, block_size=8, num_blocks=64,
                          workdir=str(tmp_path / sub),
                          sampling=SamplingParams(temperature=0.8,
                                                  top_p=0.9, seed=5))
        return cfg, InferenceEngine(cfg, ec)

    cfg, ref = build("ref")
    rng = np.random.default_rng(9)
    prompts = [list(rng.integers(0, cfg.vocab_size, 60)),
               list(rng.integers(0, cfg.vocab_size, 58))]
    want = _serve(ref, cfg, prompts, max_new=6)

    _, eng = build("fault")
    # both ranks get one long prompt; rank 1 dies mid-step during the
    # chunked prefill (step 2: 60 tokens span >= 2 chunks of 32)
    eng.injector.schedule(2, 1, severity=Severity.L6,
                          error_type=ErrorType.HBM_ECC, component="attn",
                          mid_step=True)
    got = _serve(eng, cfg, prompts, max_new=6)
    assert got == want
    surviving = [ex for ex in eng.dp_executors if ex.alive]
    assert surviving and all(
        ex.block_manager.num_allocated == 0 for ex in surviving)


def test_migrate_prefix_shared_request(tmp_path):
    """KV-block streaming of a request whose leading blocks are
    ref-shared with a co-resident: the gather reads the shared blocks in
    place, the target continues the exact token stream, and the source's
    refcounts survive the departure (the co-resident keeps decoding)."""
    cfg, src = _engine(tmp_path, sub="src")
    _, ref = _engine(tmp_path, sub="ref")
    _, tgt = _engine(tmp_path, sub="tgt")
    rng = np.random.default_rng(11)
    sysp = list(rng.integers(0, cfg.vocab_size, 24))     # 3 full blocks
    pa = sysp + list(rng.integers(0, cfg.vocab_size, 4))
    pb = sysp + list(rng.integers(0, cfg.vocab_size, 5))

    # reference: pb served start-to-finish, unmigrated, uncached engine
    rb_ref = ref.submit(list(pb), 10)
    ref.run(max_steps=200)

    ra = src.submit(list(pa), 24)
    src.step()                       # pa prefilled, blocks registered
    rb = src.submit(list(pb), 10)
    for _ in range(3):
        src.step()
    assert 0 < len(rb.output_tokens) < 10
    ex = src.dp_executors[0]
    shared = [bid for bid in ex.scheduler.block_tables[rb.req_id].blocks
              if ex.block_manager.ref_count(bid) > 1]
    assert len(shared) >= 3          # the 3 sysp blocks are ref-shared

    kv = ex.export_kv_blocks(rb)
    assert kv is not None
    # departure releases rb's share of the blocks (the engine export
    # path drives this same drain); the co-resident keeps its refs
    ex.scheduler.running.remove(rb)
    ex.scheduler._release(rb, None)
    for bid in shared:
        assert ex.block_manager.ref_count(bid) >= 1   # pa still owns them
    ex.scheduler.check_consistent()

    assert tgt.dp_executors[0].import_kv_blocks(rb, kv)
    tgt.all_requests.append(rb)
    tgt.run(max_steps=100)
    assert rb.state.value == "finished"
    assert list(rb.output_tokens) == list(rb_ref.output_tokens)
    assert rb.recomputed_tokens == 0
    # pa unharmed by the departure
    src.run(max_steps=200)
    assert ra.state.value == "finished"


def test_window_occupancy_stays_o_window(tmp_path):
    """ROADMAP follow-up (b): sliding-window configs free blocks decode
    (and chunked prefill) has slid past — peak pool occupancy is
    O(window + chunk), independent of prompt length."""
    cfg, eng = _engine(tmp_path, windowed=True, max_seq=160)
    rng = np.random.default_rng(0)
    peaks = {}
    for P in (88, 120):
        r = eng.submit(list(rng.integers(0, cfg.vocab_size, P)), 24)
        peak = 0
        while eng.unfinished:
            eng.step()
            peak = max(peak,
                       eng.dp_executors[0].block_manager.num_allocated)
        assert r.state.value == "finished"
        peaks[P] = peak
    # window (16 tok) + chunk (32 tok) at block_size 8, +straddle slack
    assert peaks[88] == peaks[120] <= (16 + 32) // 8 + 2, peaks
    assert eng.prefill_stats()["blocks_window_freed"] > 0
    assert eng.dp_executors[0].block_manager.num_allocated == 0


def test_window_release_token_parity(tmp_path):
    """Freeing out-of-window blocks must never touch a position the
    current step still attends (the window lower bound is inclusive):
    a releasing engine and one with release disabled must produce the
    identical token stream across many block-boundary crossings."""
    cfg, rel = _engine(tmp_path, windowed=True, max_seq=128, sub="rel")
    _, keep = _engine(tmp_path, windowed=True, max_seq=128, sub="keep")
    for ex in keep.dp_executors:
        ex.scheduler.window = None        # reference: no release
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, 24)),
               list(rng.integers(0, cfg.vocab_size, 11))]
    a = _serve(rel, cfg, prompts, max_new=40)
    b = _serve(keep, cfg, prompts, max_new=40)
    assert a == b
    assert rel.prefill_stats()["blocks_window_freed"] > 0


def test_windowed_stream_migration_skips_dead_blocks():
    """KV-block export of a windowed request ships no rows for window-
    released table entries, and the import installs trash sentinels for
    them instead of burning real blocks — the target then continues the
    exact token stream."""
    from repro.models.model import Model
    from repro.serving import cache_ops
    from repro.serving.executor import DPExecutor

    cfg = dataclasses.replace(get_smoke_config("internlm2-20b"),
                              sliding_window=16)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    class Ctx:
        def __init__(self, ex):
            self.params, self.runtime, self.ex = (params,
                                                  model.default_runtime(),
                                                  ex)

        def decode_fn(self, p, c, t, page, rt):
            page = {k: jnp.asarray(v) for k, v in page.items()}
            return model.decode_step_paged(p, c, jnp.asarray(t), page, rt)

        def chunk_fn(self):
            return self.decode_fn

        def prefill_fn(self, b):
            def fn(p, t, l, rt):
                return model.prefill_paged(
                    p, {"tokens": jnp.asarray(t),
                        "lengths": jnp.asarray(l)}, rt)
            return fn

        def install_fn(self, b):
            def fn(c, raw, bids, slot):
                return cache_ops.install_prefill(
                    c, raw, self.ex.paged_axes, jnp.asarray(bids),
                    jnp.int32(slot))
            return fn

    def mk(pid):
        return DPExecutor(physical_id=pid, dp_rank=pid, model=model,
                          max_batch=2, max_seq=64, num_blocks=24,
                          block_size=4, sampling=SamplingParams())

    prompt = list(np.random.default_rng(3).integers(0, cfg.vocab_size, 20))

    def run_steps(ex, ctx, req, until_tokens):
        for step in range(64):
            if len(req.output_tokens) >= until_tokens:
                break
            ex.plan()
            ex.compute(ctx, step)
            ex.commit()

    # reference: decodes to the end unmigrated
    ex_ref = mk(0)
    ctx_ref = Ctx(ex_ref)
    r_ref = Request(list(prompt), 20)
    ex_ref.scheduler.add_request(r_ref)
    run_steps(ex_ref, ctx_ref, r_ref, 20)

    ex = mk(1)
    ctx = Ctx(ex)
    r = Request(list(prompt), 20)
    ex.scheduler.add_request(r)
    run_steps(ex, ctx, r, 12)     # decode well past the 16-token window
    kv = ex.export_kv_blocks(r)
    assert kv is not None and kv.live_mask is not None
    dead = kv.live_mask.count(False)
    assert dead > 0               # window release left trash sentinels

    tgt = mk(2)
    before = tgt.block_manager.num_allocatable
    assert tgt.import_kv_blocks(r, kv)
    spent = before - tgt.block_manager.num_allocatable
    assert spent < kv.num_blocks  # dead entries cost no real blocks
    tgt.scheduler.check_consistent()
    ctx_t = Ctx(tgt)
    run_steps(tgt, ctx_t, r, 20)
    assert r.output_tokens == r_ref.output_tokens


def test_requeue_accounts_against_token_budget():
    """Satellite: a rollback-requeued request re-admits through the
    budgeted chunked path — its re-prefill is charged like any arrival
    (the old scheduler requeued outside admission accounting)."""
    bm = BlockManager(num_blocks=32, block_size=4)
    sched = LocalScheduler(max_batch=2, max_seq=64, block_manager=bm,
                           token_budget=8, chunk_tokens=8)
    log = BlockLog()
    r = Request(list(range(20)), 4)
    sched.add_request(r)
    log.begin_step()
    plan = sched.plan_step(log)
    (piece,) = plan.chunks
    assert piece.length == 8                  # budget-capped first chunk
    r.prefill_pos = piece.start + piece.length
    log.begin_step()                          # commit

    # next step's chunk is planned, then the step faults and rolls back
    plan = sched.plan_step(log)
    (piece,) = plan.chunks
    assert (piece.start, piece.length) == (8, 8)
    log.undo_all(bm, sched.block_tables)
    assert sched.rollback_aborted() == []     # admitted earlier: survives
    assert r.prefill_pos == 8                 # compute never ran

    # a full export/requeue resets the request; its re-admission is
    # budget-capped again rather than planned as one whole prefill
    for req in sched.drain():
        sched.requeue_front(req)
    assert bm.num_allocated == 0
    log.begin_step()
    plan = sched.plan_step(log)
    (piece,) = plan.chunks
    assert piece.req is r and piece.length == 8
    total = sum(p.length for p in plan.chunks) + len(plan.decode)
    assert total <= 8                         # token budget holds


def test_window_release_unblocks_exhausted_pool_during_prefill():
    """A windowed long prompt whose lazy chunked prefill exhausts the
    pool must keep making progress by releasing its own out-of-window
    blocks before growing the table (no silent livelock)."""
    bm = BlockManager(num_blocks=6, block_size=4)
    sched = LocalScheduler(max_batch=1, max_seq=64, block_manager=bm,
                           chunk_tokens=8, window=16)
    r = Request(list(range(60)), 4)
    sched.add_request(r)
    log = BlockLog()
    log.begin_step()
    for _ in range(30):
        plan = sched.plan_step(log)
        for piece in plan.chunks:
            r.prefill_pos = piece.start + piece.length
        log.begin_step()
        if r.prefill_pos >= 60:
            break
    assert r.prefill_pos >= 60, "chunked prefill livelocked"
    assert sched.stats["blocks_window_freed"] > 0


def test_window_release_prevents_decode_pool_exhaustion():
    """Decode growth at a full pool must free the request's dead
    out-of-window block first instead of raising 'out of KV blocks'."""
    bm = BlockManager(num_blocks=3, block_size=4)
    sched = LocalScheduler(max_batch=1, max_seq=64, block_manager=bm,
                           chunk_tokens=8, window=8)
    r = Request(list(range(6)), 40)
    sched.add_request(r)
    log = BlockLog()
    log.begin_step()
    plan = sched.plan_step(log)
    (piece,) = plan.chunks
    r.prefill_pos = piece.start + piece.length
    log.begin_step()
    for _ in range(34):
        r.output_tokens.append(1)
        sched.plan_step(log)          # previously raised at num_tokens=12
        log.begin_step()
        assert bm.num_allocated <= 3
    sched.check_consistent()


def test_prefix_affinity_routing_unit():
    """Router admission: a repeated prompt prefix sticks to the instance
    that served it last — until that instance falls too far behind the
    least-loaded one."""
    from collections import OrderedDict

    from repro.fleet.router import FleetRouter

    class Inst:
        def __init__(self, iid, load):
            self.iid, self.load = iid, load

    r = FleetRouter.__new__(FleetRouter)
    r.prefix_affinity = True
    r._affinity = OrderedDict()
    a, b = Inst(0, 0), Inst(1, 0)
    p1 = list(range(40))
    first = r._route([a, b], p1)
    assert r._route([a, b], p1) is first      # sticky on equal load
    # a long prompt sharing only a short system prefix (< the longest
    # key) still matches through the prefix-length ladder
    shared_short = p1[:12] + list(range(900, 928))
    assert r._route([a, b], shared_short) is first
    # overload breaks affinity: the sticky instance is now far busier
    first.load = 10
    assert r._route([a, b], p1) is not first
    # affinity disabled -> pure least-loaded
    r.prefix_affinity = False
    a.load, b.load = 5, 1
    assert r._route([a, b], p1) is b
    # LRU bound: one-off prefixes age out individually, a periodically
    # re-seen hot key survives the churn
    r.prefix_affinity = True
    a.load = b.load = 0
    hot = tuple(range(40))
    r._route([a, b], list(hot))
    for i in range(FleetRouter._AFFINITY_MAP_MAX + 64):
        r._route([a, b], list(range(100 + i, 140 + i)))
        if i % 512 == 0:
            r._route([a, b], list(hot))
    assert hot[: FleetRouter.AFFINITY_LENS[0]] in r._affinity
    assert len(r._affinity) <= FleetRouter._AFFINITY_MAP_MAX


def test_rollback_aborted_preserves_fifo_order():
    """Two admissions in one aborted step must requeue in arrival
    order: requeue_front prepends, so rollback walks the aborted list
    in reverse (a forward walk would leave [B, A] and invert FIFO)."""
    bm = BlockManager(num_blocks=32, block_size=4)
    sched = LocalScheduler(max_batch=4, max_seq=64, block_manager=bm,
                           token_budget=64, chunk_tokens=32)
    log = BlockLog()
    ra = Request(list(range(10)), 4)
    rb = Request(list(range(10, 22)), 4)
    sched.add_request(ra)
    sched.add_request(rb)
    log.begin_step()
    plan = sched.plan_step(log)
    assert [p.req for p in plan.chunks] == [ra, rb]  # both admitted
    log.undo_all(bm, sched.block_tables)
    aborted = sched.rollback_aborted()
    assert {r.req_id for r in aborted} == {ra.req_id, rb.req_id}
    assert list(sched.waiting) == [ra, rb]           # FIFO preserved
    sched.check_consistent()
