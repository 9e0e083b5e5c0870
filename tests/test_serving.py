"""Serving substrate unit tests: scheduler, sampling, cache ops,
checkpoint, migration planning."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.block_log import BlockLog, BlockManager
from repro.core.migration import plan_migration, prepare_for_migration
from repro.models.model import Model
from repro.serving.cache_ops import infer_batch_axes, read_slot, write_slot
from repro.serving.request import Request, RequestState
from repro.serving.sampling import SamplingParams, sample
from repro.serving.scheduler import LocalScheduler


def _prefill_done(*reqs):
    """Simulate the compute phase completing each request's prefill."""
    for r in reqs:
        r.prefill_pos = len(r.tokens_so_far)


def test_scheduler_admission_and_block_accounting():
    bm = BlockManager(num_blocks=8, block_size=4)
    sched = LocalScheduler(max_batch=2, max_seq=32, block_manager=bm)
    log = BlockLog()
    r1 = Request(list(range(6)), max_new_tokens=4)   # needs 2 blocks
    r2 = Request(list(range(3)), max_new_tokens=4)
    r3 = Request(list(range(3)), max_new_tokens=4)
    for r in (r1, r2, r3):
        sched.add_request(r)
    log.begin_step()
    # multi-admission: both slots fill in one step; r3 must wait
    plan = sched.plan_step(log)
    assert plan.prefills == [r1, r2]
    assert sched.block_tables[r1.req_id].num_blocks() == 2
    assert r3.state is RequestState.WAITING
    _prefill_done(r1, r2)
    plan = sched.plan_step(log)
    assert plan.prefill is None                      # max_batch=2: no slot
    assert plan.decode == [r1, r2]


def test_scheduler_budget_caps_admissions_per_step():
    """The per-step token budget admits prompts until the budget runs
    out; the first prefill may overflow it (long prompts must admit)."""
    bm = BlockManager(num_blocks=16, block_size=4)
    sched = LocalScheduler(max_batch=4, max_seq=64, block_manager=bm,
                           token_budget=10)
    log = BlockLog()
    long = Request(list(range(12)), 4)     # 12 tokens > budget: admits alone
    s1 = Request(list(range(4)), 4)
    s2 = Request(list(range(4)), 4)
    for r in (long, s1, s2):
        sched.add_request(r)
    log.begin_step()
    plan = sched.plan_step(log)
    assert plan.prefills == [long]         # overflow allowed only first
    _prefill_done(long)
    plan = sched.plan_step(log)
    # 1 decode token + 4 + 4 prefill tokens <= 10
    assert plan.decode == [long] and plan.prefills == [s1, s2]


def test_scheduler_decode_allocates_on_boundary():
    bm = BlockManager(num_blocks=8, block_size=4)
    sched = LocalScheduler(max_batch=1, max_seq=32, block_manager=bm)
    log = BlockLog()
    r = Request([0, 1, 2, 3], max_new_tokens=8)      # fills block exactly
    sched.add_request(r)
    sched.plan_step(log)
    assert sched.block_tables[r.req_id].num_blocks() == 2  # +1 for next tok
    _prefill_done(r)
    used = bm.num_allocated
    r.output_tokens.extend([5, 6, 7])                # positions 4,5,6
    sched.plan_step(log)                             # pos 7 fits block 2
    assert bm.num_allocated == used
    r.output_tokens.append(8)                        # next pos 8 -> block 3
    sched.plan_step(log)
    assert sched.block_tables[r.req_id].num_blocks() == 3


def test_finish_releases_everything():
    bm = BlockManager(8, 4)
    sched = LocalScheduler(2, 32, bm)
    log = BlockLog()
    r = Request([1, 2, 3], 2)
    sched.add_request(r)
    sched.plan_step(log)
    sched.finish(r, log)
    assert bm.num_allocated == 0
    assert sched.num_requests == 0
    assert r.batch_slot is None


def test_sampling_deterministic_and_greedy():
    logits = np.array([[0.1, 3.0, -1.0], [2.0, 0.0, 0.1]])
    out = sample(logits, SamplingParams(temperature=0.0))
    np.testing.assert_array_equal(out, [1, 0])
    p = SamplingParams(temperature=1.0, seed=7)
    a = sample(logits, p, step=3)
    b = sample(logits, p, step=3)
    np.testing.assert_array_equal(a, b)


def test_cache_slot_roundtrip():
    cfg = get_smoke_config("internlm2-20b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    axes = infer_batch_axes(model, max_seq=16)
    cache = model.init_cache(3, 16)
    batch = {"tokens": jnp.arange(8)[None, :] % cfg.vocab_size,
             "lengths": jnp.array([8], jnp.int32)}
    _, sub = model.prefill(params, batch, max_seq=16)
    cache2 = write_slot(cache, sub, 1, axes)
    back = read_slot(cache2, 1, axes)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(sub)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b, a.dtype),
                                   rtol=1e-6)
    # slot 0 untouched
    z = read_slot(cache2, 0, axes)
    assert all(float(jnp.abs(x).sum()) == 0.0
               for x in jax.tree_util.tree_leaves(z)
               if x.dtype != jnp.int32)


def test_migration_planning_balances_load():
    reqs = [Request(list(range(4)), 4) for _ in range(6)]
    for r in reqs:
        r.state = RequestState.RUNNING
    loads = {0: 2, 1: 0, 2: 5}
    assignment = plan_migration(reqs, loads)
    counts = {0: 0, 1: 0, 2: 0}
    for _, rank in assignment:
        counts[rank] += 1
    assert counts[1] > counts[2]
    # partial recomputation accounting
    r = reqs[0]
    r.output_tokens = [9, 9]
    prepare_for_migration(r)
    assert r.state is RequestState.MIGRATING
    assert r.migrations == 1
    assert r.recomputed_tokens == 6
    assert r.tokens_so_far == list(range(4)) + [9, 9]


def test_checkpoint_roundtrip(tmp_path):
    from repro.training.checkpoint import restore_like, save_checkpoint
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "w.npz")
    save_checkpoint(path, params)
    restored = restore_like(path, jax.eval_shape(lambda: params))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_expert_shard_split_assemble_roundtrip():
    """Host shards slice the bank exactly; zeroing a rank's slice and
    writing its shard back, both in place, restores the bank bit-exact
    and touches nothing else."""
    from repro.serving.weights_util import (bank_programs,
                                            expert_leaf_keys,
                                            is_expert_leaf, split_experts,
                                            update_rank_slice)
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    want = [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]
    shards = split_experts(params, ep_size=2)
    keys = expert_leaf_keys(params)
    assert keys and all(set(sh) == set(keys) for sh in shards)
    flat = dict((p, l) for p, l in
                jax.tree_util.tree_flatten_with_path(params)[0])
    E = next(l for p, l in flat.items() if is_expert_leaf(p)).shape[1]
    per = E // 2
    progs = {key: jax.jit(fn, donate_argnums=0).lower(*specs).compile()
             for key, (fn, specs) in bank_programs(params, 2).items()}
    # dead shard -> zeros in its slice, rest intact
    half = update_rank_slice(params, progs.__getitem__, 1, per)
    for (p, l), w in zip(jax.tree_util.tree_flatten_with_path(half)[0],
                         want):
        l = np.asarray(l)
        if is_expert_leaf(p):
            assert np.abs(l[:, per:].astype(np.float32)).sum() == 0.0
            np.testing.assert_array_equal(l[:, :per], w[:, :per])
            np.testing.assert_array_equal(
                l[:, per:], np.zeros_like(w[:, per:]))
        else:
            np.testing.assert_array_equal(l, w)
    together = update_rank_slice(half, progs.__getitem__, 1, per, shards[1])
    for a, b in zip(jax.tree_util.tree_leaves(together), want):
        np.testing.assert_array_equal(np.asarray(a), b)

# -- LocalScheduler edge cases (the invariants cross-instance migration
# -- relies on): exhausted block pool, rollback-then-requeue consistency


def test_admission_deferred_when_block_pool_exhausted():
    """A request whose prefill cannot get enough blocks mid-stream stays
    WAITING (never half-admitted) and admits once blocks free up."""
    bm = BlockManager(num_blocks=4, block_size=4)
    sched = LocalScheduler(max_batch=2, max_seq=32, block_manager=bm)
    log = BlockLog()
    hog = Request(list(range(12)), max_new_tokens=4)    # needs 4 blocks
    late = Request(list(range(9)), max_new_tokens=4)    # needs 3 blocks
    sched.add_request(hog)
    sched.add_request(late)
    log.begin_step()
    plan = sched.plan_step(log)
    assert plan.prefill is hog and bm.num_free == 0
    # pool exhausted: late must NOT be admitted (no partial allocation)
    plan = sched.plan_step(log)
    assert plan.prefill is None
    assert late.state is RequestState.WAITING
    assert late.req_id not in sched.block_tables
    assert late.batch_slot is None
    sched.check_consistent()
    # finishing the hog frees its blocks; late admits cleanly
    sched.finish(hog, log)
    plan = sched.plan_step(log)
    assert plan.prefill is late
    assert sched.block_tables[late.req_id].num_blocks() == 3
    sched.check_consistent()


def test_rollback_then_requeue_keeps_slots_and_tables_consistent():
    """§3.3 rollback of an aborted admission must return the batch slot
    and block table exactly; requeue_front preserves FIFO-with-priority
    ordering.  (DPExecutor.rollback_inflight drives the same path.)"""
    bm = BlockManager(num_blocks=8, block_size=4)
    sched = LocalScheduler(max_batch=2, max_seq=32, block_manager=bm)
    log = BlockLog()
    r1 = Request(list(range(4)), max_new_tokens=4)
    r2 = Request(list(range(4)), max_new_tokens=4)
    sched.add_request(r1)
    log.begin_step()
    sched.plan_step(log)                    # admits r1
    _prefill_done(r1)
    log.begin_step()                        # commit r1's step
    free_before = bm.num_free
    slots_before = sorted(sched._free_slots)
    sched.add_request(r2)
    sched.plan_step(log)                    # admits r2 (uncommitted)
    # mid-step failure: undo r2's block ops, then requeue it
    log.undo_all(bm, sched.block_tables)
    aborted = sched.rollback_aborted()
    assert aborted == [r2]
    assert bm.num_free == free_before
    assert sorted(sched._free_slots) == slots_before
    assert sched.waiting[0] is r2           # requeued at the front
    assert r2.state is RequestState.WAITING
    sched.check_consistent()
    # the requeued request admits again on the next step
    plan = sched.plan_step(log)
    assert plan.prefill is r2
    sched.check_consistent()


def test_check_consistent_catches_corruption():
    bm = BlockManager(8, 4)
    sched = LocalScheduler(2, 32, bm)
    log = BlockLog()
    r = Request([1, 2, 3], 2)
    sched.add_request(r)
    log.begin_step()
    sched.plan_step(log)
    sched.check_consistent()
    sched._free_slots.append(r.batch_slot)   # corrupt: slot double-owned
    with pytest.raises(AssertionError, match="free and in use"):
        sched.check_consistent()


def test_sampling_per_row_positions_match_scalar():
    """Vector step: each row draws from its own (seed, step) stream, so
    a row's token is independent of its batch company — the property
    cross-instance replay depends on."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(3, 16))
    p = SamplingParams(temperature=0.7, top_p=0.9, seed=11)
    batched = sample(logits, p, step=np.array([5, 9, 2]))
    for i, pos in enumerate([5, 9, 2]):
        solo = sample(logits[i:i + 1], p, step=pos)
        assert batched[i] == solo[0]


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the graph cache uses it and
    sets no directory in code; without it, one fixed directory inside the
    checkout (listed in .gitignore), never one derived from a workdir."""
    import os
    from repro.core import graph_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert graph_cache.GraphCache().persist_dir == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    gc_ = graph_cache.GraphCache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert gc_.persist_dir == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == gc_.persist_dir
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_graph_cache_lists_compiles_and_counts_hits():
    """``timings`` holds one entry per compile; a lookup that finds a
    ready executable only counts a hit, and returns its own timing."""
    from repro.core import graph_cache
    gc_ = graph_cache.GraphCache(persist=False)
    spec = (jax.ShapeDtypeStruct((3,), jnp.float32),)
    fn, tm = gc_.get_or_compile(("k",), lambda a: a + 1, spec)
    assert tm.source == "cold" and gc_.timings == [tm] and gc_.hits == 0
    for n in (1, 2):
        again, hit = gc_.get_or_compile(("k",), None, None)
        assert again is fn and gc_.hits == n
        assert (hit.source, hit.read_cache_s, hit.compile_s) == (
            "precompiled", 0.0, 0.0)
    assert gc_.timings == [tm]
