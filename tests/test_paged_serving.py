"""Paged-KV serving path: the engine's block-pool cache must reproduce
the dense ring-cache decode exactly (the compiled serving path equals the
reference semantics) across GQA, MLA, windowed, hybrid and SSM configs —
including after a §3.3 rollback, and across KV-block-streamed migration
(token-exact vs the re-prefill fallback).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.block_log import BlockLog, BlockManager, BlockTable
from repro.models import attention as A
from repro.models.layers import apply_rope, rope_sincos
from repro.models.model import Model
from repro.serving import cache_ops
from repro.serving.kvcache import (PagedKVCache, build_page_context,
                                   padded_block_ids, table_array)

KEY = jax.random.PRNGKey(3)


def test_paged_attention_equals_ring_decode():
    """One GQA layer: write a prompt's K/V through block tables, then
    decode one token both ways (ring cache vs paged pools+kernel)."""
    cfg = get_smoke_config("internlm2-20b")
    p = A.gqa_init(KEY, cfg)
    B, S = 2, 13
    bs = 4
    x_prompt = jax.random.normal(KEY, (B, S, cfg.d_model)) * 0.3
    x_new = jax.random.normal(jax.random.fold_in(KEY, 1),
                              (B, cfg.d_model)) * 0.3
    positions = jnp.arange(S)

    # --- ring-cache reference path
    _, (k_full, v_full) = A.gqa_forward(p, cfg, x_prompt, positions,
                                        return_kv=True)
    from repro.models.model import _ring_from_full
    ring = _ring_from_full(k_full, v_full, positions, 0, max_seq=32)
    pos = jnp.full((B,), S, jnp.int32)
    y_ref, _ = A.gqa_decode(p, cfg, x_new, ring, pos)

    # --- paged path: allocate blocks through the (logged) manager
    manager = BlockManager(num_blocks=32, block_size=bs)
    log = BlockLog()
    log.begin_step()
    tables = {}
    need = (S + 1 + bs - 1) // bs
    for seq in range(B):
        t = BlockTable(seq)
        for _ in range(need):
            t.append_block(manager.allocate(log), log)
        tables[seq] = t

    cache = PagedKVCache(cfg, num_layers=1, num_blocks=32, block_size=bs)
    for seq in range(B):
        cache.write_prefill(0, tables[seq].blocks, k_full[seq], v_full[seq])

    # the new token's k/v (with rope at position S) lands in its slot
    Dh = cfg.resolved_head_dim()
    k_new = (x_new @ p["wk"]).reshape(B, cfg.num_kv_heads, Dh)
    v_new = (x_new @ p["wv"]).reshape(B, cfg.num_kv_heads, Dh)
    q_new = (x_new @ p["wq"]).reshape(B, cfg.num_heads, Dh)
    sin, cos = rope_sincos(pos, Dh, cfg.rope_theta)
    k_new = apply_rope(k_new, sin[:, None, :], cos[:, None, :])
    q_new = apply_rope(q_new, sin[:, None, :], cos[:, None, :])
    for seq in range(B):
        bid = tables[seq].blocks[S // bs]
        cache.write_token(0, bid, S % bs, k_new[seq], v_new[seq])

    bt = jnp.asarray(table_array(tables, [0, 1], max_blk=need))
    seq_lens = jnp.full((B,), S + 1, jnp.int32)
    # jnp oracle and Pallas kernel (interpret) must both match the ring
    for use_pallas in (False, True):
        attn = cache.attend(0, q_new, bt, seq_lens, use_pallas=use_pallas)
        y_paged = attn.reshape(B, -1) @ p["wo"]
        np.testing.assert_allclose(np.asarray(y_paged), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)


def test_paged_pools_survive_block_log_rollback():
    """Blocks allocated mid-step and rolled back are returned to the free
    list; the pool rows they touched are dead (never referenced again)."""
    manager = BlockManager(num_blocks=8, block_size=4)
    log = BlockLog()
    t = BlockTable(0)
    log.begin_step()
    committed = manager.allocate(log)
    t.append_block(committed, log)
    log.begin_step()          # commit
    free_before = manager.num_free
    # in-flight step allocates one more block, then the device fails
    b2 = manager.allocate(log)
    t.append_block(b2, log)
    log.undo_all(manager, {0: t})
    assert manager.num_free == free_before
    assert t.blocks == [committed]
    # re-allocation reuses the rolled-back block id: no leak
    b3 = manager.allocate()
    assert b3 == b2


# -- dense-vs-paged decode parity across architectures ----------------------
#
# The ring caches in repro.models are the reference decode semantics; the
# engine's compiled path is the paged cache.  For every family the engine
# serves, N decode steps through both paths must agree numerically.

def _windowed_internlm():
    cfg = get_smoke_config("internlm2-20b")
    return dataclasses.replace(cfg, sliding_window=16)


PARITY_ARCHS = [
    ("qwen2-moe-a2.7b", None),          # GQA + MoE
    ("minicpm3-4b", None),              # MLA (latent pool)
    ("internlm2-20b", _windowed_internlm),  # GQA + sliding window
]
PARITY_ARCHS_SLOW = [
    ("jamba-1.5-large-398b", None),     # hybrid: pools + SSM state
    ("falcon-mamba-7b", None),          # pure SSM: state only
]


def _run_parity(arch, cfg_fn, n_decode=5):
    cfg = cfg_fn() if cfg_fn else get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq, bs, nb, max_batch = 32, 4, 24, 2
    max_blk = (max_seq + bs - 1) // bs
    rng = np.random.default_rng(0)
    toks = list(rng.integers(0, cfg.vocab_size, 9))
    Sp = len(toks)
    batch = {"tokens": jnp.asarray([toks + [0] * (16 - Sp)], jnp.int32),
             "lengths": jnp.asarray([Sp], jnp.int32)}

    # ring reference: prefill into slot 1 of a batched ring cache
    last_r, sub = model.prefill(params, batch, max_seq=max_seq)
    ring = model.init_cache(max_batch, max_seq)
    axes_r = cache_ops.infer_batch_axes(model, max_seq)
    ring = cache_ops.write_slot(ring, sub, 1, axes_r)

    # paged: prefill raw K/V, scatter into blocks of slot 1
    last_p, raw = model.prefill_paged(params, batch)
    np.testing.assert_allclose(np.asarray(last_p), np.asarray(last_r),
                               rtol=1e-4, atol=1e-4)
    cache = model.init_paged_cache(max_batch, nb, bs)
    _, axes = cache_ops.infer_paged_axes(model, nb, bs)
    man = BlockManager(nb, bs)
    table = BlockTable(7)
    for _ in range((Sp + 1 + bs - 1) // bs):
        table.append_block(man.allocate())
    bids = padded_block_ids(table.blocks, (16 + bs - 1) // bs,
                            trash_block=nb)
    cache = cache_ops.install_prefill(cache, raw, axes,
                                      jnp.asarray(bids), jnp.int32(1))

    class _R:
        batch_slot, req_id = 1, 7
    req = _R()
    tok = int(np.argmax(np.asarray(last_r)[0]))
    ntok = Sp + 1
    tokens = np.zeros((max_batch,), np.int32)
    for _ in range(n_decode):
        tokens[1] = tok
        lr, ring = model.decode_step(params, ring, jnp.asarray(tokens))
        req.num_tokens = ntok
        if (ntok - 1) // bs >= table.num_blocks():
            table.append_block(man.allocate())
        page = build_page_context([req], {7: table}, max_batch=max_batch,
                                  max_blk=max_blk, block_size=bs,
                                  trash_block=nb)
        page = {k: jnp.asarray(v) for k, v in page.items()}
        lp, cache = model.decode_step_paged(params, cache,
                                            jnp.asarray(tokens), page)
        a, b = np.asarray(lr)[1], np.asarray(lp)[1]
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)
        tok = int(np.argmax(a))
        ntok += 1


@pytest.mark.parametrize("arch,cfg_fn", PARITY_ARCHS,
                         ids=[a for a, _ in PARITY_ARCHS])
def test_dense_vs_paged_decode_parity(arch, cfg_fn):
    _run_parity(arch, cfg_fn)


@pytest.mark.slow
@pytest.mark.parametrize("arch,cfg_fn", PARITY_ARCHS_SLOW,
                         ids=[a for a, _ in PARITY_ARCHS_SLOW])
def test_dense_vs_paged_decode_parity_slow(arch, cfg_fn):
    _run_parity(arch, cfg_fn)


# -- executor-level invariants: rollback-then-migrate -----------------------


class _DirectCtx:
    """Uncompiled executor context: model functions called eagerly."""

    def __init__(self, model, params, executor):
        self.model = model
        self.params = params
        self.runtime = model.default_runtime()
        self.ex = executor

    def decode_fn(self, params, cache, tokens, page, runtime):
        page = {k: jnp.asarray(v) for k, v in page.items()}
        return self.model.decode_step_paged(params, cache,
                                            jnp.asarray(tokens), page,
                                            runtime)

    def chunk_fn(self):
        # chunked prefill shares the decode step (virtual token slots)
        return self.decode_fn

    def prefill_fn(self, bucket):
        def fn(params, tokens, lengths, runtime):
            return self.model.prefill_paged(
                params, {"tokens": jnp.asarray(tokens),
                         "lengths": jnp.asarray(lengths)}, runtime)
        return fn

    def install_fn(self, bucket):
        def fn(cache, raw, bids, slot):
            return cache_ops.install_prefill(
                cache, raw, self.ex.paged_axes, jnp.asarray(bids),
                jnp.int32(slot))
        return fn


def _executor(model, dp_rank=0, pool_undo="rows"):
    from repro.serving.executor import DPExecutor
    from repro.serving.sampling import SamplingParams
    return DPExecutor(physical_id=dp_rank, dp_rank=dp_rank, model=model,
                      max_batch=2, max_seq=32, num_blocks=16, block_size=4,
                      sampling=SamplingParams(), pool_undo=pool_undo)


@pytest.mark.parametrize("pool_undo", ["rows", "snapshot"])
def test_rollback_then_migrate_pool_and_table_consistency(pool_undo):
    """§3.3 + §3.2 composed: a mid-step fault rolls the executor back to
    the step boundary (block tables from the op log, pools by restoring
    the captured write-set rows — or, legacy, the functional snapshot —
    bit-identical either way), and the rolled-back executor can then
    stream a resident's KV blocks to a peer that continues the exact
    token sequence."""
    from repro.serving.request import Request, RequestState
    cfg = get_smoke_config("internlm2-20b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ex = _executor(model, 0, pool_undo=pool_undo)
    ctx = _DirectCtx(model, params, ex)

    rng = np.random.default_rng(1)
    r1 = Request(list(rng.integers(0, cfg.vocab_size, 6)), 8)
    ex.scheduler.add_request(r1)
    # step 1: prefill r1; step 2: decode — both committed
    for step in (1, 2):
        ex.plan()
        ex.compute(ctx, step)
        ex.commit()
    cache_at_boundary = ex.cache
    snap = ex.block_manager.snapshot()
    tokens_before = list(r1.output_tokens)

    # reference: an identical unmolested executor decodes r1's next token
    ex_ref = _executor(model, 1)
    ctx_ref = _DirectCtx(model, params, ex_ref)
    r_ref = Request(list(r1.prompt_tokens), 8)
    ex_ref.scheduler.add_request(r_ref)
    for step in (1, 2, 3):
        ex_ref.plan()
        ex_ref.compute(ctx_ref, step)
        ex_ref.commit()

    # in-flight step admits r2 and allocates blocks... then the fault
    r2 = Request(list(rng.integers(0, cfg.vocab_size, 5)), 8)
    ex.scheduler.add_request(r2)
    ex.plan()
    assert len(ex.block_log) > 0
    undone = ex.rollback_inflight()
    assert undone > 0
    # pool consistency: the cache equals the step-boundary value exactly
    # (snapshot mode restores the identical object; row mode scatters
    # the captured write-set rows back), tables/manager match it
    if pool_undo == "snapshot":
        assert ex.cache is cache_at_boundary
    else:
        for a, b in zip(jax.tree_util.tree_leaves(ex.cache),
                        jax.tree_util.tree_leaves(cache_at_boundary)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ex.block_manager.snapshot() == snap
    assert r1.output_tokens == tokens_before
    ex.scheduler.check_consistent()
    assert ex.scheduler.waiting[0] is r2     # aborted admission requeued

    # migrate r1 by KV-block stream to a fresh peer; its next decoded
    # token must equal the unmigrated reference's
    kv = ex.export_kv_blocks(r1)
    assert kv is not None and kv.valid_len == r1.num_tokens - 1
    ex2 = _executor(model, 2)
    ctx2 = _DirectCtx(model, params, ex2)
    assert ex2.import_kv_blocks(r1, kv)
    ex2.scheduler.check_consistent()
    ex2.plan()
    ex2.compute(ctx2, 1)
    ex2.commit()
    assert r1.output_tokens[-1] == r_ref.output_tokens[len(tokens_before)]
    assert r1.recomputed_tokens == 0


def test_import_kv_blocks_refuses_without_capacity():
    """The stream install is all-or-nothing: no slot or not enough free
    blocks -> False, and the target's accounting is untouched (callers
    fall back to token replay)."""
    from repro.serving.request import Request
    cfg = get_smoke_config("internlm2-20b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ex = _executor(model, 0)
    ctx = _DirectCtx(model, params, ex)
    r1 = Request(list(np.random.default_rng(2).integers(
        0, cfg.vocab_size, 6)), 8)
    ex.scheduler.add_request(r1)
    for step in (1, 2):
        ex.plan()
        ex.compute(ctx, step)
        ex.commit()
    kv = ex.export_kv_blocks(r1)
    assert kv is not None

    tgt = _executor(model, 1)
    tgt.scheduler._free_slots = []           # no batch slot
    before = tgt.block_manager.snapshot()
    assert not tgt.import_kv_blocks(r1, kv)
    assert tgt.block_manager.snapshot() == before

    tgt2 = _executor(model, 2)
    while tgt2.block_manager.num_free > 1:   # not enough blocks
        tgt2.block_manager.allocate()
    assert not tgt2.import_kv_blocks(r1, kv)
    tgt2.scheduler.check_consistent()


# -- engine-level: KV-stream vs re-prefill token-exact equivalence ----------


def test_kv_stream_equals_reprefill_tokens(tmp_path):
    """Acceptance: migrating a mid-generation request by KV-block stream
    and by token-replay re-prefill produces the identical token sequence;
    only the replay path pays recomputed tokens."""
    from repro.serving.engine import EngineConfig, InferenceEngine
    from repro.serving.sampling import SamplingParams
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=4,
                                     num_redundant_experts=2, top_k=2,
                                     capacity_factor=8.0, min_capacity=64))
    ecfg = EngineConfig(mode="collocated", num_dp=1, max_batch=2,
                        max_seq=64, block_size=8, num_blocks=32,
                        workdir=str(tmp_path), dtype="float32",
                        sampling=SamplingParams(temperature=0.8,
                                                top_p=0.9, seed=7))
    src = InferenceEngine(cfg, ecfg)
    tgt = InferenceEngine(cfg, ecfg)
    prompt = list(np.random.default_rng(5).integers(0, cfg.vocab_size, 9))

    outs = {}
    for mode in ("stream", "replay"):
        req = src.submit(list(prompt), 12)
        for _ in range(4):
            src.step()
        assert 0 < len(req.output_tokens) < 12
        if mode == "stream":
            (req2, kv), = src.export_live_requests(with_kv=True)
            assert req2 is req and kv is not None
        else:
            (req2,) = src.export_live_requests()
            assert req2 is req
            kv = None
        tgt.admit(req, kv=kv)
        tgt.run(max_steps=80)
        assert req.state.value == "finished"
        outs[mode] = (list(req.output_tokens), req.recomputed_tokens)

    assert outs["stream"][0] == outs["replay"][0]
    assert outs["stream"][1] == 0            # no re-prefill when streamed
    assert outs["replay"][1] > 0             # fallback pays the replay
