"""Decode megakernel (ISSUE 5): the fused attention+MoE step must be
token-exact against the composed kernel chain across GQA / MLA /
windowed architectures, survive every ReviveMoE recovery mutation
(fail_rank / mask_experts / rollback) with zero recompiles, and its
Pallas kernel must match the jnp oracle in interpret mode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.block_log import BlockManager, BlockTable
from repro.models import moe as MoE
from repro.models.model import Model
from repro.serving import cache_ops
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.kvcache import build_page_context, padded_block_ids
from repro.serving.sampling import SamplingParams

KEY = jax.random.PRNGKey(7)


# -- Pallas kernel vs jnp oracle (interpret mode) ---------------------------

def _megastep_inputs(*, B=3, H=4, Hkv=2, Dh=16, bs=4, nb=10, max_blk=3,
                     D=32, E_log=5, E=7, K=2, F=48, Fs=0, cap=5, seed=0,
                     lost=None, masked=None, window=False, offset=0):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 14)
    q = jax.random.normal(ks[0], (B, H, Dh)) * 0.3
    k_pool = jax.random.normal(ks[1], (nb, bs, Hkv, Dh)) * 0.3
    v_pool = jax.random.normal(ks[2], (nb, bs, Hkv, Dh)) * 0.3
    bt = jax.random.randint(ks[3], (B, max_blk), 0, nb)
    sl = jax.random.randint(ks[4], (B,), 0, max_blk * bs + 1)  # incl. idle
    st = (jnp.maximum(sl - 6, 0) if window
          else jnp.zeros((B,), jnp.int32))
    x = jax.random.normal(ks[5], (B, D)) * 0.2
    w_post = jax.random.normal(ks[6], (H * Dh, D)) * 0.1
    ln2 = jnp.ones((D,)) * 1.1
    router = jax.random.normal(ks[7], (D, E_log)) * 0.2
    # two replicas for the first couple of logical experts
    l2p = jnp.stack(
        [jnp.arange(E_log, dtype=jnp.int32),
         jnp.where(jnp.arange(E_log) < 2, E_log + jnp.arange(E_log),
                   0).astype(jnp.int32)], axis=1)
    rcnt = jnp.where(jnp.arange(E_log) < 2, 2, 1).astype(jnp.int32)
    mask = jnp.ones((E_log,), bool)
    if lost is not None:
        rcnt = rcnt.at[lost].set(0)
    if masked is not None:
        mask = mask.at[masked].set(False)
    g = jax.random.normal(ks[8], (E, D, F)) * 0.05
    u = jax.random.normal(ks[9], (E, D, F)) * 0.05
    d = jax.random.normal(ks[10], (E, F, D)) * 0.05
    if Fs:
        sg = jax.random.normal(ks[11], (D, Fs)) * 0.05
        su = jax.random.normal(ks[12], (D, Fs)) * 0.05
        sd = jax.random.normal(ks[13], (Fs, D)) * 0.05
    else:
        sg = su = sd = None
    args = (q, k_pool, v_pool, bt, sl, st, x, w_post, ln2, router, l2p,
            rcnt, mask, g, u, d, jnp.int32(offset), sg, su, sd)
    return args, dict(top_k=K, cap=cap, e_local=E)


@pytest.mark.parametrize("case", [
    dict(),                                      # plain GQA-shaped
    dict(Hkv=1, Dh=24, H=6),                     # MLA-shaped (Hkv=1 pool)
    dict(window=True),                           # sliding-window starts
    dict(lost=3, masked=4),                      # §3.4 recovery mutations
    dict(E=3, offset=2, E_log=6),                # EP shard slice
    dict(F=96, cap=3),                           # F blocking + tight cap
    dict(Fs=40),                                 # in-kernel shared experts
], ids=["gqa", "mla_shaped", "windowed", "lost_masked", "ep_offset",
        "fblocked", "shared"])
def test_megastep_kernel_matches_ref(case):
    from repro.kernels import ref
    from repro.kernels.decode_megakernel import decode_megastep_pallas
    args, kw = _megastep_inputs(**case)
    y_ref, h2_ref = ref.decode_megastep_ref(*args, **kw)
    # block_d=24 < D=32: every variant runs the blocked+padded D path
    y_pal, h2_pal = decode_megastep_pallas(*args, **kw, block_f=32,
                                           block_d=24, interpret=True)
    np.testing.assert_allclose(np.asarray(h2_pal), np.asarray(h2_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", [
    dict(),                                      # plain
    dict(lost=2, masked=3),                      # recovery mutations
    dict(window=True),                           # sliding-window starts
], ids=["plain", "lost_masked", "windowed"])
def test_megastep_kernel_deployment_d_model(case):
    """Blocked-D parity at a deepseek_v3-class hidden size: weight
    matrices stream through (block_d)-wide VMEM pages while the (B, D)
    activations stay resident, so d_model = 7168 runs without a weight
    ever needing its full D extent on chip (carry-overs (a)/(d))."""
    from repro.kernels import ref
    from repro.kernels.decode_megakernel import decode_megastep_pallas
    args, kw = _megastep_inputs(B=2, H=2, Hkv=1, Dh=16, bs=4, nb=6,
                                max_blk=2, D=7168, E_log=4, E=4, K=2,
                                F=64, Fs=64, cap=4, **case)
    y_ref, h2_ref = ref.decode_megastep_ref(*args, **kw)
    y_pal, h2_pal = decode_megastep_pallas(*args, **kw, block_f=64,
                                           block_d=512, interpret=True)
    np.testing.assert_allclose(np.asarray(h2_pal), np.asarray(h2_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_megastep_kernel_mutation_is_data_not_recompile():
    """The Pallas wrapper path recompiles for shapes only: mutated
    MoERuntime arrays, paging arrays and expert offsets reuse the same
    jitted executable (§3.4 for the megakernel)."""
    from repro.kernels import ops
    args, kw = _megastep_inputs()
    f = jax.jit(lambda *a: ops.decode_megastep(*a, **kw,
                                               use_pallas=False))
    y0, _ = f(*args)
    n0 = f._cache_size()
    a = list(args)
    a[11] = a[11].at[0].set(0)        # drop a replica (fail_rank's edit)
    a[12] = a[12].at[1].set(False)    # mask an expert
    a[4] = a[4] + 1                   # sequences grew a token
    y1, _ = f(*a)
    assert f._cache_size() == n0
    assert np.isfinite(np.asarray(y1)).all()
    assert not np.allclose(np.asarray(y0), np.asarray(y1))


# -- model-level token parity: megakernel vs composed -----------------------

def _decode_tokens(cfg, n_decode=5, runtime_fn=None):
    """Greedy-decode a prompt through decode_step_paged; returns the
    token ids and per-step logits."""
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq, bs, nb, max_batch = 32, 4, 24, 2
    max_blk = (max_seq + bs - 1) // bs
    rng = np.random.default_rng(0)
    toks = list(rng.integers(0, cfg.vocab_size, 9))
    Sp = len(toks)
    batch = {"tokens": jnp.asarray([toks + [0] * (16 - Sp)], jnp.int32),
             "lengths": jnp.asarray([Sp], jnp.int32)}
    runtime = (runtime_fn(model) if runtime_fn
               else model.default_runtime())
    last, raw = model.prefill_paged(params, batch, runtime)
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
    tok = int(np.argmax(np.asarray(last)[0]))
    ntok = Sp + 1
    tokens = np.zeros((max_batch,), np.int32)
    out_toks, out_logits = [], []
    for _ in range(n_decode):
        tokens[1] = tok
        req.num_tokens = ntok
        if (ntok - 1) // bs >= table.num_blocks():
            table.append_block(man.allocate())
        page = build_page_context([req], {7: table}, max_batch=max_batch,
                                  max_blk=max_blk, block_size=bs,
                                  trash_block=nb)
        page = {k: jnp.asarray(v) for k, v in page.items()}
        lg, cache = model.decode_step_paged(params, cache,
                                            jnp.asarray(tokens), page,
                                            runtime)
        out_logits.append(np.asarray(lg)[1])
        tok = int(np.argmax(np.asarray(lg)[1]))
        out_toks.append(tok)
        ntok += 1
    return out_toks, out_logits


def _windowed_qwen():
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    return dataclasses.replace(cfg, sliding_window=6)


PARITY_ARCHS = [
    ("qwen2-moe-a2.7b", None),     # GQA + MoE + shared experts
    ("deepseek-v3", None),         # MLA + MoE + first-k-dense
    ("qwen2-moe-a2.7b", _windowed_qwen),   # GQA + sliding window
]


@pytest.mark.parametrize("arch,cfg_fn", PARITY_ARCHS,
                         ids=["gqa_moe", "mla_moe", "windowed"])
def test_megakernel_token_parity(arch, cfg_fn):
    cfg = cfg_fn() if cfg_fn else get_smoke_config(arch)
    t_c, l_c = _decode_tokens(cfg)
    t_m, l_m = _decode_tokens(
        dataclasses.replace(cfg, decode_impl="megakernel"))
    assert t_m == t_c
    for a, b in zip(l_c, l_m):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)


def test_megakernel_token_parity_masked_and_lost_experts():
    """Recovery state (masked expert + fully lost expert) flows through
    the megakernel identically to the composed chain."""
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_redundant_experts=2))

    def hurt(model):
        rt = model.default_runtime()
        return MoE.MoERuntime(rt.logical_to_physical,
                              rt.replica_count.at[2].set(0),
                              rt.expert_mask.at[3].set(False))

    t_c, _ = _decode_tokens(cfg, runtime_fn=hurt)
    t_m, l_m = _decode_tokens(
        dataclasses.replace(cfg, decode_impl="megakernel"),
        runtime_fn=hurt)
    assert t_m == t_c
    assert all(np.isfinite(lg).all() for lg in l_m)


def test_megastep_zero_recompile_full_step():
    """A jitted megakernel decode_step_paged is retrace-free under every
    per-step change the engine performs: new tokens, new paging arrays,
    and recovery-mutated MoERuntime."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-moe-a2.7b"),
                              decode_impl="megakernel")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_batch, nb, bs = 2, 16, 4
    cache = model.init_paged_cache(max_batch, nb, bs)
    f = jax.jit(model.decode_step_paged)
    page = {"tables": jnp.zeros((max_batch, 4), jnp.int32),
            "seq_lens": jnp.asarray([1, 0], jnp.int32),
            "write_bid": jnp.asarray([0, nb], jnp.int32),
            "write_off": jnp.zeros((max_batch,), jnp.int32)}
    toks = jnp.zeros((max_batch,), jnp.int32)
    rt = model.default_runtime()
    _, cache = f(params, cache, toks, page, rt)
    n0 = f._cache_size()
    rt2 = MoE.MoERuntime(rt.logical_to_physical,
                         rt.replica_count.at[0].set(0),
                         rt.expert_mask.at[1].set(False))
    page2 = dict(page, seq_lens=jnp.asarray([2, 0], jnp.int32),
                 write_bid=jnp.asarray([1, nb], jnp.int32),
                 write_off=jnp.asarray([1, 0], jnp.int32))
    lg, _ = f(params, cache, toks + 3, page2, rt2)
    assert f._cache_size() == n0          # §3.4: pure data, no retrace
    assert np.isfinite(np.asarray(lg)).all()


# -- engine-level: serving, recovery, rollback ------------------------------

def _engine(tmp_path, sub, decode_impl=None, num_dp=1, **over):
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    # megakernel vs composed is compared token for token: float32 weights
    over.setdefault("dtype", "float32")
    ec = EngineConfig(mode="collocated", num_dp=num_dp, max_batch=2,
                      max_seq=over.pop("max_seq", 64), block_size=8,
                      num_blocks=64, workdir=str(tmp_path / sub),
                      decode_impl=decode_impl,
                      sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                              seed=3), **over)
    return cfg, InferenceEngine(cfg, ec)


def _serve(eng, cfg, prompts, max_new=6):
    reqs = [eng.submit(list(p), max_new) for p in prompts]
    eng.run(max_steps=400)
    assert all(r.state.value == "finished" for r in reqs), \
        [r.state for r in reqs]
    return [list(r.output_tokens) for r in reqs]


def test_engine_chunked_token_parity_and_rollback(tmp_path):
    """Chunked prefill + decode through the compiled megakernel path is
    token-exact vs composed, and a mid-step fault during a megastep
    chunk rolls back via the row-level undo and replays to exactly the
    stream the composed path produces under the identical fault (the
    lost rank carries an expert shard, so the no-fault stream is not
    the reference — the composed engine under the same fault is)."""
    from repro.core.fault_codes import ErrorType, Severity
    rng = np.random.default_rng(9)
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    prompts = [list(rng.integers(0, cfg.vocab_size, 60)),
               list(rng.integers(0, cfg.vocab_size, 58))]

    _, ref = _engine(tmp_path, "ref", None, num_dp=2, max_seq=96)
    want = _serve(ref, cfg, prompts)

    _, mega = _engine(tmp_path, "mega", "megakernel", num_dp=2,
                      max_seq=96)
    got = _serve(mega, cfg, prompts)
    assert got == want

    def fault_run(sub, decode_impl):
        _, eng = _engine(tmp_path, sub, decode_impl, num_dp=2,
                         max_seq=96)
        eng.injector.schedule(2, 1, severity=Severity.L6,
                              error_type=ErrorType.HBM_ECC,
                              component="attn", mid_step=True)
        out = _serve(eng, cfg, prompts)
        surviving = [ex for ex in eng.dp_executors if ex.alive]
        assert surviving and all(
            ex.block_manager.num_allocated == 0 for ex in surviving)
        return out

    want_f = fault_run("fault_ref", None)
    got_f = fault_run("fault_mega", "megakernel")
    assert got_f == want_f


def test_engine_fail_rank_and_mask_zero_recompile(tmp_path):
    """fail_rank + mask_experts on a serving megakernel engine are pure
    MoERuntime data edits: serving continues and the graph cache never
    sees a fresh compile."""
    cfg, eng = _engine(tmp_path, "m", "megakernel", num_dp=2,
                       precompile_failure_scenarios=False)
    rng = np.random.default_rng(4)

    _serve(eng, cfg, [list(rng.integers(0, cfg.vocab_size, 12))])
    n0 = len(eng.graph_cache.timings)
    # recovery's two runtime mutations, applied as the §3.4 data edit
    eng.expert_map.fail_rank(1)
    eng.expert_map.mask_experts(
        [e for e in range(cfg.moe.num_experts)
         if not any(s not in set(eng.expert_map.rank_slots(1))
                    for s in eng.expert_map.replicas_of(e))])
    eng.runtime = eng.expert_map.runtime()
    out = _serve(eng, cfg, [list(rng.integers(0, cfg.vocab_size, 9))])
    assert len(eng.graph_cache.timings) == n0   # no miss compiled
    assert out and len(out[0]) == 6


# -- in-instance prefix affinity (ROADMAP paged-KV (i)) ---------------------

def test_assign_prefers_prefix_affine_executor(tmp_path):
    """_assign sends a shared-prefix arrival to the DP rank whose
    BlockManager holds the prefix digests (not the least-loaded one),
    unless that rank is beyond the load-slack guard."""
    cfg, eng = _engine(tmp_path, "aff", None, num_dp=2)
    rng = np.random.default_rng(11)
    sysp = list(rng.integers(0, cfg.vocab_size, 24))  # 3 full blocks

    r0 = eng.submit(sysp + list(rng.integers(0, cfg.vocab_size, 6)), 4)
    eng.run(max_steps=200)
    assert r0.state.value == "finished"
    owner = r0.dp_rank
    other = 1 - owner
    # cached-free blocks keep the digests addressable on the owner
    digests_held = eng.dp_executors[owner].block_manager.cache_hits >= 0

    # load the owner so plain least-loaded would pick the other rank
    from repro.serving.request import Request
    filler = Request(list(rng.integers(0, cfg.vocab_size, 4)), 30)
    eng.dp_executors[owner].scheduler.add_request(filler)

    r1 = eng.submit(sysp + list(rng.integers(0, cfg.vocab_size, 5)), 2)
    assert r1.dp_rank == owner, (r1.dp_rank, owner, digests_held)

    # beyond the slack guard the affinity yields to load balance
    for _ in range(eng.ASSIGN_AFFINITY_SLACK + 1):
        eng.dp_executors[owner].scheduler.add_request(
            Request(list(rng.integers(0, cfg.vocab_size, 4)), 30))
    r2 = eng.submit(sysp + list(rng.integers(0, cfg.vocab_size, 7)), 2)
    assert r2.dp_rank == other
