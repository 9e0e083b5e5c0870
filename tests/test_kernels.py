"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.expert_ffn import expert_ffn_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                            pages_per_step)
from repro.kernels.router_topk import router_topk_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("T,E,k", [(16, 8, 2), (100, 60, 4), (256, 64, 8),
                                   (33, 384, 8)])
@pytest.mark.parametrize("masked", [0, 3])
def test_router_topk(T, E, k, masked):
    ks = jax.random.split(jax.random.fold_in(KEY, T * E + k), 2)
    logits = jax.random.normal(ks[0], (T, E), jnp.float32)
    mask = jnp.ones((E,), bool)
    if masked:
        dead = jax.random.choice(ks[1], E, (masked,), replace=False)
        mask = mask.at[dead].set(False)
    w1, i1 = router_topk_pallas(logits, mask, k, interpret=True)
    w2, i2 = ref.router_topk_ref(logits, mask, k)
    np.testing.assert_allclose(np.sort(w1, -1), np.sort(np.asarray(w2), -1),
                               rtol=2e-5, atol=1e-6)
    # selected sets must match (order may differ on exact ties)
    np.testing.assert_array_equal(np.sort(i1, -1), np.sort(np.asarray(i2), -1))
    if masked:
        assert not np.isin(np.asarray(i1), np.asarray(dead)).any()
    # weights renormalized
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("E,C,D,F", [(2, 64, 128, 256), (3, 100, 256, 384),
                                     (8, 128, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_ffn(E, C, D, F, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, E * C), 4)
    x = (jax.random.normal(ks[0], (E, C, D)) * 0.1).astype(dtype)
    g = (jax.random.normal(ks[1], (E, D, F)) * 0.05).astype(dtype)
    u = (jax.random.normal(ks[2], (E, D, F)) * 0.05).astype(dtype)
    d = (jax.random.normal(ks[3], (E, F, D)) * 0.05).astype(dtype)
    y1 = expert_ffn_pallas(x, g, u, d, interpret=True)
    y2 = ref.expert_ffn_ref(x, g, u, d)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=tol,
                               atol=tol)


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("B,H,Hkv,Dh,bs,mb,dtype,sl,st", [
    pytest.param(2, 4, 4, 64, 16, 3, F32, None, None,          # MHA
                 id="2-4-4-64-16-3"),
    pytest.param(3, 8, 2, 64, 16, 4, F32, None, None,          # GQA
                 id="3-8-2-64-16-4"),
    pytest.param(1, 16, 8, 128, 32, 2, F32, None, None,
                 id="1-16-8-128-32-2"),
    pytest.param(4, 8, 2, 64, 16, 5, F32, [0, 33, 0, 80], None,
                 id="idle-rows"),
    pytest.param(3, 8, 2, 64, 16, 4, F32, [17, 49, 63], None,
                 id="partial-last-page"),
    pytest.param(3, 8, 2, 64, 16, 40, F32, [640, 300, 17], [300, 290, 0],
                 id="window-skips-leading-pages"),
    pytest.param(2, 4, 4, 64, 16, 45, F32, [720, 530], [0, 515],
                 id="max-blk-not-divisible"),
    pytest.param(3, 48, 8, 128, 16, 20, BF16, [300, 0, 257], None,
                 id="internlm2-gqa-bf16"),
    pytest.param(2, 16, 1, 576, 16, 6, BF16, [90, 33], [40, 0],
                 id="mla-latent-bf16"),
])
def test_paged_attention(B, H, Hkv, Dh, bs, mb, dtype, sl, st):
    """The kernel against its oracle under the TPU interpreter: DMAs land
    only when waited on, unwritten VMEM reads NaN, races are detected."""
    nb = mb * B + 2
    ks = jax.random.split(jax.random.fold_in(KEY, B * H * bs), 5)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (nb, bs, Hkv, Dh),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (nb, bs, Hkv, Dh),
                           jnp.float32).astype(dtype)
    bt = jax.random.randint(ks[3], (B, mb), 0, nb)
    sl = (jax.random.randint(ks[4], (B,), 1, mb * bs + 1) if sl is None
          else jnp.asarray(sl, jnp.int32))
    st = None if st is None else jnp.asarray(st, jnp.int32)
    P = pages_per_step(bs, Hkv, -(-Dh // 128) * 128, q.dtype.itemsize, mb)
    if mb == 45:
        assert mb % P, P            # the last grid step's block is short
    o1 = paged_attention_pallas(
        q, kp, vp, bt, sl, st,
        interpret=pltpu.InterpretParams(detect_races=True))
    o2 = ref.paged_attention_ref(q, kp, vp, bt, sl, st)
    # the oracle rounds its probabilities to the pools' dtype, the kernel
    # keeps them in f32
    tol = (1e-4, 1e-5) if dtype == F32 else (2e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol[0],
                               atol=tol[1])
    idle = np.asarray(sl) == 0
    assert not np.asarray(o1, np.float32)[idle].any()


@pytest.mark.parametrize("B,S,d,N,block_d,chunk", [
    (1, 64, 256, 16, 256, 32),
    (2, 128, 512, 16, 128, 64),
    (2, 96, 256, 8, 256, 32),
])
def test_ssm_scan(B, S, d, N, block_d, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, S * d), 5)
    u = jax.random.normal(ks[0], (B, S, d)) * 0.1
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d)) - 2)
    A = -jnp.exp(jax.random.normal(ks[2], (d, N)) * 0.3)
    Bs = jax.random.normal(ks[3], (B, S, N)) * 0.2
    Cs = jax.random.normal(ks[4], (B, S, N)) * 0.2
    y1, h1 = ssm_scan_pallas(u, dt, A, Bs, Cs, block_d=block_d, chunk=chunk,
                             interpret=True)
    y2, h2 = ref.ssm_scan_ref(u, dt, A, Bs, Cs)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-5)


# -- fused MoE dispatch->FFN->combine pipeline ------------------------------

MOE_FUSED_GRID = [
    # T, k, e_phys, e_local, off, D, F, cap
    (32, 2, 4, 4, 0, 128, 256, 12),     # aligned, all experts local
    (19, 3, 6, 3, 3, 96, 144, 4),       # odd shapes, offset slice, overflow
    (8, 2, 4, 2, 2, 64, 40, 8),         # tiny F (< one 128 lane tile)
    (100, 2, 8, 8, 0, 128, 128, 16),    # capacity overflow on hot experts
]


def _moe_fused_inputs(T, k, e_phys, e_local, D, F, dtype=jnp.float32,
                      alive_p=0.85):
    ks = jax.random.split(jax.random.fold_in(KEY, T * e_phys + k * D), 7)
    x = (jax.random.normal(ks[0], (T, D)) * 0.1).astype(dtype)
    g = (jax.random.normal(ks[1], (e_local, D, F)) * 0.05).astype(dtype)
    u = (jax.random.normal(ks[2], (e_local, D, F)) * 0.05).astype(dtype)
    d = (jax.random.normal(ks[3], (e_local, F, D)) * 0.05).astype(dtype)
    phys = jax.random.randint(ks[4], (T, k), 0, e_phys)
    w = jax.nn.softmax(jax.random.normal(ks[5], (T, k)), -1)
    alive = jax.random.bernoulli(ks[6], alive_p, (T, k))
    return x, g, u, d, phys, w, alive


@pytest.mark.parametrize("T,k,e_phys,e_local,off,D,F,cap", MOE_FUSED_GRID)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_fused_parity(T, k, e_phys, e_local, off, D, F, cap, dtype):
    """Pallas fused pipeline == jnp fused oracle == dense-scatter path."""
    from repro.kernels.moe_fused import moe_fused_pallas
    from repro.models.moe import dispatch_compute_combine
    x, g, u, d, phys, w, alive = _moe_fused_inputs(
        T, k, e_phys, e_local, D, F, dtype)
    y_dense = dispatch_compute_combine(x, w, phys, alive, g, u, d,
                                       cap=cap, expert_offset=off,
                                       e_local=e_local)
    y_ref = ref.moe_fused_ref(x, g, u, d, w, phys, alive, cap=cap,
                              expert_offset=off, e_local=e_local)
    y_pal = moe_fused_pallas(x, g, u, d, w, phys, alive, cap=cap,
                             expert_offset=off, e_local=e_local,
                             interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=tol, atol=tol)


def test_moe_fused_masked_and_lost_experts():
    """Fused path under real routing with a masked expert (§3.4) and a
    fully-lost expert (replica_count == 0) matches the dense path."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import (MoERuntime, default_runtime,
                                  dispatch_compute_combine, route,
                                  select_replicas)
    from repro.kernels.moe_fused import moe_fused_pallas
    moe = MoEConfig(num_experts=4, top_k=2, expert_d_ff=64,
                    num_redundant_experts=2)
    e_phys = 6
    rt0 = default_runtime(moe)
    # expert 3 masked out of routing; expert 2 fully lost (tokens that
    # still select it are dropped via alive=False)
    rt = MoERuntime(rt0.logical_to_physical,
                    rt0.replica_count.at[2].set(0),
                    rt0.expert_mask.at[3].set(False))
    T, D, F, cap = 24, 64, 64, 10
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (T, D)) * 0.1
    router_w = jax.random.normal(ks[1], (D, 4)) * 0.1
    g = jax.random.normal(ks[2], (e_phys, D, F)) * 0.05
    u = jax.random.normal(ks[3], (e_phys, D, F)) * 0.05
    d = jax.random.normal(ks[4], (e_phys, F, D)) * 0.05
    w, sel, _ = route(router_w, x, rt, moe)
    assert not np.isin(np.asarray(sel), [3]).any()    # mask respected
    phys, alive = select_replicas(sel, rt)
    assert not np.asarray(alive).all()                # lost expert hit
    y_dense = dispatch_compute_combine(x, w, phys, alive, g, u, d,
                                       cap=cap, expert_offset=0,
                                       e_local=e_phys)
    y_pal = moe_fused_pallas(x, g, u, d, w, phys, alive, cap=cap,
                             expert_offset=0, e_local=e_phys,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-5)


def test_moe_fused_runtime_mutation_no_recompile():
    """§3.4 for the fused pipeline: replica drop and expert mask are data
    (MoERuntime arrays), so recovery never retraces the compiled step."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import moe as MoE
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_redundant_experts=2), moe_impl="fused")
    p = MoE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(KEY, (16, cfg.d_model))
    f = jax.jit(lambda xx, rt: MoE.moe_apply_local(p, cfg, xx, rt, cap=8))
    rt = MoE.default_runtime(cfg.moe)
    y0, _ = f(x, rt)
    n0 = f._cache_size()
    # drop a replica + mask an expert — recovery's two mutations
    rt2 = MoE.MoERuntime(rt.logical_to_physical,
                         rt.replica_count.at[0].set(1),
                         rt.expert_mask.at[1].set(False))
    y1, _ = f(x, rt2)
    assert f._cache_size() == n0          # no retrace / recompile
    assert np.isfinite(np.asarray(y1)).all()
    assert not np.allclose(np.asarray(y0), np.asarray(y1))  # mask applied


def test_router_topk_mask_is_data_not_recompile():
    """The §3.4 property: changing the failure mask re-uses the same
    compiled kernel (mask is an argument, not a constant)."""
    from repro.kernels import ops
    T, E, k = 32, 16, 2
    logits = jax.random.normal(KEY, (T, E))
    m1 = jnp.ones((E,), bool)
    m2 = m1.at[0].set(False)
    f = jax.jit(lambda lg, m: ops.router_topk(lg, m, k, use_pallas=False))
    _ = f(logits, m1)
    n0 = f._cache_size()
    _ = f(logits, m2)
    assert f._cache_size() == n0  # no retrace/recompile


@pytest.mark.parametrize("B,S,H,Hkv,Dh,bq,bk", [
    (1, 128, 4, 4, 64, 64, 64),    # MHA
    (2, 128, 8, 2, 64, 32, 64),    # GQA, bq != bk
    (1, 256, 16, 8, 128, 128, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_prefill(B, S, H, Hkv, Dh, bq, bk, causal):
    from repro.kernels.flash_prefill import flash_prefill_pallas
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + causal), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    o1 = flash_prefill_pallas(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
    o2 = ref.flash_prefill_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4,
                               atol=2e-4)


def test_flash_prefill_matches_model_attention():
    """Kernel semantics == the model's chunked-flash jnp implementation."""
    from repro.kernels.flash_prefill import flash_prefill_pallas
    from repro.models.attention import flash_attention
    B, S, H, Hkv, Dh = 2, 128, 8, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    pos = jnp.arange(S)
    o_model = flash_attention(q, k, v, pos, pos, causal=True)
    o_kernel = flash_prefill_pallas(q, k, v, causal=True, block_q=64,
                                    block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               rtol=2e-4, atol=2e-4)
