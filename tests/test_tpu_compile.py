"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler (installed with libtpu) compiles each
kernel for one chip of a ``v5e:2x2`` topology that is described, not
attached, at qwen2-moe-a2.7b widths in bfloat16; paged attention also at
internlm2-20b's and at minicpm3-4b's MLA latent widths.  This catches what
interpret mode cannot — tiling-rule violations, scalar accesses to vector
memory, VMEM overflow — before any chip time is spent.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.decode_megakernel import decode_megastep_pallas
from repro.kernels.moe_fused import moe_fused_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models.moe import capacity, physical_experts

QWEN = get_config("qwen2-moe-a2.7b")
BS, MAX_BLK, NB = 16, 8, 257          # the serving engine's paged pools
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shape maker on one described chip, with JAX's persistent compile
    cache off: a compile for a described chip is written to it but cannot
    be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _dims():
    moe = QWEN.moe
    return (QWEN.d_model, QWEN.num_heads, QWEN.resolved_head_dim(),
            QWEN.num_kv_heads, physical_experts(moe), moe.expert_d_ff,
            moe.top_k, moe.num_experts,
            moe.num_shared_experts * moe.expert_d_ff)


@pytest.mark.parametrize("B", [4, 32])   # decode batch, chunk width
def test_paged_attention_compiles(chip, B):
    D, H, Dh, Hkv, *_ = _dims()
    _compile(lambda q, k, v, bt, sl: paged_attention_pallas(q, k, v, bt, sl),
             chip((B, H, Dh), BF), chip((NB, BS, Hkv, Dh), BF),
             chip((NB, BS, Hkv, Dh), BF), chip((B, MAX_BLK), jnp.int32),
             chip((B,), jnp.int32))


def _paged_widths(name):
    """(H, Hkv, Dh) a configuration's paged-attention call runs at: MLA
    attends one latent head of R + dr lanes."""
    cfg = get_config(name)
    if cfg.mla is not None:
        return (cfg.num_heads, 1,
                cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim)
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()


@pytest.mark.parametrize("name,B,max_blk,nb", [
    ("internlm2-20b", 8, 208, 1665),      # the batch cell's decode call
    ("internlm2-20b", 32, 208, 1665),     # and its chunk call
    ("minicpm3-4b", 8, 160, 1025),        # MLA latent pool, 288 lanes
])
def test_paged_attention_compiles_at(chip, name, B, max_blk, nb):
    H, Hkv, Dh = _paged_widths(name)
    _compile(lambda q, k, v, bt, sl, st: paged_attention_pallas(
                 q, k, v, bt, sl, st),
             chip((B, H, Dh), BF), chip((nb, BS, Hkv, Dh), BF),
             chip((nb, BS, Hkv, Dh), BF), chip((B, max_blk), jnp.int32),
             chip((B,), jnp.int32), chip((B,), jnp.int32))


@pytest.mark.parametrize("T", [4, 32])
def test_moe_fused_compiles(chip, T):
    D, H, Dh, Hkv, E, F, K, _, _ = _dims()
    cap = capacity(T * K, E, QWEN.moe.capacity_factor, QWEN.moe.min_capacity)
    _compile(lambda x, g, u, d, w, p, a: moe_fused_pallas(
                 x, g, u, d, w, p, a, cap=cap, e_local=E),
             chip((T, D), BF), chip((E, D, F), BF), chip((E, D, F), BF),
             chip((E, F, D), BF), chip((T, K), jnp.float32),
             chip((T, K), jnp.int32), chip((T, K), jnp.bool_))


@pytest.mark.parametrize("B", [4, 32])
def test_decode_megastep_compiles(chip, B):
    D, H, Dh, Hkv, E, F, K, E_log, Fs = _dims()
    cap = capacity(B * K, E, QWEN.moe.capacity_factor, QWEN.moe.min_capacity)

    def step(q, kp, vp, bt, sl, st, x, wpost, ln2, rw, l2p, rc, em, g, u,
             d, sg, su, sd):
        return decode_megastep_pallas(
            q, kp, vp, bt, sl, st, x, wpost, ln2, rw, l2p, rc, em, g, u, d,
            0, sg, su, sd, top_k=K, cap=cap, e_local=E)

    _compile(step,
             chip((B, H, Dh), BF), chip((NB, BS, Hkv, Dh), BF),
             chip((NB, BS, Hkv, Dh), BF), chip((B, MAX_BLK), jnp.int32),
             chip((B,), jnp.int32), chip((B,), jnp.int32),
             chip((B, D), BF), chip((H * Dh, D), BF), chip((D,), BF),
             chip((D, E_log), BF), chip((E_log, 2), jnp.int32),
             chip((E_log,), jnp.int32), chip((E_log,), jnp.bool_),
             chip((E, D, F), BF), chip((E, D, F), BF), chip((E, F, D), BF),
             chip((D, Fs), BF), chip((D, Fs), BF), chip((Fs, D), BF))
