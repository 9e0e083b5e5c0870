"""§3.6 / Figure 1 analogue: graph compilation tiers.

Measures, for the decode graph of the serving model:
  cold          compile with the persistent cache turned off (the
                paper's 12.9-min full compile, scaled to our model)
  cached        same HLO recompiled with the persistent on-disk
                compilation cache enabled (the paper's Dynamo/Ascend-IR
                cache -> "Read Cache" + short "Compile")
  precompiled   ReviveMoE's failure-scenario precompilation: recovery-time
                cost is a lookup (~0)
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import get_smoke_config
from repro.core.graph_cache import GraphCache
from repro.models.model import Model


def _specs(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def run() -> List[Dict]:
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    model = Model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(4, 64))
    tok = jax.ShapeDtypeStruct((4,), jnp.int32)
    rt = jax.eval_shape(model.default_runtime)
    args = (params, cache, tok, rt)

    rows: List[Dict] = []

    def fresh_fn(tag):
        def fn(p, c, t, r):
            return model.decode_step(p, c, t, r)
        fn.__name__ = f"decode_{tag}"
        fn.__qualname__ = fn.__name__
        return fn

    # cold: the persistent cache turned off around this one compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        gc_cold = GraphCache(persist=False)
        _, tm = gc_cold.get_or_compile(("cold",), fresh_fn("cold"), args)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    rows.append({"tier": "cold_compile", "read_cache_s": tm.read_cache_s,
                 "compile_s": tm.compile_s})

    # populate the persistent cache (its one fixed directory), then
    # measure a cached compile of the SAME HLO under a new function
    # identity (what recovery does)
    gc_warm = GraphCache()
    gc_warm.get_or_compile(("warm0",), fresh_fn("warm0"), args)
    _, tm = gc_warm.get_or_compile(("warm1",), fresh_fn("warm1"), args)
    rows.append({"tier": "cached_compile", "read_cache_s": tm.read_cache_s,
                 "compile_s": tm.compile_s})

    # precompiled failure-scenario executable: recovery does a lookup
    gc_pre = GraphCache()
    gc_pre.precompile(("v1",), fresh_fn("v1"), args)
    t0 = time.perf_counter()
    _, tm = gc_pre.get_or_compile(("v1",), None, None)
    rows.append({"tier": "precompiled_lookup",
                 "read_cache_s": tm.read_cache_s,
                 "compile_s": time.perf_counter() - t0})
    return rows


def print_table(rows: List[Dict]) -> None:
    print("\n# §3.6 analogue: compile tiers (seconds)")
    print(f"{'tier':22s} {'read_cache':>11s} {'compile':>9s}")
    for r in rows:
        print(f"{r['tier']:22s} {r['read_cache_s']:11.3f} "
              f"{r['compile_s']:9.4f}")
    cold = rows[0]["read_cache_s"] + rows[0]["compile_s"]
    pre = rows[2]["read_cache_s"] + rows[2]["compile_s"]
    print(f"\nprecompiled vs cold speedup: {cold / max(pre, 1e-9):.0f}x "
          f"(paper: 12.9 min -> <10 s)")


if __name__ == "__main__":
    print_table(run())
