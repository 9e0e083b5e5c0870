"""Graph precompilation and cached compilation (§3.6).

The computation graph is compiled per (phase, domain version, shape
bucket).  Recovery changes the domain version (the post-failure world),
so a fresh executable is needed before inference can resume.  Three tiers,
mirroring the paper's Figure 5 categories:

* **precompiled**  — ReviveMoE precompiles executables for anticipated
  failure scenarios at startup; recovery-time cost is a dict lookup
  ("Read Cache" ~ 0, "Compile" ~ 0).
* **cached compile** — JAX's persistent compilation cache on disk plays
  the role of the saved Dynamo/Ascend-IR cache: the HLO is re-lowered but
  the expensive backend compile is served from disk.
* **cold compile** — nothing cached; the full compile (the paper's 12.9
  minute case, scaled down to our model sizes).

Every compile is timed and the (read_cache_s, compile_s, source) triple
is what benchmarks/recovery_time.py reports.

The on-disk tier lives where ``JAX_COMPILATION_CACHE_DIR`` says when it
is set (JAX reads it itself; nothing here sets another directory), and
otherwise at one fixed path inside the checkout, ``.jax_cache/``.  The
path is part of the cache's key, so it never moves with a workdir.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax

from repro.paths import REPO_ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = str(REPO_ROOT / ".jax_cache")


def persistent_cache_dir() -> str:
    """Where the persistent compilation cache lives (see module doc)."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


@dataclass
class CompileTiming:
    source: str            # 'precompiled' | 'cached' | 'cold'
    read_cache_s: float
    compile_s: float
    key: Tuple = ()


class GraphCache:
    def __init__(self, persist: bool = True):
        """persist: enables the on-disk compilation cache tier."""
        self.persist_dir = persistent_cache_dir() if persist else None
        if persist:
            if not os.environ.get(CACHE_ENV):
                jax.config.update("jax_compilation_cache_dir",
                                  self.persist_dir)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self._exec: Dict[Tuple, Any] = {}
        self.timings: list[CompileTiming] = []

    def __contains__(self, key) -> bool:
        return key in self._exec

    def precompile(self, key: Tuple, fn: Callable, arg_shapes: Tuple,
                   static_argnames=(), donate_argnums=()) -> CompileTiming:
        """AOT lower+compile now so recovery finds a ready executable.

        ``donate_argnums`` donates those inputs' buffers to the outputs
        (the engine donates the KV pool into decode/chunk steps — safe
        because the §3.3 row-level undo snapshots the written rows on the
        host *before* the step runs)."""
        t0 = time.perf_counter()
        lowered = jax.jit(fn, static_argnames=static_argnames,
                          donate_argnums=donate_argnums).lower(*arg_shapes)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        self._exec[key] = compiled
        tm = CompileTiming("precompiled", t1 - t0, t2 - t1, key)
        self.timings.append(tm)
        return tm

    def get_or_compile(self, key: Tuple, fn: Callable, arg_shapes: Tuple,
                       donate_argnums=()) -> Tuple[Any, CompileTiming]:
        """Recovery-time lookup: precompiled hit is ~free; otherwise a real
        (possibly persistent-cache-served) compile happens and is timed."""
        if key in self._exec:
            tm = CompileTiming("precompiled", 0.0, 0.0, key)
            self.timings.append(tm)
            return self._exec[key], tm
        t0 = time.perf_counter()
        lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*arg_shapes)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        self._exec[key] = compiled
        source = "cached" if self.persist_dir else "cold"
        tm = CompileTiming(source, t1 - t0, t2 - t1, key)
        self.timings.append(tm)
        return compiled, tm

    def invalidate(self, predicate: Callable[[Tuple], bool]) -> int:
        drop = [k for k in self._exec if predicate(k)]
        for k in drop:
            del self._exec[k]
        return len(drop)
