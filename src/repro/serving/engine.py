"""FlowServe-style inference engine with ReviveMoE recovery wired in.

One process simulates the whole deployment: executors are logical ranks
owning physically separate state (expert shards, KV caches, block
tables), so injected hardware failures destroy real state and recovery
manipulates real data structures, real compiled executables, and real
weight files.

Two deployment modes (§2.2):
* ``collocated``   — every device hosts attention + an EP expert shard.
* ``disaggregated`` — DPExecutors (attention) and MoEExecutors (experts)
  on separate devices; MoE failures can role-switch a DP rank (§3.4).
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.comm_domain import CommDomain
from repro.core.fault_codes import Action
from repro.core.detection import (AnnotationPoller, HeartbeatMonitor,
                                  StragglerDetector)
from repro.core.expert_map import ExpertMap
from repro.core.faults import FaultInjector, SimulatedDeviceFailure
from repro.core.graph_cache import GraphCache
from repro.core.weights import DenseFFNGroups, RecoveryPolicy
from repro.models.model import Model
from repro.serving.executor import DPExecutor, MoEExecutor, next_bucket
from repro.serving.request import Request, RequestState
from repro.serving.sampling import SamplingParams
from repro.serving.telemetry import (BUILD, CONTROL, HEALTH, Telemetry,
                                     register)
from repro.serving.weights_util import (bank_programs, split_experts,
                                        update_rank_slice)
from repro.training.checkpoint import restore_like, save_checkpoint


def _specs(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree)


def _decode_closure(model: Model, version: int):
    def fn(params, cache, tokens, page, runtime):
        return model.decode_step_paged(params, cache, tokens, page, runtime)
    fn.__name__ = f"decode_v{version}"
    fn.__qualname__ = fn.__name__
    return fn


def _chunk_closure(model: Model, version: int):
    # a chunked-prefill step IS a decode step over chunk-width virtual
    # slots (per-token page context); only the compiled width differs
    def fn(params, cache, tokens, page, runtime):
        return model.decode_step_paged(params, cache, tokens, page, runtime)
    fn.__name__ = f"chunk_v{version}"
    fn.__qualname__ = fn.__name__
    return fn


def _prefill_closure(model: Model, version: int, max_seq: int):
    def fn(params, tokens, lengths, runtime):
        batch = {"tokens": tokens, "lengths": lengths}
        return model.prefill_paged(params, batch, runtime)
    fn.__name__ = f"prefill_v{version}"
    fn.__qualname__ = fn.__name__
    return fn


def _install_closure(axes_leaves, bucket: int):
    from repro.serving.cache_ops import install_prefill

    def fn(cache, raw, block_ids, slot):
        return install_prefill(cache, raw, axes_leaves, block_ids, slot)
    fn.__name__ = f"install_b{bucket}"
    fn.__qualname__ = fn.__name__
    return fn


@functools.lru_cache(maxsize=None)
def _jitted_init(cfg: ModelConfig, dtype):
    # one traced program: the random draws never materialize in float32
    # beside the weights, and equal (config, dtype) pairs share it
    return jax.jit(Model(cfg, dtype=dtype).init)


class _Ctx:
    """What an executor sees during compute: weights + compiled fns."""

    def __init__(self, engine: "InferenceEngine"):
        self.engine = engine
        self.params = engine.params
        self.runtime = engine.runtime

    def decode_fn(self, *args):
        return self.engine.get_compiled("decode")( *args)

    def chunk_fn(self):
        return self.engine.get_compiled("chunk")

    def prefill_fn(self, bucket: int):
        return self.engine.get_compiled("prefill", bucket)

    def install_fn(self, bucket: int):
        return self.engine.get_compiled("install", bucket)


@dataclass
class EngineConfig:
    mode: str = "collocated"            # 'collocated' | 'disaggregated'
    num_dp: int = 2
    num_moe: int = 2                    # disaggregated only
    max_batch: int = 4
    max_seq: int = 128
    block_size: int = 16
    num_blocks: int = 128
    sampling: SamplingParams = field(default_factory=SamplingParams)
    seed: int = 0
    workdir: str = "/tmp/repro_engine"
    policy: RecoveryPolicy = field(default_factory=RecoveryPolicy)
    precompile_failure_scenarios: bool = True
    # weights and KV pools; a CPU test whose tolerance needs float32
    # asks for it here
    dtype: str = "bfloat16"
    heartbeat_timeout_steps: int = 2
    # override ModelConfig.moe_impl (e.g. 'fused' routes the MoE layer
    # through the fused Pallas dispatch->FFN->combine pipeline); None
    # keeps the model config's choice
    moe_impl: Optional[str] = None
    # override ModelConfig.decode_impl: 'megakernel' fuses each
    # attention+MoE block's decode/chunk step into one kernel launch
    # (ops.decode_megastep); None keeps the model config's choice, whose
    # default — 'composed' — is the kernel-chain oracle path
    decode_impl: Optional[str] = None
    # -- admission pipeline ---------------------------------------------------
    # 'chunked': token-budget continuous batching — many prefills per
    #   step, each chunked so long prompts interleave with decodes
    #   (attention-only models; recurrent-state models whole-prefill
    #   under the same budget).
    # 'serial': the legacy one-whole-prefill-per-step baseline.
    admission: str = "chunked"
    prefill_chunk: int = 32             # batched chunk width (tokens)
    # per-step decode+prefill token target; None -> max_batch + chunk
    token_budget: Optional[int] = None
    # content-hash shared-prefix block reuse across requests (COW at the
    # divergence block); chunked admission only
    prefix_cache: bool = True
    # §3.3 device-pool rollback strategy: 'rows' restores only the
    # step's captured write set (donation-friendly); 'snapshot' keeps
    # the legacy O(1) functional reference to the whole cache
    pool_undo: str = "rows"
    # multi-token self-speculative decode: > 1 lets decode-ready
    # requests verify up to this many tokens per step through the
    # compiled chunk graph (n-gram self-drafts, deterministic
    # accept/reject — output stays token-identical to plain decode).
    # 0/1 disables; chunked admission only (recurrent-prefill models
    # fall back to plain decode automatically)
    spec_window: int = 0
    # async pipelined engine: while step N runs on device, plan step N+1
    # against the predicted post-N state (speculative host bookkeeping
    # only — token streams stay bit-identical to lockstep, and §3.3
    # rollback/replay is unchanged because every plan-ahead frame
    # unwinds before recovery looks at the tables).  Tokens are sampled
    # on-device and drained one step late through a small ring of
    # in-flight D2H copies.  Requires chunked admission + row-level
    # pool undo; models without chunked-prefill support fall back to
    # lockstep automatically.
    overlap: bool = False

    def __post_init__(self):
        # ValueError (not assert) so misconfiguration still fails loudly
        # under `python -O`
        if self.mode not in ("collocated", "disaggregated"):
            raise ValueError(
                f"EngineConfig.mode must be 'collocated' or "
                f"'disaggregated', got {self.mode!r}")
        for name in ("num_dp", "max_batch", "max_seq", "block_size",
                     "num_blocks"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"EngineConfig.{name} must be a positive int, "
                    f"got {v!r}")
        if not isinstance(self.num_moe, int) or self.num_moe < 0:
            raise ValueError(
                f"EngineConfig.num_moe must be a non-negative int, "
                f"got {self.num_moe!r}")
        if self.dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"EngineConfig.dtype must be 'bfloat16' or 'float32', "
                f"got {self.dtype!r}")
        if self.heartbeat_timeout_steps < 1:
            raise ValueError(
                f"EngineConfig.heartbeat_timeout_steps must be >= 1, "
                f"got {self.heartbeat_timeout_steps!r}")
        if (self.moe_impl is not None
                and self.moe_impl not in ModelConfig.MOE_IMPLS):
            raise ValueError(
                f"EngineConfig.moe_impl must be one of "
                f"{ModelConfig.MOE_IMPLS} or None, got {self.moe_impl!r}")
        if (self.decode_impl is not None
                and self.decode_impl not in ModelConfig.DECODE_IMPLS):
            raise ValueError(
                f"EngineConfig.decode_impl must be one of "
                f"{ModelConfig.DECODE_IMPLS} or None, "
                f"got {self.decode_impl!r}")
        if self.admission not in ("chunked", "serial"):
            raise ValueError(
                f"EngineConfig.admission must be 'chunked' or 'serial', "
                f"got {self.admission!r}")
        if not isinstance(self.prefill_chunk, int) or self.prefill_chunk < 1:
            raise ValueError(
                f"EngineConfig.prefill_chunk must be a positive int, "
                f"got {self.prefill_chunk!r}")
        # None stays None here (resolved at executor construction from
        # the *final* max_batch/prefill_chunk, so dataclasses.replace
        # after construction cannot freeze a stale default)
        if self.token_budget is not None and (
                not isinstance(self.token_budget, int)
                or self.token_budget < 1):
            raise ValueError(
                f"EngineConfig.token_budget must be a positive int or "
                f"None, got {self.token_budget!r}")
        if self.pool_undo not in ("rows", "snapshot"):
            raise ValueError(
                f"EngineConfig.pool_undo must be 'rows' or 'snapshot', "
                f"got {self.pool_undo!r}")
        if not isinstance(self.spec_window, int) or self.spec_window < 0:
            raise ValueError(
                f"EngineConfig.spec_window must be a non-negative int, "
                f"got {self.spec_window!r}")
        if self.spec_window > self.prefill_chunk:
            raise ValueError(
                f"EngineConfig.spec_window ({self.spec_window}) cannot "
                f"exceed prefill_chunk ({self.prefill_chunk}) — verify "
                f"windows ride the chunk graph")
        if self.overlap and self.pool_undo != "rows":
            raise ValueError(
                "EngineConfig.overlap requires pool_undo='rows' — "
                "stacked plan-ahead frames restore per-frame write "
                "sets; the whole-pool snapshot cannot unwind one frame "
                "at a time")
        if self.overlap and self.admission != "chunked":
            raise ValueError(
                "EngineConfig.overlap requires admission='chunked' — "
                "whole-prefill installs synchronize with the device "
                "and cannot be planned ahead")


@dataclass
class InstanceHealth:
    """Engine health surface consumed by the fleet control plane."""
    serving: bool                # >=1 healthy attention rank
    healthy_dp: int
    total_dp: int
    healthy_moe: int
    total_moe: int
    expert_coverage: float       # 1.0 = every logical expert has a live slot
    queue_depth: int             # waiting + running on healthy ranks
    unfinished: int
    soft_signals: Dict[int, float] = field(default_factory=dict)
    # physical_id -> slowdown ratio vs fleet median (straggler suspicion)

    @property
    def degraded(self) -> bool:
        return (self.healthy_dp < self.total_dp
                or self.healthy_moe < self.total_moe
                or self.expert_coverage < 1.0
                or bool(self.soft_signals))


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig = None):
        import dataclasses
        self.ecfg = engine_cfg or EngineConfig()
        if self.ecfg.moe_impl is not None and cfg.moe is not None:
            cfg = dataclasses.replace(cfg, moe_impl=self.ecfg.moe_impl)
        if self.ecfg.decode_impl is not None:
            cfg = dataclasses.replace(cfg, decode_impl=self.ecfg.decode_impl)
        self.cfg = cfg
        if cfg.moe is None:
            # dense model: no expert ranks; disaggregated degenerates
            self.ecfg.mode = "collocated"
        self.init_timings: Dict[str, float] = {}
        self.step_no = 0
        self.reports: List[Any] = []
        self.all_requests: List[Request] = []
        self._handled_faults: set = set()
        # §4.3: role switches deferred by the background policy; executed
        # between steps while service continues
        self.pending_switches: List[Any] = []
        self.background_reports: List[Dict] = []
        # fleet hook: called with each actionable FaultEvent BEFORE the
        # in-place revive pipeline runs; returning anything other than
        # "revive" defers handling to the fleet control plane (the engine
        # only isolates the failed device; the router tracks the rest)
        self.fault_interceptor = None
        # latest straggler suspicion {physical_id: slowdown ratio}
        self.soft_signals: Dict[int, float] = {}
        # campaign determinism hook: when set, straggler detection
        # samples this fixed virtual step duration (+ any simulated
        # slowdown) instead of the wall clock, so chaos campaigns are a
        # pure function of their seed
        self.virtual_step_s: Optional[float] = None
        # spans of every step, build and revive; registered once, so a
        # rebuild (revive, restart) keeps recording into the same one
        self.telemetry = Telemetry()
        register(self.telemetry)
        self._build(first_time=True)

    # -- construction / reinitialization ---------------------------------------

    def _build(self, first_time: bool) -> Dict[str, float]:
        with self.telemetry.span(BUILD):
            return self._build_stages(first_time)

    def _build_stages(self, first_time: bool) -> Dict[str, float]:
        ec = self.ecfg
        t: Dict[str, float] = {}

        def stage(key):
            return self.telemetry.stage(BUILD, t, key)

        with stage("engine"):
            # paper baseline is a *cached* reinit: the compile cache lives
            # on disk (Dynamo/IR cache analogue = XLA persistent cache)
            self.graph_cache = getattr(self, "graph_cache", None) or \
                GraphCache()
            self.injector = getattr(self, "injector", None) or FaultInjector()
            self.poller = AnnotationPoller(self.injector)
            self.monitor = HeartbeatMonitor(ec.heartbeat_timeout_steps)
            self.straggler = StragglerDetector()
            self.model = Model(self.cfg, dtype=jnp.dtype(ec.dtype))
            from repro.serving.cache_ops import infer_paged_axes
            _, self.paged_axes = infer_paged_axes(
                self.model, ec.num_blocks, ec.block_size)
            os.makedirs(ec.workdir, exist_ok=True)
            self.ckpt_path = os.path.join(ec.workdir, "weights.npz")

        with stage("generator"):
            # model instantiation + weight loading + KV warmup
            # the device holds the weights once: ``self.params`` is the
            # served tree, expert bank included (every rank alive)
            if os.path.exists(self.ckpt_path):
                template = self.model.param_specs()
                self.params = jax.tree_util.tree_map(
                    jnp.asarray, restore_like(self.ckpt_path, template))
            else:
                self.params = _jitted_init(self.cfg, self.model.dtype)(
                    jax.random.PRNGKey(ec.seed))
                save_checkpoint(self.ckpt_path, self.params)
            self.ep_size = (ec.num_moe if ec.mode == "disaggregated"
                            else ec.num_dp) if self.cfg.moe else 0
            if self.cfg.moe is not None:
                self.shards = split_experts(self.params, self.ep_size)
                from repro.serving.weights_util import save_shard_checkpoints
                save_shard_checkpoints(ec.workdir, self.shards)
                self.expert_map = ExpertMap(self.cfg.moe, self.ep_size)
                self.runtime = self.expert_map.runtime()
                # the host shard whose weights each rank's slice holds
                self.bank_shards = list(self.shards)
                self.dense_groups = (
                    DenseFFNGroups(max(2, self.ep_size // 2))
                    if self.cfg.moe.first_k_dense else None)
            else:
                self.shards = []
                self.expert_map = None
                self.runtime = None
                self.bank_shards = []
                self.dense_groups = None

        with stage("executor_processes"):
            self.dp_executors: List[DPExecutor] = []
            for i in range(ec.num_dp):
                shard = None
                ep_rank = None
                if self.cfg.moe is not None and ec.mode == "collocated":
                    shard, ep_rank = self.shards[i], i
                self.dp_executors.append(
                    self._make_dp_executor(i, i, shard=shard,
                                           ep_rank=ep_rank))
            self.moe_executors: List[MoEExecutor] = []
            if self.cfg.moe is not None and ec.mode == "disaggregated":
                for j in range(ec.num_moe):
                    self.moe_executors.append(MoEExecutor(
                        physical_id=ec.num_dp + j, ep_rank=j,
                        shard=self.shards[j]))
            for ex in self.dp_executors + self.moe_executors:
                self.monitor.register(ex.physical_id, self.step_no)

        with stage("distributed_groups"):
            # torch.distributed analogue: default world group + subgroups
            self.world_group = [ex.physical_id for ex in
                                self.dp_executors + self.moe_executors]

        with stage("xccl"):
            self.domain = CommDomain(
                ec.num_dp,
                ec.num_moe if ec.mode == "disaggregated" else 0,
                collocated=(ec.mode == "collocated"))
            if not first_time:
                self.domain.version = self._next_version
            self.domain.rebuild()

        # initial graph compilation (Fig. 1 "Read Cache"/"Compile")
        with self.telemetry.span(BUILD + ".graph"):
            self._compile_initial(t)

        if first_time and ec.precompile_failure_scenarios:
            with stage("precompile_failure_scenarios"):
                self._precompile_failure_graphs()

        with stage("other"):
            from repro.core.revive import RecoveryManager
            self.recovery = RecoveryManager(self)
        self.init_timings = t
        return t

    def _make_dp_executor(self, physical_id: int, dp_rank: int, *,
                          shard=None, ep_rank: Optional[int] = None
                          ) -> DPExecutor:
        ec = self.ecfg
        return DPExecutor(
            physical_id=physical_id, dp_rank=dp_rank, model=self.model,
            max_batch=ec.max_batch, max_seq=ec.max_seq,
            num_blocks=ec.num_blocks, block_size=ec.block_size,
            sampling=ec.sampling, ep_rank=ep_rank, shard=shard,
            paged_axes=self.paged_axes,
            admission=ec.admission,
            prefill_chunk=ec.prefill_chunk,
            token_budget=(ec.token_budget
                          if ec.token_budget is not None
                          else ec.max_batch + ec.prefill_chunk),
            prefix_cache=ec.prefix_cache,
            pool_undo=ec.pool_undo,
            spec_window=ec.spec_window,
            telemetry=self.telemetry)

    @property
    def _next_version(self) -> int:
        return self.domain.version + 1 if hasattr(self, "domain") else 0

    def _cache_specs(self):
        return jax.eval_shape(
            lambda: self.model.init_paged_cache(
                self.ecfg.max_batch, self.ecfg.num_blocks,
                self.ecfg.block_size))

    def _arg_specs(self, phase: str, bucket: Optional[int] = None):
        from repro.serving.kvcache import (max_blocks_per_seq,
                                           page_context_specs)
        p_specs = _specs(self.params)
        r_specs = _specs(self.runtime)
        if phase in ("decode", "chunk"):
            width = (self.ecfg.max_batch if phase == "decode"
                     else self.ecfg.prefill_chunk)
            c_specs = self._cache_specs()
            tok = jax.ShapeDtypeStruct((width,), jnp.int32)
            page = page_context_specs(
                width,
                max_blocks_per_seq(self.ecfg.max_seq, self.ecfg.block_size))
            return (p_specs, c_specs, tok, page, r_specs)
        toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        lens = jax.ShapeDtypeStruct((1,), jnp.int32)
        if phase == "install":
            raw_specs = jax.eval_shape(self.model.prefill_paged, p_specs,
                                       {"tokens": toks, "lengths": lens},
                                       r_specs)[1]
            nblk = max_blocks_per_seq(bucket, self.ecfg.block_size)
            bids = jax.ShapeDtypeStruct((nblk,), jnp.int32)
            slot = jax.ShapeDtypeStruct((), jnp.int32)
            return (self._cache_specs(), raw_specs, bids, slot)
        return (p_specs, toks, lens, r_specs)

    def _donate(self, phase: str) -> tuple:
        """Donate the KV pool (cache, arg 1) into the compiled decode and
        chunk steps so the token writes happen in place (carry-over (h)).
        Safe only under row-level undo: ``plan()`` captures the step's
        write-set rows *before* compute, so rollback never needs the
        pre-step buffers.  The legacy 'snapshot' strategy keeps a live
        reference to them and must not donate."""
        if phase in ("decode", "chunk") and self.ecfg.pool_undo == "rows":
            return (1,)
        return ()

    def _compile_initial(self, t: Dict[str, float]) -> None:
        v = self.domain.version
        phases = [("decode", _decode_closure(self.model, v))]
        if self._chunking:
            phases.append(("chunk", _chunk_closure(self.model, v)))
        for phase, fn in phases:
            key = (phase, v, None)
            if key not in self.graph_cache:
                _, tm = self.graph_cache.get_or_compile(
                    key, fn, self._arg_specs(phase),
                    donate_argnums=self._donate(phase))
                t["read_cache"] = t.get("read_cache", 0.0) + tm.read_cache_s
                t["compile"] = t.get("compile", 0.0) + tm.compile_s
            else:
                self.graph_cache.get_or_compile(key, fn,
                                                self._arg_specs(phase))

    def _bank_fn(self, key: tuple):
        """Compiled in-place rank-slice update of the expert bank (keys of
        ``weights_util.bank_programs``); like the install scatter it has
        no collectives, so one executable serves every domain version
        and every rank."""
        if key in self.graph_cache:
            return self.graph_cache.get_or_compile(key, None, None)[0]
        fn, specs = bank_programs(self.params, self.ep_size)[key]
        return self.graph_cache.get_or_compile(key, fn, specs,
                                               donate_argnums=(0,))[0]

    def _precompile_failure_graphs(self) -> None:
        """§3.6: precompile graphs for the anticipated failure scenario
        (post-failure domain version), so recovery does a cached compile."""
        if self.cfg.moe is not None:
            # a lost rank zeroes its bank slice, a restored one writes it
            for key, (fn, specs) in bank_programs(self.params,
                                                  self.ep_size).items():
                if key not in self.graph_cache:
                    self.graph_cache.precompile(key, fn, specs,
                                                donate_argnums=(0,))
        v = self.domain.version + 1
        self.graph_cache.precompile(
            ("decode", v, None), _decode_closure(self.model, v),
            self._arg_specs("decode"),
            donate_argnums=self._donate("decode"))
        if self._chunking:
            # chunked admission re-prefills migrated/rolled-back requests
            # through the chunk graph — it must be ready post-failure
            self.graph_cache.precompile(
                ("chunk", v, None), _chunk_closure(self.model, v),
                self._arg_specs("chunk"),
                donate_argnums=self._donate("chunk"))
            return
        # whole-prefill path: the most common prefill bucket is needed
        # right after migration
        b = next_bucket(16, self.ecfg.max_seq)
        self.graph_cache.precompile(
            ("prefill", v, b),
            _prefill_closure(self.model, v, self.ecfg.max_seq),
            self._arg_specs("prefill", b))
        if ("install", 0, b) not in self.graph_cache:
            self.graph_cache.precompile(
                ("install", 0, b), _install_closure(self.paged_axes, b),
                self._arg_specs("install", b))

    @property
    def _chunking(self) -> bool:
        return (self.ecfg.admission == "chunked"
                and self.model.supports_chunked_prefill)

    @property
    def _overlap_active(self) -> bool:
        # recurrent-prefill models fall back to lockstep (they cannot
        # chunk, so plan-ahead would have to predict whole prefills)
        return self.ecfg.overlap and self._chunking

    # -- compiled-fn access ------------------------------------------------------

    def get_compiled(self, phase: str, bucket: Optional[int] = None):
        return self.compiled_with_timing(phase, bucket)[0]

    def compiled_with_timing(self, phase: str,
                             bucket: Optional[int] = None):
        """The executable of ``phase`` for the current domain version, and
        the :class:`~repro.core.graph_cache.CompileTiming` of getting it."""
        # the install scatter has no collectives: its graph is domain-
        # version independent and survives every comm rebuild
        v = 0 if phase == "install" else self.domain.version
        key = (phase, v, bucket if phase in ("prefill", "install") else None)
        if key in self.graph_cache:
            return self.graph_cache.get_or_compile(key, None, None)
        if phase == "decode":
            fn = _decode_closure(self.model, v)
        elif phase == "chunk":
            fn = _chunk_closure(self.model, v)
        elif phase == "install":
            fn = _install_closure(self.paged_axes, bucket)
        else:
            fn = _prefill_closure(self.model, v, self.ecfg.max_seq)
        return self.graph_cache.get_or_compile(
            key, fn, self._arg_specs(phase, bucket),
            donate_argnums=self._donate(phase))

    # -- request API ----------------------------------------------------------------

    def submit(self, prompt_tokens: List[int], max_new_tokens: int = 16,
               eos_token: Optional[int] = None) -> Request:
        req = Request(list(prompt_tokens), max_new_tokens,
                      eos_token=eos_token)
        self._assign(req)
        self.all_requests.append(req)
        return req

    # a prefix-affine executor may be at most this many requests busier
    # than the least-loaded one (mirrors FleetRouter.AFFINITY_SLACK —
    # cache hits must not create hotspots within the instance either)
    ASSIGN_AFFINITY_SLACK = 4

    def _assign(self, req: Request) -> None:
        """Pick an attention rank for a request: least-loaded, biased
        toward in-instance prefix affinity (ROADMAP paged-KV (i)) — the
        DP executor whose BlockManager already holds the prompt's
        leading full-block digests serves the shared prefix from its
        cache instead of recomputing it on a cold rank, unless it is
        more than ``ASSIGN_AFFINITY_SLACK`` requests busier than the
        least-loaded executor."""
        healthy = [ex for ex in self.dp_executors
                   if ex.alive and ex.cache is not None]
        if not healthy:
            raise RuntimeError(
                "no healthy attention ranks left on this instance")
        least = min(healthy, key=lambda e: e.scheduler.num_requests)
        ex = least
        digests = None
        if (len(healthy) > 1 and self._chunking and self.ecfg.prefix_cache
                and len(req.tokens_so_far) > self.ecfg.block_size):
            from repro.core.block_log import prompt_digests
            digests = prompt_digests(tuple(req.tokens_so_far),
                                     self.ecfg.block_size)
            best, best_hits = None, 0
            for cand in healthy:
                hits = cand.prefix_hit_blocks(digests,
                                              len(req.tokens_so_far))
                if hits > best_hits:
                    best, best_hits = cand, hits
            if (best is not None
                    and best.scheduler.num_requests
                    <= least.scheduler.num_requests
                    + self.ASSIGN_AFFINITY_SLACK):
                ex = best
        req.dp_rank = ex.dp_rank
        ex.scheduler.add_request(req)
        if digests is not None:
            # hand the chain digests to the scheduler's per-request memo
            # so admission doesn't rehash the prompt _assign just hashed
            ex.scheduler.memo_digests(req.req_id, digests)

    def admit(self, req: Request, kv=None) -> Request:
        """Admit a request created elsewhere (cross-instance migration).

        With a :class:`~repro.core.migration.KVBlocks` payload the least-
        loaded healthy executor installs the streamed blocks directly —
        the request skips re-prefill and decodes on the next step.
        Without one (or if no executor can take the blocks) it re-enters
        with prompt + decoded prefix intact, so the next prefill resumes
        generation without redoing completed tokens."""
        if kv is not None:
            healthy = sorted(
                (ex for ex in self.dp_executors
                 if ex.alive and ex.cache is not None),
                key=lambda e: e.scheduler.num_requests)
            for ex in healthy:
                if ex.import_kv_blocks(req, kv):
                    if all(r is not req for r in self.all_requests):
                        self.all_requests.append(req)
                    return req
            # stream install failed (no slot/blocks): the prefix must be
            # re-prefilled after all — charge the replay now
            from repro.core.migration import charge_replay
            charge_replay(req)
        self._assign(req)
        if all(r is not req for r in self.all_requests):
            self.all_requests.append(req)
        return req

    def export_live_requests(self, with_kv: bool = False):
        """Fleet drain/export hook: strip every unfinished request off
        this instance — dead executors included, their token ids live in
        host memory.  With ``with_kv``, each RUNNING request's live
        blocks are extracted first from executors whose device state is
        still reachable (rollback-then-migrate: any uncommitted step is
        rolled back before the read, so tables and pools agree) and the
        result is ``[(req, KVBlocks | None)]``; a None payload means
        token-replay re-prefill on the target."""
        from repro.core.migration import prepare_for_migration
        out = []
        for ex in self.dp_executors:
            payloads = {}
            # pipeline quiesce before the export: the in-flight step's
            # readback already landed, so its outcome commits; leftover
            # speculative overlays must not leak into migration prompts,
            # even from dead executors (rollback is cache-None-safe)
            if ex._inflight is not None:
                ex.flush(None)
            if ex.has_uncommitted():
                ex.rollback_inflight()
            if with_kv and ex.alive and ex.cache is not None:
                for req in list(ex.scheduler.running):
                    blocks_kv = ex.export_kv_blocks(req)
                    if blocks_kv is not None:
                        payloads[req.req_id] = blocks_kv
            for req in ex.scheduler.drain():
                if req.state in (RequestState.FINISHED,
                                 RequestState.FAILED):
                    continue
                blocks_kv = payloads.get(req.req_id)
                prepare_for_migration(req, streamed=blocks_kv is not None)
                out.append((req, blocks_kv) if with_kv else req)
        gone = {(r[0] if with_kv else r).req_id for r in out}
        self.all_requests = [r for r in self.all_requests
                             if r.req_id not in gone]
        return out

    def streamable_split(self) -> Tuple[int, int]:
        """(streamable, replay-only) token counts over this instance's
        unfinished requests — the spare-substitution cost split: RUNNING
        requests on reachable executors can stream their KV blocks;
        everything else re-prefills on the target."""
        stream = replay = 0
        for ex in self.dp_executors:
            reachable = ex.alive and ex.cache is not None
            for r in list(ex.scheduler.waiting) + list(ex.scheduler.running):
                if r.state in (RequestState.FINISHED, RequestState.FAILED):
                    continue
                if (reachable and r.state is RequestState.RUNNING
                        and r.batch_slot is not None and r.output_tokens):
                    stream += r.num_tokens
                else:
                    replay += r.num_tokens
        return stream, replay

    def predict_masked_fraction(self, rank: int) -> float:
        """Fraction of logical experts that would lose every live replica
        if physical ``rank``'s expert slots died — the degraded-quality
        input to the fleet cost model (revive may serve with those
        experts masked until a role switch restores them)."""
        if self.expert_map is None:
            return 0.0
        ep_rank = None
        for ex in self.dp_executors:
            if ex.physical_id == rank:
                ep_rank = ex.ep_rank
        for mex in self.moe_executors:
            if mex.physical_id == rank:
                ep_rank = mex.ep_rank
        if ep_rank is None:
            return 0.0
        emap = self.expert_map
        dead = set(emap.rank_slots(ep_rank))
        lost = sum(
            1 for e in range(emap.moe.num_experts)
            if e not in emap.masked
            and not [s for s in emap.replicas_of(e) if s not in dead])
        return lost / emap.moe.num_experts

    def health(self) -> InstanceHealth:
        healthy_dp = [ex for ex in self.dp_executors
                      if ex.alive and ex.cache is not None]
        healthy_moe = [m for m in self.moe_executors if m.device_alive]
        cov = (self.expert_map.coverage()
               if self.expert_map is not None else 1.0)
        return InstanceHealth(
            serving=bool(healthy_dp),
            healthy_dp=len(healthy_dp), total_dp=len(self.dp_executors),
            healthy_moe=len(healthy_moe),
            total_moe=len(self.moe_executors),
            expert_coverage=cov,
            queue_depth=sum(ex.scheduler.num_requests
                            for ex in healthy_dp),
            unfinished=self.unfinished,
            soft_signals=dict(self.soft_signals))

    @property
    def unfinished(self) -> int:
        return sum(1 for r in self.all_requests
                   if r.state not in (RequestState.FINISHED,
                                      RequestState.FAILED))

    def prefill_stats(self) -> Dict[str, int]:
        """Aggregated admission-pipeline counters across attention ranks:
        prefill tokens actually computed vs skipped via the shared-prefix
        cache, chunk count, window-freed blocks, and the BlockManagers'
        cache acquire/eviction counters."""
        out: Dict[str, int] = {}
        for ex in self.dp_executors:
            for k, val in ex.scheduler.stats.items():
                out[k] = out.get(k, 0) + val
            out["prefix_cache_hits"] = (out.get("prefix_cache_hits", 0)
                                        + ex.block_manager.cache_hits)
            out["prefix_cache_evictions"] = (
                out.get("prefix_cache_evictions", 0)
                + ex.block_manager.cache_evictions)
        return out

    def spec_histogram(self) -> Dict[int, int]:
        """Speculation-window width histogram ({planned rows: count})
        aggregated across attention ranks — the spec-efficiency surface
        the benchmarks record next to accepted tokens/step."""
        out: Dict[int, int] = {}
        for ex in self.dp_executors:
            for g, n in ex.scheduler.spec_hist.items():
                out[g] = out.get(g, 0) + n
        return out

    # -- main loop --------------------------------------------------------------------

    def step(self) -> List[Request]:
        self.step_no += 1
        with self.telemetry.step(self.step_no):
            if self._overlap_active:
                return self._step_overlap()
            return self._step_lockstep()

    def _pre_step(self) -> None:
        """Finish deferred role switches in the background (§4.3: service
        already resumed; these timings are not downtime), then fire the
        faults due before the step."""
        while self.pending_switches:
            plan = self.pending_switches.pop(0)
            self.background_reports.append(
                self.recovery.complete_background_switch(plan))
        self.injector.pre_step_faults(self.step_no)

    def _fail_mid_step(self, executors) -> bool:
        """Mid-step faults fire while the collective step is in flight;
        True when one did (its annotation is then pending)."""
        hit = False
        for ex in executors:
            try:
                self.injector.maybe_fail_mid_step(self.step_no,
                                                  ex.physical_id)
            except SimulatedDeviceFailure:
                ex.fail_device()
                hit = True
        return hit

    def _step_health(self, samples) -> List[Any]:
        """Record each ``(executor, wall seconds, compiled)`` sample of
        the step and run the straggler check (slowdown detection, §6
        future work); returns the straggler events."""
        for ex, wall_s, compiled in samples:
            if self.virtual_step_s is not None:
                base = self.virtual_step_s
            elif compiled:
                continue     # a step that compiled says nothing of speed
            else:
                base = wall_s
            self.straggler.record(ex.physical_id,
                                  base + ex.simulated_slowdown_s)
        # soft signal: suspicion that has not yet hardened into an L4
        # fault, surfaced via health() for the fleet arbiter to act on
        self.soft_signals = self.straggler.suspects()
        return list(self.straggler.check())

    def _beat(self) -> None:
        for ex in self.dp_executors + self.moe_executors:
            alive = (ex.device_alive if isinstance(ex, MoEExecutor)
                     else ex.alive)
            if alive:
                self.monitor.beat(ex.physical_id, self.step_no)

    def _step_lockstep(self) -> List[Request]:
        tel = self.telemetry
        with tel.span(CONTROL, step=self.step_no):
            self._pre_step()
            for ev in self.poller.poll():
                self._handle(ev)
            for ev in self.monitor.check(self.step_no):
                self._handle(ev)

        active = [ex for ex in self.dp_executors
                  if ex.alive and ex.cache is not None
                  and ex.scheduler.num_requests]
        for ex in active:
            ex.plan()

        with tel.span(CONTROL, step=self.step_no):
            hit = self._fail_mid_step(
                active + [m for m in self.moe_executors if m.device_alive])
            if hit:
                # global stop: the step aborts with uncommitted logs
                # everywhere; detection fires on the annotation just
                # recorded
                for ev in self.poller.poll():
                    self._handle(ev)
        if hit:
            return []

        finished: List[Request] = []
        ctx = _Ctx(self)
        samples = []
        for ex in active:
            t0, n0 = time.perf_counter(), tel.step_compiles
            finished.extend(ex.compute(ctx, self.step_no))
            ex.commit()
            samples.append((ex, time.perf_counter() - t0,
                            tel.step_compiles != n0))
        with tel.span(HEALTH, step=self.step_no):
            for ev in self._step_health(samples):
                self._handle(ev)
            self._beat()
        return finished

    def _step_overlap(self) -> List[Request]:
        """Pipelined step: each executor plans+launches step N against
        the predicted post-(N-1) state, then drains step N-1 (whose
        logits forced while N's plan was being built on the host).
        Fault handling is strictly *before* any executor work and always
        quiesces the pipeline first — flush the in-flight step (its
        readback predates the fault), roll back anything else — so
        recovery, and the migration/replay machinery behind it, sees
        exactly the state lockstep would have committed."""
        tel = self.telemetry
        finished: List[Request] = []
        with tel.span(CONTROL, step=self.step_no):
            self._pre_step()
            events = list(self.poller.poll()) + list(
                self.monitor.check(self.step_no))
            if events:
                finished.extend(self._quiesce_inflight())
                for ev in events:
                    self._handle(ev)
            # mid-step faults fire while the previous step's collective
            # is still in flight — the canonical §3.3 scenario the
            # pipeline must survive: the already-drained-readback step
            # commits, the faulted step's partial work rolls back, and
            # replay regenerates everything after the commit point
            # bit-identically
            hit = self._fail_mid_step(
                [ex for ex in self.dp_executors
                 if ex.alive and ex.cache is not None]
                + [m for m in self.moe_executors if m.device_alive])
            if hit:
                finished.extend(self._quiesce_inflight())
                for ev in self.poller.poll():
                    self._handle(ev)
        if hit:
            return finished

        ctx = _Ctx(self)
        samples = []
        for ex in self.dp_executors:
            if not (ex.alive and ex.cache is not None):
                continue
            if not (ex.scheduler.num_requests or ex._inflight is not None):
                continue
            t0, n0 = time.perf_counter(), tel.step_compiles
            finished.extend(ex.overlap_step(ctx, self.step_no))
            samples.append((ex, time.perf_counter() - t0,
                            tel.step_compiles != n0))
        with tel.span(HEALTH, step=self.step_no):
            events = self._step_health(samples)
            if events:
                finished.extend(self._quiesce_inflight())
                for ev in events:
                    self._handle(ev)
            self._beat()
        return finished

    def _quiesce_inflight(self) -> List[Request]:
        """Retire the pipeline before recovery or migration reads
        request/table state.  The in-flight step launched a full engine
        step before the fault fired, so its token-id readback was
        already on the wire — flush commits its authoritative outcome
        through the normal drain path, exactly the step lockstep had
        already committed synchronously (this is what keeps fault-path
        token streams bit-identical to lockstep).  Anything still
        uncommitted afterwards rolls back via §3.3.  Runs on *all* DP
        executors — a FAILED executor's pending outcome still commits
        (its readback preceded the fault), and its overlays must never
        leak into the migration replay prompt (rollback is
        cache-None-safe)."""
        finished: List[Request] = []
        for ex in self.dp_executors:
            if ex._inflight is not None:
                finished.extend(ex.flush(None))
            if ex.has_uncommitted():
                ex.rollback_inflight()
        return finished

    def overlap_stats(self) -> Dict[str, int]:
        """Aggregated pipeline counters across attention ranks."""
        out = {"steps": 0, "planned_ahead": 0, "replans": 0, "drains": 0}
        for ex in self.dp_executors:
            for k, v in ex.overlap_stats.items():
                out[k] = out.get(k, 0) + v
        return out

    def run(self, max_steps: int = 1000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.unfinished:
                break
            done.extend(self.step())
        return done

    # -- failure handling ------------------------------------------------------------

    def _handle(self, ev) -> None:
        if ev.rank in self._handled_faults:
            return
        self._handled_faults.add(ev.rank)
        if (self.fault_interceptor is not None
                and ev.action is not Action.IGNORE):
            verdict = self.fault_interceptor(ev)
            if verdict != "revive":
                # the fleet owns this fault: isolate the device so the
                # step loop skips it, then defer (restart / spare /
                # redistribution happen at the fleet tick)
                self._isolate_only(ev)
                return
        report = self.recovery.recover(ev)
        self.reports.append(report)
        # inference was paused during recovery: reset the heartbeat clock
        # for every surviving executor so the pause is not mistaken for a
        # hang (the monitor resumes with inference)
        for ex in self.dp_executors:
            if ex.alive:
                self.monitor.beat(ex.physical_id, self.step_no)
        for mex in self.moe_executors:
            if mex.device_alive:
                self.monitor.beat(mex.physical_id, self.step_no)

    def _isolate_only(self, ev) -> None:
        """Minimal isolation for a fleet-deferred fault: terminate the
        failed executor and stop expecting its heartbeats, nothing else."""
        try:
            self.domain.device(ev.rank).alive = False
        except KeyError:
            pass
        for ex in self.dp_executors:
            if ex.physical_id == ev.rank:
                ex.fail_device()
                ex.terminate_process()
        for mex in self.moe_executors:
            if mex.physical_id == ev.rank:
                mex.fail_device()
        self.monitor.unregister(ev.rank)

    # -- device rejoin (cleared transient faults) --------------------------------

    def rejoin_device(self, physical_id: int) -> bool:
        """A cleared transient fault (flapping link restored, thermals
        back in range) returns the device to service: rebuild its
        executor, restore its expert shard from the checkpoint when its
        EP rank is uncovered, re-admit it to the comm domain (version
        bump -> cached graph for the new domain), and reset the
        detection state so the rank is faultable again.

        Returns True if a device actually rejoined; False when there is
        nothing to rejoin (rank alive, unknown, or its expert duty has
        been taken over by a role-switched donor)."""
        from repro.serving.weights_util import (
            load_expert_shard_from_checkpoint)
        dp = next((ex for ex in self.dp_executors
                   if ex.physical_id == physical_id), None)
        mex = next((m for m in self.moe_executors
                    if m.physical_id == physical_id), None)
        if dp is not None:
            if dp.alive:
                return False
            shard, ep_rank = None, dp.ep_rank
            if ep_rank is not None and self.expert_map is not None:
                if self._shard_owner(ep_rank) is not None:
                    ep_rank = None      # duty covered elsewhere
                else:
                    shard = load_expert_shard_from_checkpoint(
                        self.ckpt_path, self.shards[ep_rank], ep_rank,
                        self.ep_size, workdir=self.ecfg.workdir)
            fresh = self._make_dp_executor(physical_id, dp.dp_rank,
                                           shard=shard, ep_rank=ep_rank)
            self.dp_executors[self.dp_executors.index(dp)] = fresh
            if shard is not None:
                self.expert_map.install_rank(ep_rank)
        elif mex is not None:
            if mex.device_alive:
                return False
            if self._shard_owner(mex.ep_rank) is not None:
                return False            # a role-switched donor owns it
            shard = load_expert_shard_from_checkpoint(
                self.ckpt_path, self.shards[mex.ep_rank], mex.ep_rank,
                self.ep_size, workdir=self.ecfg.workdir)
            mex.install_shard(shard)
            self.expert_map.install_rank(mex.ep_rank)
        else:
            return False
        if self.expert_map is not None:
            self.runtime = self.expert_map.runtime()
            self.reassemble_params()
        # comm domain: back in with a fresh logical rank at the end of
        # its role group; rebuild compacts any remaining gaps and bumps
        # the version (cached compile on the next step)
        dev = self.domain.device(physical_id)
        if not dev.alive:
            peers = [r.logical_rank for r in self.domain.group(
                "moe" if (mex is not None and not self.domain.collocated)
                else "attn")]
            dev.logical_rank = (max(peers) + 1) if peers else 0
            dev.alive = True
        self.domain.rebuild()
        self.world_group = [ex.physical_id for ex in self.dp_executors
                            if ex.alive] + \
                           [m.physical_id for m in self.moe_executors
                            if m.device_alive]
        self.monitor.register(physical_id, self.step_no)
        self.straggler.forgive(physical_id)
        self._handled_faults.discard(physical_id)
        self.injector.clear(physical_id)
        return True

    # -- weight assembly -----------------------------------------------------------------

    @property
    def shard_alive(self) -> List[bool]:
        return [s is not None for s in self.bank_shards]

    def reassemble_params(self, reload=()) -> None:
        """Bring the device expert bank in line with the live shards, in
        place: a rank whose owner died has its slice zeroed, and a rank
        whose owner now holds another host shard (role switch, rejoin —
        each loads its shard from disk) or is listed in ``reload``
        (rebalanced replica slots) has that shard written into its slice.
        The old buffers are donated, so no second bank is ever allocated
        and nothing else is re-uploaded."""
        if self.cfg.moe is None:
            return
        per = self.expert_map.slots_per_rank
        for r in range(self.ep_size):
            owner = self._shard_owner(r)
            shard = owner.shard if owner is not None else None
            if shard is not self.bank_shards[r] or (
                    shard is not None and r in reload):
                self.params = update_rank_slice(
                    self.params, self._bank_fn, r, per, shard)
                self.bank_shards[r] = shard

    def _shard_owner(self, ep_rank: int):
        """The executor currently hosting this EP rank's shard (or None)."""
        if self.ecfg.mode == "collocated":
            for ex in self.dp_executors:
                if ex.ep_rank == ep_rank and ex.device_alive \
                        and ex.shard is not None:
                    return ex
            return None
        for mex in self.moe_executors:
            if mex.ep_rank == ep_rank and mex.device_alive \
                    and mex.shard is not None:
                return mex
        return None

    def rebalance_experts(self, usage_counts) -> Dict[int, int]:
        """Maintenance op: re-point redundant replica slots at the hottest
        experts (paper §3.4/§4.3 — replicas follow usage frequency) and
        physically copy the weights into the replica slots' shards."""
        if self.expert_map is None:
            return {}
        emap = self.expert_map
        moves = emap.rebalance_replicas(usage_counts)
        copied = set()
        for slot, logical in moves.items():
            # copy weights from an alive source slot of `logical`
            sources = [s for s in emap.replicas_of(logical) if s != slot]
            if not sources:
                continue
            src = sources[0]
            dst_owner = self._shard_owner(emap.rank_of_slot(slot))
            src_owner = self._shard_owner(emap.rank_of_slot(src))
            if dst_owner is None or src_owner is None:
                continue
            per = emap.slots_per_rank
            s_loc, d_loc = src % per, slot % per
            for key, arr in dst_owner.shard.items():
                arr[:, d_loc] = src_owner.shard[key][:, s_loc]
            copied.add(emap.rank_of_slot(slot))
        self.runtime = emap.runtime()
        self.reassemble_params(reload=copied)
        return moves

    # -- baseline: full instance reinitialization (Fig. 1) ------------------------------

    def full_reinit(self) -> Dict[str, float]:
        """The baseline recovery: relaunch engine + executors, reload
        weights, rebuild groups, cached-compile — everything, timed."""
        in_flight = []
        for ex in self.dp_executors:
            # dead executors included: their requests' token ids survive
            # in host memory and must be requeued after the rebuild —
            # the in-flight step's readback landed (commit it), minus
            # any speculative overlay still riding on the requests
            if ex._inflight is not None:
                ex.flush(None)
            if ex.has_uncommitted():
                ex.rollback_inflight()
            in_flight.extend(ex.scheduler.drain())
        self.monitor = HeartbeatMonitor(self.ecfg.heartbeat_timeout_steps)
        # process death: in-memory executables are gone (the on-disk
        # persistent compile cache survives — that's the "cached" part)
        self.graph_cache.invalidate(lambda k: True)
        # ... and so is every device buffer: drop the weights and KV pools
        # before the rebuild loads them again, or the device would hold
        # two copies of the model at once
        self.params = None
        self.dp_executors, self.moe_executors = [], []
        t = self._build(first_time=False)
        # restore shard state for ranks that had died (weights came from
        # disk in _build's generator stage — that's the point of reinit)
        for req in in_flight:
            if req.state not in (RequestState.FINISHED,):
                req.state = RequestState.WAITING
                self._assign(req)
        self._handled_faults.clear()
        self.soft_signals = {}
        return t
