"""Executors: DPExecutor (attention rank) and MoEExecutor (expert rank).

A DPExecutor owns a local scheduler and the paged serving cache: block
pools (one trailing trash block for idle batch slots) addressed through
the ``BlockManager``/``BlockTable`` accounting, with the §3.3 undo log
covering both the host-side block ops and the device-side pool writes
(row-level write-set capture by default; the legacy O(1) functional
snapshot as fallback).  Prefill runs as batched multi-request *chunks*
— prompt tokens become virtual decode slots against the pools, ragged
across requests purely as paging data — on attention-only models;
recurrent-state models keep whole-prompt installs.  Decode attends
through per-step paging arrays (``kvcache.build_page_context``) that
ride into the compiled step as data, so continuous batching and
recovery never retrigger compilation.

Steps are two-phase to model collective lockstep: ``plan`` (host work —
admission, block allocation, prefix-cache sharing, all logged) then
``compute`` (the device step).  A fault between the phases leaves an
uncommitted log, which recovery rolls back (§3.3) — block tables from
the op log, pools by scattering the captured write-set rows back.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.block_log import BlockLog, BlockManager, BlockTable
from repro.core.migration import KVBlocks
from repro.serving.cache_ops import (capture_pool_rows,
                                     copy_block_prefixes,
                                     gather_request_blocks,
                                     infer_paged_axes, restore_pool_rows,
                                     restore_pool_rows_subset,
                                     scatter_request_blocks)
from repro.serving.kvcache import (build_chunk_context, build_page_context,
                                   max_blocks_per_seq, padded_block_ids,
                                   walked_pages)
from repro.serving.request import Request, RequestState
from repro.serving.sampling import (SamplingParams, device_predict, sample,
                                    seeded_uniforms, spec_verify)
from repro.serving.scheduler import ChunkPiece, LocalScheduler, StepPlan
from repro.serving.telemetry import (COMMIT, LAUNCH, LAUNCH_CHUNK,
                                     LAUNCH_DECODE, LAUNCH_PREFILL, PLAN,
                                     READBACK, SAMPLE, UNDO_CAPTURE,
                                     Telemetry)


def next_bucket(n: int, max_seq: int, min_bucket: int = 16) -> int:
    b = min_bucket
    while b < n:
        b *= 2
    return min(b, max_seq)


@dataclass
class _Point:
    """One sampling event of an in-flight step (overlap pipeline)."""
    req: Request
    kind: str                   # 'chunk_last' | 'spec' | 'decode'
    section: str                # which launch holds its logits
    row: int                    # logits row (chunk row0 / decode slot)
    win: Optional[ChunkPiece] = None
    guesses: List[int] = field(default_factory=list)
    positions: List[int] = field(default_factory=list)
    predicted_done: bool = False
    sidx: int = -1              # row in the device-predict arrays


@dataclass
class _Det:
    """Deterministic chunk bookkeeping applied at launch (plan-ahead):
    correct whatever the step's sampled outcome, undone only when the
    whole plan is rolled back (reconcile / fault abort)."""
    req: Request
    piece: ChunkPiece
    prev_prefill_pos: int
    prev_next_register: Optional[int]
    counted_was: bool


@dataclass
class _Actual:
    """Authoritative outcome of one sampling event, host-derived from
    the drained logits (pure — nothing mutated until the pipeline
    decides between confirm and reconcile)."""
    tokens: List[int]
    accepted: int
    finished: bool


class _Pending:
    """One launched-but-uncommitted step riding the readback ring:
    device references to its logits and predicted tokens (D2H copies
    enqueued at launch, forced one step late), plus the speculative
    host bookkeeping needed to confirm or unwind it."""
    __slots__ = ("plan", "step_no", "chunk_logits", "decode_logits",
                 "pred_chunk", "pred_decode", "points", "det",
                 "prefill_finished")

    def __init__(self, plan: StepPlan, step_no: int):
        self.plan = plan
        self.step_no = step_no
        self.chunk_logits = None
        self.decode_logits = None
        self.pred_chunk = None      # (targets, accepted) device arrays
        self.pred_decode = None
        self.points: List[_Point] = []
        self.det: List[_Det] = []
        self.prefill_finished: List[Request] = []


def _host_async(*arrays) -> None:
    """Enqueue device→host copies without blocking (the readback ring)."""
    for a in arrays:
        if a is not None and hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()


class MoEExecutor:
    """Stateless expert host: one EP rank's slice of the physical slots."""

    def __init__(self, physical_id: int, ep_rank: int,
                 shard: Dict[str, np.ndarray]):
        self.physical_id = physical_id
        self.ep_rank = ep_rank
        self.shard: Optional[Dict[str, np.ndarray]] = shard
        self.device_alive = True
        self.process_alive = True

    def fail_device(self) -> None:
        """Hardware gone: the only copies of these weights are lost."""
        self.device_alive = False
        self.shard = None

    def install_shard(self, shard: Dict[str, np.ndarray]) -> None:
        self.shard = shard
        self.device_alive = True
        self.process_alive = True


class DPExecutor:
    def __init__(self, physical_id: int, dp_rank: int, model, *,
                 max_batch: int, max_seq: int, num_blocks: int,
                 block_size: int, sampling: SamplingParams,
                 ep_rank: Optional[int] = None,
                 shard: Optional[Dict[str, np.ndarray]] = None,
                 paged_axes: Optional[list] = None,
                 admission: str = "chunked",
                 prefill_chunk: int = 32,
                 token_budget: Optional[int] = None,
                 prefix_cache: bool = True,
                 pool_undo: str = "rows",
                 spec_window: int = 0,
                 telemetry: Optional[Telemetry] = None):
        self.physical_id = physical_id
        self.dp_rank = dp_rank
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.sampling = sampling
        self.device_alive = True
        self.process_alive = True
        # collocated mode: this device also hosts an expert shard
        self.ep_rank = ep_rank
        self.shard = shard

        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blk = max_blocks_per_seq(max_seq, block_size)
        self.trash_block = num_blocks      # the extra pool row (see model)
        self.block_manager = BlockManager(num_blocks, block_size)
        self.block_log = BlockLog()
        self.admission = admission
        self.pool_undo = pool_undo
        # chunked prefill needs a batch-width-free cache (attention-only
        # pools); recurrent-state models fall back to whole-prompt installs
        chunk = (prefill_chunk if admission == "chunked"
                 and model.supports_chunked_prefill else 0)
        self.chunk_tokens = chunk
        self.scheduler = LocalScheduler(
            max_batch, max_seq, self.block_manager,
            token_budget=(token_budget if admission == "chunked" else None),
            chunk_tokens=chunk,
            prefix_cache=prefix_cache and chunk > 0,
            window=model.cfg.sliding_window or None,
            max_prefills=1 if admission == "serial" else None,
            spec_window=spec_window)
        self.cache = model.init_paged_cache(max_batch, num_blocks,
                                            block_size)
        if paged_axes is None:   # the engine passes its shared copy in
            _, paged_axes = infer_paged_axes(model, num_blocks, block_size)
        self.paged_axes = paged_axes
        self.last_token = np.zeros((max_batch,), np.int32)
        self.steps_done = 0
        self._plan: Optional[StepPlan] = None
        # injected extra per-step latency (straggler simulation)
        self.simulated_slowdown_s = 0.0
        # overlap pipeline state: the launched-but-undrained step, plus a
        # device-resident next-token vector so step N+1's inputs chain
        # from step N without a host round trip
        self._inflight: Optional[_Pending] = None
        self._dev_last = None
        self._dev_stale = True
        self.overlap_stats = {"steps": 0, "planned_ahead": 0,
                              "replans": 0, "drains": 0}
        # the engine's telemetry; spans carry this rank as their id
        self.telemetry = telemetry or Telemetry()

    # -- lifecycle --------------------------------------------------------------

    @property
    def alive(self) -> bool:
        return self.device_alive and self.process_alive

    def fail_device(self) -> None:
        self.device_alive = False
        if self.shard is not None:
            self.shard = None  # collocated: expert weights die too

    def terminate_process(self) -> None:
        """Engine-side isolation of the failed/hanging process."""
        self.process_alive = False
        self._plan = None

    def drop_attention_state(self, collect_kv: bool = False):
        """Role switch (§3.4): shed KV caches, scheduler, attention duty.

        Returns the requests that must migrate elsewhere; with
        ``collect_kv`` their live blocks are extracted *first* (the donor
        device is healthy — §3.4's role switch, unlike a failure, can
        stream its residents' KV instead of forcing re-prefill) and the
        result is ``[(req, KVBlocks | None)]``."""
        payloads = {}
        if collect_kv:
            for req in list(self.scheduler.running):
                kv = self.export_kv_blocks(req)
                if kv is not None:
                    payloads[req.req_id] = kv
        reqs = self.scheduler.drain()
        self.cache = None
        self.block_log = BlockLog()
        if collect_kv:
            return [(r, payloads.get(r.req_id)) for r in reqs]
        return reqs

    def prefix_hit_blocks(self, digests, prompt_len: int) -> int:
        """How many *leading* full prompt blocks this executor's
        BlockManager can serve from its shared-prefix cache — the
        engine's in-instance affinity signal (``_assign``).  Mirrors the
        admission matcher: the prompt's final token is never cacheable
        (its logits must be computed), so the last block is skipped."""
        bs = self.block_size
        hits = 0
        for b, d in enumerate(digests):
            if (b + 1) * bs >= prompt_len:
                break
            if self.block_manager.lookup(d) is None:
                break
            hits += 1
        return hits

    # -- two-phase step -----------------------------------------------------------

    def _span(self, name: str):
        return self.telemetry.span(name, rank=self.dp_rank)

    def _readback(self, logits) -> np.ndarray:
        """The host waits on the device for a launch's logits."""
        with self._span(READBACK):
            return np.asarray(logits)

    def plan(self) -> StepPlan:
        with self._span(PLAN):
            self.block_log.begin_step()
            plan = self.scheduler.plan_step(self.block_log)
            if self.cache is not None:
                with self._span(UNDO_CAPTURE):
                    self._capture_undo(plan)
            self._plan = plan
        return plan

    def _capture_undo(self, plan: StepPlan) -> None:
        # §3.3 device half: either the O(1) functional snapshot of the
        # whole cache (legacy; pins the pre-step pool buffers), or —
        # default — capture exactly the rows this step will write, known
        # at plan time, so rollback is O(write set) and the pool buffers
        # stay donation-friendly on TPU
        if self.pool_undo == "snapshot":
            self.block_log.snapshot_pools(self.cache)
        else:
            bids, offs = self._write_manifest(plan)
            self.block_log.record_pool_undo(capture_pool_rows(
                self.cache, self.paged_axes, bids, offs))
        # prefix-cache COW: seed private divergence blocks from the
        # shared sources *after* the capture (the copies are part of the
        # step's write set and roll back with it); one batched row
        # scatter covers every COW admission of the step
        self.cache = copy_block_prefixes(self.cache, self.paged_axes,
                                         plan.cow_copies)

    def _write_manifest(self, plan: StepPlan
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Every (block, offset) pool row the planned step writes: decode
        destinations for all batch slots (idle slots hit the trash row),
        each chunk token's slot, whole-prefill installs (their padded
        block scatter covers every offset), and COW destination rows."""
        bs = self.block_size
        tables = self.scheduler.block_tables
        bids: List[int] = []
        offs: List[int] = []
        if plan.decode:
            row_bid = [self.trash_block] * self.max_batch
            row_off = [0] * self.max_batch
            for req in plan.decode:
                wp = req.num_tokens - 1
                blocks = tables[req.req_id].blocks
                row_bid[req.batch_slot] = blocks[wp // bs]
                row_off[req.batch_slot] = wp % bs
            bids += row_bid
            offs += row_off
        if plan.chunks or plan.spec:
            # speculation windows ride the same launch right after the
            # prefill pieces; their manifest rows are what the verify
            # phase partially restores for rejected drafts
            n = 0
            for piece in plan.chunks + plan.spec:
                blocks = tables[piece.req.req_id].blocks
                for j in range(piece.length):
                    pos = piece.start + j
                    bids.append(blocks[pos // bs])
                    offs.append(pos % bs)
                n += piece.length
            for _ in range(self.chunk_tokens - n):   # idle chunk rows
                bids.append(self.trash_block)
                offs.append(0)
        out_b = [np.asarray(bids, np.int32)]
        out_o = [np.asarray(offs, np.int32)]
        for req in plan.prefills:
            # the install scatter writes every offset of every padded
            # block id (bucket-sized, trash repeats included)
            bucket = next_bucket(len(req.tokens_so_far), self.max_seq)
            nblk = max_blocks_per_seq(bucket, bs)
            pb = padded_block_ids(tables[req.req_id].blocks, nblk,
                                  self.trash_block)
            out_b.append(np.repeat(pb, bs))
            out_o.append(np.tile(np.arange(bs, dtype=np.int32), nblk))
        for _, dst, n in plan.cow_copies:
            out_b.append(np.full((n,), dst, np.int32))
            out_o.append(np.arange(n, dtype=np.int32))
        return (np.concatenate(out_b).astype(np.int32),
                np.concatenate(out_o).astype(np.int32))

    def compute(self, ctx, step_no: int) -> List[Request]:
        """Run the planned step on device; returns finished requests.

        Lockstep path: dispatch then commit back to back.  The overlap
        pipeline calls the same two halves a step apart."""
        return self.finish_compute(self.begin_compute(ctx, step_no))

    def begin_compute(self, ctx, step_no: int,
                      predict: bool = False) -> _Pending:
        """Dispatch the planned step's device work without forcing any
        result.  With ``predict`` (overlap pipeline) the launch's token
        inputs come from the device-resident chain instead of the host
        vector, and a jitted epilogue samples the step's tokens
        on-device so the next step can launch before this one drains."""
        with self._span(LAUNCH):
            return self._dispatch(ctx, step_no, predict)

    def _dispatch(self, ctx, step_no: int, predict: bool) -> _Pending:
        plan, self._plan = self._plan, None
        assert plan is not None, "compute without plan"
        pend = _Pending(plan, step_no)
        params, runtime = ctx.params, ctx.runtime

        if plan.chunks or plan.spec:
            with self._span(LAUNCH_CHUNK):
                tokens, page = build_chunk_context(
                    plan.chunks + plan.spec, self.scheduler.block_tables,
                    width=self.chunk_tokens, max_blk=self.max_blk,
                    block_size=self.block_size,
                    trash_block=self.trash_block)
                self._count_pages(page)
                if predict:
                    tokens = self._chain_chunk_tokens(tokens, plan)
                logits, self.cache = ctx.chunk_fn()(
                    params, self.cache, tokens, page, runtime)
                pend.chunk_logits = logits

        assert not (predict and plan.prefills), \
            "overlap requires chunked admission (no whole-prompt installs)"
        for req in plan.prefills:
            toks = req.tokens_so_far
            with self._span(LAUNCH_PREFILL):
                bucket = next_bucket(len(toks), self.max_seq)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :len(toks)] = toks
                lengths = np.asarray([len(toks)], np.int32)
                prefill_fn = ctx.prefill_fn(bucket)
                last_logits, raw = prefill_fn(params, padded, lengths,
                                              runtime)
                nblk = max_blocks_per_seq(bucket, self.block_size)
                bids = padded_block_ids(
                    self.scheduler.block_tables[req.req_id].blocks, nblk,
                    self.trash_block)
                install_fn = ctx.install_fn(bucket)
                self.cache = install_fn(self.cache, raw, bids,
                                        np.int32(req.batch_slot))
            last_logits = self._readback(last_logits)
            with self._span(SAMPLE):
                req.prefill_pos = len(toks)
                self.scheduler.note_prefill_done(len(toks))
                tok = int(sample(last_logits, self.sampling,
                                 step=req.num_tokens)[0])
                req.output_tokens.append(tok)
                req.note_token()
                req.state = RequestState.RUNNING
                self.last_token[req.batch_slot] = tok
                if req.done:
                    self.scheduler.finish(req, self.block_log)
                    req.finish_time = time.monotonic()
                    pend.prefill_finished.append(req)

        if plan.decode:
            with self._span(LAUNCH_DECODE):
                page = build_page_context(
                    plan.decode, self.scheduler.block_tables,
                    max_batch=self.max_batch, max_blk=self.max_blk,
                    block_size=self.block_size,
                    trash_block=self.trash_block)
                self._count_pages(page)
                tokens = (self._dev_chain() if predict
                          else np.asarray(self.last_token))
                logits, new_cache = ctx.decode_fn(
                    params, self.cache, tokens, page, runtime)
                self.cache = new_cache
                pend.decode_logits = logits

        if predict:
            self._launch_predict(pend)
        return pend

    def _count_pages(self, page: Dict[str, np.ndarray]) -> None:
        """Charge a launch's paged-attention walk to the telemetry: the
        pages its rows' valid windows span, and those its tables hold."""
        sl = page["seq_lens"]
        self.telemetry.count_pages(
            walked_pages(sl, self.block_size, self.model.cfg.sliding_window),
            sl.size * self.max_blk)

    def finish_compute(self, pend: _Pending,
                       chunk_book: bool = True) -> List[Request]:
        """Force the step's logits and commit its outcome on the host
        (the authoritative sampler).  ``chunk_book=False`` skips the
        chunk-piece bookkeeping the overlap launch already applied."""
        finished: List[Request] = []
        if pend.chunk_logits is not None:
            logits = self._readback(pend.chunk_logits)
            with self._span(SAMPLE):
                finished.extend(self._commit_chunks(pend.plan, logits,
                                                    chunk_book))
        finished.extend(pend.prefill_finished)
        if pend.decode_logits is not None:
            logits = self._readback(pend.decode_logits)
            with self._span(SAMPLE):
                finished.extend(self._commit_decode(pend.plan, logits))
        self._dev_stale = True
        self.steps_done += 1
        return finished

    def _commit_chunks(self, plan: StepPlan, logits: np.ndarray,
                       chunk_book: bool) -> List[Request]:
        finished: List[Request] = []
        row = 0
        for piece in plan.chunks:
            req = piece.req
            if chunk_book:
                req.prefill_pos = piece.start + piece.length
                self.scheduler.note_chunk_done(piece, self.block_log)
            if piece.last:
                # seed by sequence position, not engine step: the token
                # is a pure function of (seed, prefix, position) and
                # survives replay on any executor of any fleet instance
                tok = int(sample(logits[row + piece.length - 1][None],
                                 self.sampling, step=req.num_tokens)[0])
                req.output_tokens.append(tok)
                req.note_token()
                req.state = RequestState.RUNNING
                self.last_token[req.batch_slot] = tok
                if req.done or req.num_tokens >= self.max_seq:
                    self.scheduler.finish(req, self.block_log)
                    req.finish_time = time.monotonic()
                    finished.append(req)
            row += piece.length
        if plan.spec:
            finished.extend(self._verify_spec(plan, logits, row))
        return finished

    def _commit_decode(self, plan: StepPlan,
                       logits: np.ndarray) -> List[Request]:
        finished: List[Request] = []
        # one batched sample over the whole decode batch (the
        # per-request loop serialized B host round trips per step)
        slots = np.fromiter((r.batch_slot for r in plan.decode),
                            np.intp, count=len(plan.decode))
        positions = np.fromiter((r.num_tokens for r in plan.decode),
                                np.int64, count=len(plan.decode))
        toks = sample(logits[slots], self.sampling, step=positions)
        for req, tok in zip(plan.decode, toks):
            tok = int(tok)
            req.output_tokens.append(tok)
            req.note_token()
            self.last_token[req.batch_slot] = tok
            # decode-grown blocks publish in the prefix cache as they
            # fill (carry-over (f)) — register before a possible finish
            # so the blocks park cache-addressable
            self.scheduler.note_decode_progress(req, self.block_log)
            if req.done or req.num_tokens >= self.max_seq:
                self.scheduler.finish(req, self.block_log)
                req.finish_time = time.monotonic()
                finished.append(req)
        return finished

    def _verify_spec(self, plan: StepPlan, logits: np.ndarray,
                     row0: int) -> List[Request]:
        """Commit each speculation window against the verifier logits.

        Window rows sit after the prefill-chunk rows in both the launch
        (logits rows) and the plan-time write manifest, in the same
        order — so a window's manifest indices are its logits rows
        shifted by the decode section.  Every emitted token is the
        seeded sampler's output at its own sequence position
        (``spec_verify``), keeping the stream token-identical to plain
        decode; pool rows written by rejected drafts are restored
        bit-exact from the §3.3 write-set capture (under the legacy
        snapshot strategy they are left stale, which is safe: a stale
        row's position is only ever attended after its true token's
        decode step rewrites it)."""
        finished: List[Request] = []
        undo = self.block_log.peek_pool_undo()
        base_manifest = self.max_batch if plan.decode else 0
        row = row0
        for win in plan.spec:
            req = win.req
            g = win.length
            base = req.num_tokens          # next position to commit
            drafts = win.tokens[base:]     # the g - 1 proposals
            toks, accepted = spec_verify(
                logits[row:row + g], drafts, self.sampling,
                start_step=base)
            emitted = 0
            for tok in toks:
                req.output_tokens.append(int(tok))
                req.note_token()
                self.last_token[req.batch_slot] = int(tok)
                emitted += 1
                if req.done or req.num_tokens >= self.max_seq:
                    break
            # window row r wrote the KV row of position base - 1 + r;
            # rows [emitted, g) hold drafts that were rejected (or never
            # reached) — scatter their pre-step rows back
            if emitted < g and undo is not None:
                idx = np.arange(base_manifest + row + emitted,
                                base_manifest + row + g, dtype=np.int32)
                self.cache = restore_pool_rows_subset(
                    self.cache, self.paged_axes, undo, idx)
            self.scheduler.note_spec_done(win, emitted, accepted)
            self.scheduler.note_decode_progress(req, self.block_log)
            if req.done or req.num_tokens >= self.max_seq:
                self.scheduler.finish(req, self.block_log)
                req.finish_time = time.monotonic()
                finished.append(req)
            row += g
        return finished

    def commit(self) -> None:
        """Step boundary reached: the undo log is no longer needed."""
        with self._span(COMMIT):
            self.block_log.begin_step()  # clears; committed counter advances

    def rollback_inflight(self) -> int:
        """§3.3: undo every uncommitted step — host block tables from
        the op log, device pools by restoring each frame's captured
        write-set rows (or the legacy step-boundary snapshot), newest
        frame first, so table and pool agree exactly on which rows are
        live.  Under the overlap pipeline this is *total*: the in-flight
        step's speculative token guesses and launch-time chunk
        bookkeeping unwind first, then both stacked frames — recovery
        then sees exactly the last committed state, and replay
        regenerates the lost step's tokens bit-identically (they are
        pure functions of seed/prefix/position)."""
        if self._inflight is not None:
            pend, self._inflight = self._inflight, None
            self._unwind_overlay(pend)
            self._unwind_det(pend)
            self.scheduler.unwind_plan_stats(pend.plan)
        n = 0
        for _ in range(self.block_log.num_frames):
            undo = self.block_log.take_pool_undo()
            snap = self.block_log.take_pool_snapshot()
            if self.cache is not None:
                if undo is not None:
                    self.cache = restore_pool_rows(
                        self.cache, self.paged_axes, undo)
                elif snap is not None:
                    self.cache = snap
            n += self.block_log.undo_newest(self.block_manager,
                                            self.scheduler.block_tables)
        # admissions from the aborted step(s) return to the waiting queue
        self.scheduler.rollback_aborted()
        self._plan = None
        self._dev_stale = True
        return n

    def has_uncommitted(self) -> bool:
        """Anything between this executor and its last step boundary —
        logged block ops, an armed pool capture, a stacked plan-ahead
        frame, or an undrained launch.  (The overlap pipeline can hold
        speculative state with *zero* block ops — a pure-decode frame —
        so ``len(block_log) > 0`` alone is not a safe export guard.)"""
        return (self._inflight is not None
                or len(self.block_log) > 0
                or self.block_log.num_frames > 1
                or self.block_log.has_pool_state())

    # -- overlap pipeline (host/device overlap, async readback) -------------------
    #
    # Lifecycle per engine step k (one call to ``overlap_step``):
    #   1. plan step k against the *predicted* post-(k-1) state (the
    #      k-1 launch applied its guessed tokens as a speculative
    #      overlay, so the scheduler simply plans at the right
    #      positions), in a fresh undo frame stacked on k-1's;
    #   2. launch step k: token inputs chain from the device-resident
    #      next-token vector (never the host guesses), a jitted
    #      epilogue samples k's tokens on-device, and only token-id
    #      sized D2H copies join the readback ring;
    #   3. drain step k-1: force its logits (one step late), re-derive
    #      the authoritative outcome with the host sampler, and either
    #      confirm (replace guessed values, commit the oldest frame) or
    #      reconcile (roll back k's frame + overlay, commit k-1's true
    #      outcome via the lockstep commit code, replan k).
    # A plan stays valid whenever the *shape* of the outcome matched —
    # per-event token counts, finishes, and the device-chain inputs the
    # next step consumed — so guessed token values never force replans
    # on their own.

    def overlap_step(self, ctx, step_no: int) -> List[Request]:
        prev = self._inflight
        nxt = None
        if self.scheduler.num_requests:
            nxt = self._plan_and_launch(ctx, step_no,
                                        stacked=prev is not None)
            if nxt is not None and prev is not None:
                self.overlap_stats["planned_ahead"] += 1
        finished: List[Request] = []
        if prev is not None:
            finished, diverged = self._drain(prev, nxt)
            if diverged:
                self.overlap_stats["replans"] += 1
                nxt = (self._plan_and_launch(ctx, step_no, stacked=False)
                       if self.scheduler.num_requests else None)
        self._inflight = nxt
        self.overlap_stats["steps"] += 1
        return finished

    def flush(self, ctx) -> List[Request]:
        """Drain the in-flight step without launching another (pipeline
        tail / engine quiesce)."""
        prev, self._inflight = self._inflight, None
        if prev is None:
            return []
        finished, _ = self._drain(prev, None)
        return finished

    def _plan_and_launch(self, ctx, step_no: int, *,
                         stacked: bool) -> Optional[_Pending]:
        """Plan-ahead half: plan in a (possibly stacked) undo frame,
        capture the write set, and dispatch.  Returns None when the
        scheduler has nothing plannable (pool/budget pressure)."""
        with self._span(PLAN):
            if stacked:
                self.block_log.push_frame()
            plan = self.scheduler.plan_step(self.block_log)
            if plan.empty:
                if stacked:
                    self.block_log.undo_newest(self.block_manager,
                                               self.scheduler.block_tables)
                return None
            # the capture gathers post-(k-1) row values: it dispatches
            # after k-1's compute in device program order, which is
            # exactly what a rollback of step k alone must restore
            with self._span(UNDO_CAPTURE):
                self._capture_undo(plan)
            self._plan = plan
        return self.begin_compute(ctx, step_no, predict=True)

    def _dev_chain(self):
        """Device-resident last-token vector (refreshed from the host
        copy whenever the pipeline broke the chain)."""
        if self._dev_last is None or self._dev_stale:
            import jax.numpy as jnp
            self._dev_last = jnp.asarray(self.last_token)
            self._dev_stale = False
        return self._dev_last

    def _chain_chunk_tokens(self, tokens: np.ndarray, plan: StepPlan):
        """Chunk-launch inputs with every speculative-window row 0 (the
        re-forwarded last committed token — a host-side *guess* under
        plan-ahead) overridden from the device chain."""
        import jax.numpy as jnp
        dev = jnp.asarray(tokens)
        if not plan.spec:
            return dev
        row = sum(p.length for p in plan.chunks)
        idx, slots = [], []
        for win in plan.spec:
            idx.append(row)
            slots.append(win.req.batch_slot)
            row += win.length
        chain = self._dev_chain()
        return dev.at[jnp.asarray(idx, jnp.int32)].set(
            chain[jnp.asarray(slots, jnp.int32)])

    def _launch_predict(self, pend: _Pending) -> None:
        """Device-side sampling epilogue: enumerate the step's sampling
        events, guess their outcomes for the overlay, sample their
        tokens on-device (position-seeded uniforms computed host-side),
        scatter the emitted last tokens into the device chain, and
        enqueue the token-id D2H copies."""
        plan = pend.plan
        sched = self.scheduler
        points: List[_Point] = []
        row = 0
        for piece in plan.chunks:
            if piece.last:
                points.append(_Point(piece.req, "chunk_last", "chunk",
                                     row + piece.length - 1))
            row += piece.length
        for win in plan.spec:
            points.append(_Point(win.req, "spec", "chunk", row, win=win))
            row += win.length
        for req in plan.decode:
            points.append(_Point(req, "decode", "decode", req.batch_slot))

        # guesses + sample positions (pre-overlay state = the state the
        # in-flight inputs were built from)
        for pt in points:
            req = pt.req
            base = req.num_tokens
            if pt.kind == "spec":
                drafts = [int(t) for t in pt.win.tokens[base:]]
                bonus = sched.predict_next_token(req,
                                                 context=pt.win.tokens)
                pt.guesses = drafts + [bonus]
            else:
                pt.guesses = [sched.predict_next_token(req)]
            pt.positions = list(range(base, base + len(pt.guesses)))
        pend.points = points

        G = max(sched.spec_window, 1)
        S = self.max_batch

        def run_section(section: str, logits):
            sec = [pt for pt in points if pt.section == section]
            if not sec or logits is None:
                return None
            row0 = np.zeros(S, np.int32)
            lens = np.zeros(S, np.int32)
            drafts = np.zeros((S, G), np.int32)
            u = np.zeros((S, G), np.float32)
            slots = np.full(S, S, np.int32)   # out of range -> dropped
            for i, pt in enumerate(sec):
                pt.sidx = i
                row0[i] = pt.row
                lens[i] = len(pt.positions)
                slots[i] = pt.req.batch_slot
                if pt.kind == "spec":
                    dr = pt.guesses[:-1]      # the forwarded drafts
                    drafts[i, 1:1 + len(dr)] = dr
                if self.sampling.temperature > 0.0:
                    u[i, :len(pt.positions)] = seeded_uniforms(
                        self.sampling.seed,
                        np.asarray(pt.positions, np.int64))
            targets, accepted, new_last = device_predict(
                logits, row0, lens, drafts, u, self._dev_chain(), slots,
                temperature=self.sampling.temperature,
                top_p=self.sampling.top_p)
            self._dev_last = new_last
            self._dev_stale = False
            _host_async(targets, accepted)
            return targets, accepted

        pend.pred_chunk = run_section("chunk", pend.chunk_logits)
        pend.pred_decode = run_section("decode", pend.decode_logits)
        _host_async(pend.chunk_logits, pend.decode_logits)

        # deterministic chunk bookkeeping applies at launch (correct for
        # any sampled outcome; undone only with the whole frame)
        for piece in plan.chunks:
            req = piece.req
            info = sched._seq.get(req.req_id)
            pend.det.append(_Det(
                req, piece, req.prefill_pos,
                None if info is None else info.next_register,
                True if info is None else info.counted))
            req.prefill_pos = piece.start + piece.length
            sched.note_chunk_done(piece, self.block_log)

        # the speculative overlay: guessed tokens advance each request's
        # host-visible position so the next plan sees post-step state
        for pt in points:
            pt.req.apply_speculative(pt.guesses)
            pt.predicted_done = (pt.req.done
                                 or pt.req.num_tokens >= self.max_seq)

    def _unwind_overlay(self, pend: _Pending) -> None:
        for pt in reversed(pend.points):
            pt.req.unwind_speculative(len(pt.guesses))

    def _unwind_det(self, pend: _Pending) -> None:
        sched = self.scheduler
        for d in reversed(pend.det):
            d.req.prefill_pos = d.prev_prefill_pos
            info = sched._seq.get(d.req.req_id)
            if info is None:
                continue
            if d.prev_next_register is not None:
                info.next_register = d.prev_next_register
            if info.counted and not d.counted_was:
                info.counted = False
                sched.stats["prefill_tokens_cached"] -= info.cached_tokens

    def _unwind_pending(self, pend: _Pending) -> None:
        """Roll back a launched plan-ahead step completely: speculative
        overlay, launch-time bookkeeping, pool rows (restoring the
        post-(k-1) values its capture gathered), block ops, and any
        admissions of its frame."""
        self._unwind_overlay(pend)
        self._unwind_det(pend)
        self.scheduler.unwind_plan_stats(pend.plan)
        undo = self.block_log.take_pool_undo()
        snap = self.block_log.take_pool_snapshot()
        if self.cache is not None:
            if undo is not None:
                self.cache = restore_pool_rows(self.cache,
                                               self.paged_axes, undo)
            elif snap is not None:
                self.cache = snap
        self.block_log.undo_newest(self.block_manager,
                                   self.scheduler.block_tables)
        self.scheduler.rollback_aborted()

    def _actual_outcome(self, pend: _Pending, ch: Optional[np.ndarray],
                        de: Optional[np.ndarray]) -> List[_Actual]:
        """The authoritative outcome of each sampling event, re-derived
        from the drained logits with the host sampler — pure (no state
        mutated), replicating the lockstep commit's emit/finish logic
        against the *committed* (pre-overlay) positions."""
        out: List[_Actual] = []
        for pt in pend.points:
            req = pt.req
            committed = req.num_tokens - req.speculative_tokens
            committed_out = len(req.output_tokens) - req.speculative_tokens
            logits = ch if pt.section == "chunk" else de
            if pt.kind == "spec":
                g = pt.win.length
                toks, accepted = spec_verify(
                    logits[pt.row:pt.row + g], pt.win.tokens[committed:],
                    self.sampling, start_step=committed)
            else:
                toks = sample(logits[pt.row][None], self.sampling,
                              step=committed)
                accepted = 0
            tokens: List[int] = []
            fin = False
            n_out = committed_out
            for t in toks:
                t = int(t)
                tokens.append(t)
                n_out += 1
                done = (n_out >= req.max_new_tokens
                        or (req.eos_token is not None
                            and t == req.eos_token))
                fin = done or committed + len(tokens) >= self.max_seq
                if fin:
                    break
            out.append(_Actual(tokens, accepted, fin))
        return out

    def _diverged(self, pend: _Pending, actual: List[_Actual]) -> bool:
        """Did the in-flight step's real outcome invalidate the stacked
        plan-ahead step?  Token *values* never do on their own — only
        the outcome's shape: emitted counts (spec accepts), finishes,
        and the device-chain tokens the next launch actually consumed
        as inputs (greedy prediction is exact; temperature>0 can
        diverge in the last ULP, costing a replan, never a token)."""
        pred = {}
        for arrs, sec in ((pend.pred_chunk, "chunk"),
                          (pend.pred_decode, "decode")):
            if arrs is not None:
                pred[sec] = (np.asarray(arrs[0]), np.asarray(arrs[1]))
        for pt, act in zip(pend.points, actual):
            if len(act.tokens) != len(pt.guesses):
                return True
            if act.finished != pt.predicted_done:
                return True
            if not act.finished:
                targets, accepted = pred[pt.section]
                dev_tok = int(targets[pt.sidx, int(accepted[pt.sidx])])
                if dev_tok != act.tokens[-1]:
                    return True
        return False

    def _confirm(self, pend: _Pending,
                 actual: List[_Actual]) -> List[Request]:
        """Matched outcome: swap the authoritative token values in for
        the guesses and run the commit-side bookkeeping, targeting the
        *oldest* frame (this step's own — a stacked plan-ahead frame
        may sit on top)."""
        finished: List[Request] = []
        oldest = self.block_log.oldest()
        for pt, act in zip(pend.points, actual):
            req = pt.req
            req.confirm_speculative(act.tokens)
            req.note_token()
            self.last_token[req.batch_slot] = act.tokens[-1]
            if pt.kind == "spec":
                self.scheduler.note_spec_done(pt.win, len(act.tokens),
                                              act.accepted)
            if pt.kind in ("spec", "decode"):
                self.scheduler.note_decode_progress(req, oldest)
            if act.finished:
                self.scheduler.finish(req, oldest)
                req.finish_time = time.monotonic()
                finished.append(req)
        return finished

    def _drain(self, prev: _Pending,
               nxt: Optional[_Pending]) -> Tuple[List[Request], bool]:
        """Retire the in-flight step one launch late.  Returns
        ``(finished, diverged)``; on divergence the stacked plan-ahead
        step ``nxt`` has been fully unwound (newest-first, so pool rows
        restore in exact reverse temporal order) and the true outcome
        committed via the lockstep commit path."""
        self.overlap_stats["drains"] += 1
        ch = de = None
        if prev.chunk_logits is not None:
            ch = prev.chunk_logits = self._readback(prev.chunk_logits)
        if prev.decode_logits is not None:
            de = prev.decode_logits = self._readback(prev.decode_logits)
        with self._span(SAMPLE):
            actual = self._actual_outcome(prev, ch, de)
            diverged = self._diverged(prev, actual)
            if diverged:
                # reconcile: unwind the mispredicted plan-ahead step
                # first (its pool capture holds post-prev values, so it
                # must restore before prev's own spec-reject restores),
                # then pop prev's guesses
                if nxt is not None:
                    self._unwind_pending(nxt)
                self._unwind_overlay(prev)
            else:
                finished = self._confirm(prev, actual)
        if diverged:
            # replay prev's true outcome through the lockstep commit code
            finished = self.finish_compute(prev, chunk_book=False)
        else:
            self.steps_done += 1
        with self._span(COMMIT):
            self.block_log.commit_oldest()
        return finished, diverged

    # -- KV-block migration (§3.2, streaming path) --------------------------------

    def export_kv_blocks(self, req: Request) -> Optional[KVBlocks]:
        """Extract a RUNNING request's live blocks + recurrent state.

        None when this device's state is unreachable or the request has
        no installed KV yet (still WAITING, mid-chunked-prefill, or
        mid-migration) — callers fall back to token-replay re-prefill.
        Prefix-shared blocks are read in place (sharing is refcounted;
        a gather never mutates), and window-released table entries ship
        trash rows the target's attention window masks identically."""
        if self.cache is None or not self.alive:
            return None
        if req.state is not RequestState.RUNNING or req.batch_slot is None:
            return None
        if self.scheduler.prefilling(req):
            return None
        table = self.scheduler.block_tables.get(req.req_id)
        if table is None or not req.output_tokens:
            return None
        valid_len = req.num_tokens - 1   # last sampled token's KV is not
        if valid_len <= 0:               # written until its decode step
            return None
        nblk = (valid_len + self.block_size - 1) // self.block_size
        bids = table.blocks[:nblk]
        # window-released entries are trash sentinels: ship no rows for
        # them (their positions are below the attention window forever)
        live_mask = [b < self.num_blocks for b in bids]
        live_bids = [b for b in bids if b < self.num_blocks]
        pools, state = gather_request_blocks(self.cache, self.paged_axes,
                                             live_bids, req.batch_slot)
        return KVBlocks(
            block_size=self.block_size, num_blocks=nblk,
            valid_len=valid_len,
            pool_blocks=[None if p is None else np.asarray(p)
                         for p in pools],
            state=[None if s is None else np.asarray(s) for s in state],
            last_token=int(req.output_tokens[-1]),
            live_mask=live_mask)

    def import_kv_blocks(self, req: Request, kv: KVBlocks) -> bool:
        """Install streamed blocks: allocate fresh physical blocks here,
        scatter the payload, and adopt the request as RUNNING — it skips
        re-prefill entirely and decodes on the next step.  False when
        this executor lacks a batch slot or enough free blocks."""
        if self.cache is None or not self.alive:
            return False
        if kv.block_size != self.block_size:
            return False
        if not self.scheduler._free_slots:
            return False
        span = max(kv.num_blocks, self.scheduler._blocks_needed(
            min(req.num_tokens + 1, self.max_seq)))
        live = (kv.live_mask if kv.live_mask is not None
                else [True] * kv.num_blocks)
        # dead (window-released) table entries install as trash
        # sentinels here too — only live payload blocks and the growth
        # region past the payload need real allocations
        need = sum(live) + (span - kv.num_blocks)
        if self.block_manager.num_allocatable < need:
            return False
        # host accounting mirrors admission; import runs at a step
        # boundary, so the ops commit immediately (log=None)
        table = BlockTable(req.req_id)
        for j in range(span):
            if j < kv.num_blocks and not live[j]:
                table.append_block(self.trash_block)
            else:
                table.append_block(self.block_manager.allocate())
        self.scheduler.block_tables[req.req_id] = table
        req.batch_slot = self.scheduler._free_slots.pop()
        req.dp_rank = self.dp_rank
        req.state = RequestState.RUNNING
        self.scheduler.running.append(req)
        self.scheduler.register_imported(req)
        live_ids = [table.blocks[j] for j in range(kv.num_blocks)
                    if live[j]]
        self.cache = scatter_request_blocks(
            self.cache, self.paged_axes, kv.pool_blocks, kv.state,
            np.asarray(live_ids, np.int32), req.batch_slot)
        self.last_token[req.batch_slot] = kv.last_token
        self._dev_stale = True   # device token chain must re-sync
        return True
