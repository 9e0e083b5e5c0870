"""Reductions the metric readers share: each takes the finished
:class:`bench.harness.Run` and returns a number, or None when the run
holds nothing to read."""
from __future__ import annotations

from typing import Optional

from bench import trace as TR


def ttft_s(run) -> list:
    """First token minus due time of every request due in the window; a
    request with no token by the window's end counts its wait so far."""
    out = []
    for r in run.reqs:
        if run.in_window(r.arrival.at):
            first = r.token_t[0] if r.token_t else run.seconds
            out.append(min(first, run.seconds) - r.arrival.at)
    return out


def itl_s(run) -> list:
    """Every gap between consecutive output tokens of any request, both
    tokens inside the window."""
    out = []
    for r in run.reqs:
        t = r.token_t
        for a, b in zip(t, t[1:]):
            if a >= 0.0 and b < run.seconds:
                out.append(b - a)
    return out


def tokens_in_window(run) -> int:
    return sum(run.in_window(t) for r in run.reqs for t in r.token_t)


def processed_in_window(run) -> int:
    """Prompt positions computed by the steps that ended inside the
    window, plus the output tokens emitted inside it."""
    return sum(s.prefill_tokens for s in run.steps
               if run.in_window(s.t1)) + tokens_in_window(run)


def queue_wait_s(run) -> list:
    """Due time to the first step after which the request left the
    waiting queue, for every request due in the window."""
    out = []
    for r in run.reqs:
        if run.in_window(r.arrival.at):
            left = (r.left_waiting_t if r.left_waiting_t is not None
                    else run.seconds)
            out.append(min(left, run.seconds) - r.arrival.at)
    return out


def recovery_s(run) -> Optional[float]:
    """From the start of the step in which the fault fired to the end of
    the first later step that gave any request a token."""
    fs = run.fault_step
    if fs is None:
        return None
    after = [s for s in run.steps if s.t0 >= fs.t1 and s.tokens > 0]
    if not after:
        return run.seconds - fs.t0
    return after[0].t1 - fs.t0


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of the window's steps over their summed wall time, as a
    share of the chip's bf16 peak (%)."""
    steps = run.window_steps()
    busy = sum(s.t1 - s.t0 for s in steps)
    if not steps or busy <= 0 or "bf16_flops" not in run.peaks:
        return None
    return 100.0 * sum(s.flops for s in steps) / busy \
        / run.peaks["bf16_flops"]


def kernel_roofline(run, token: str) -> Optional[float]:
    """Least time the traced steps' paged-attention calls need at the
    chip's peaks (each call bound by its FLOPs or its bytes), over the
    device time of the kernel's events in the trace (%)."""
    if run.trace is None or "bf16_flops" not in run.peaks:
        return None
    spent = TR.kernel_seconds(run.trace, token)
    if spent <= 0:
        return None
    pf, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    need = 0.0
    for s in run.steps:
        if s.traced:
            for ctxs in s.attn_rows.values():
                f, b = run.arch.attention_call(run.shape, ctxs)
                need += max(f / pf, b / bw)
    return 100.0 * need * run.shape.layers / spent


def device_idle(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
