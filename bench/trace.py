"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The device's work is the events on each chip's ``XLA Ops`` line; an
event's name there is its HLO instruction, and the op is named by the
text before `` = `` (``%paged_attention.6``, ``%fusion.263``).  Busy
time is the union of their intervals inside the traced window, averaged
over the chips used; the window is the harness's ``bench.traced`` host
span.  Every gap between busy intervals is named by the innermost
``bench.*`` host span open at its middle.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


@dataclass
class Trace:
    """Events in seconds on one clock: device ops per chip, host spans.
    A control-flow op (``while``, ``conditional``) spans the ops of its
    body; it counts towards busy time but is not an op of its own."""
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    containers: set = field(default_factory=set)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = Trace()
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                evs = out.ops.setdefault(plane.name, [])
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    name = op_name(e.name)
                    if any(f" {c}(" in e.name for c in CONTAINERS):
                        out.containers.add(name)
                    evs.append((name, t0, t0 + e.duration_ns * 1e-9))
            elif not plane.name.startswith(DEVICE_PLANE):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        t0 = e.start_ns * 1e-9
                        out.spans.append(
                            (e.name, t0, t0 + e.duration_ns * 1e-9))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.263`` of ``%fusion.263 = s32[128]{0} fusion(...)``."""
    return hlo.split(" = ", 1)[0]


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def window(tr: Trace) -> Interval:
    ws = [(a, b) for n, a, b in tr.spans if n == WINDOW_SPAN]
    if len(ws) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(ws)}")
    return ws[0]


@dataclass
class Summary:
    window_s: float
    busy_s: float                              # mean over chips
    op_seconds: Dict[str, float]               # summed over chips
    gaps: List[Tuple[str, float]]              # longest first


def summarize(tr: Trace, max_gaps: int = 10) -> Summary:
    lo, hi = window(tr)
    if not tr.ops:
        raise ValueError("the trace holds no device operations")
    busy = []
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for evs in tr.ops.values():
        spans = [(a, b) for _, a, b in evs]
        merged = union(clip(spans, lo, hi))
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in evs:
            if name in tr.containers:
                continue
            for c, d in clip([(a, b)], lo, hi):
                op_s[name] = op_s.get(name, 0.0) + (d - c)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((host_at(tr, (a + b) / 2), b - a))
    gaps.sort(key=lambda g: -g[1])
    return Summary(hi - lo, sum(busy) / len(busy), op_s, gaps[:max_gaps])


def host_at(tr: Trace, t: float) -> str:
    """The innermost harness span open at time ``t``."""
    best = None
    for name, a, b in tr.spans:
        if name != WINDOW_SPAN and a <= t <= b and (
                best is None or a >= best[1]):
            best = (name, a)
    return best[0][len(HOST_PREFIX):] if best else "outside any span"


def kernel_seconds(s: Summary, token: str) -> float:
    """Device time of the ops whose own name contains ``token`` (a Pallas
    kernel's op is named after its kernel: ``%paged_attention.6``)."""
    return sum(v for k, v in s.op_seconds.items() if token in k)
