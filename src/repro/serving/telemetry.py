"""Step telemetry: the program's one tracing API.

``Telemetry.span(name, **ids)`` is a context manager with two effects.
While a profiler records it enters ``jax.profiler.TraceAnnotation(name,
**ids)``, so the span lands on the host plane on the same clock as the
device's ``XLA Ops``; and it always records ``(name, parent, t0, t1)`` on
``time.perf_counter_ns`` into the record of the step it is open in.  A
span's self time is its duration less that of its child spans.

``Telemetry.step(step_no)`` opens the ``repro.step`` span: each call of
``InferenceEngine.step()`` gives exactly one :class:`StepRecord` (wall
time, self seconds per span name, compiles), kept in a bounded ring.
The spans of the last finished step are kept as well, for ``summary()``.
Every span, in a step or not (engine build, revive), also feeds an
aggregate per name: count, total and self seconds, compiles.

Compiles come from one process-wide ``jax.monitoring`` listener: every
backend compile, fresh or served from the persistent compilation cache,
is charged to the innermost span open on the compiling thread and to its
step.  That names the step that recompiled, eager rollback programs
included.

Each engine registers its telemetry once in a bounded process registry
(:func:`register`, :func:`registered`), so an exporter or a benchmark
can read it after the engine is freed.

With no profiler active a span costs a few microseconds.
"""
from __future__ import annotations

import collections
import contextlib
import threading
from time import perf_counter_ns
from typing import Deque, Dict, List, Optional, Tuple

import jax

SpanEntry = Tuple[str, Optional[str], int, int]

# span names, from the step down
STEP = "repro.step"
CONTROL = "repro.step.control"
HEALTH = "repro.step.health"
PLAN = "repro.plan"
UNDO_CAPTURE = "repro.plan.undo_capture"
LAUNCH = "repro.launch"
LAUNCH_CHUNK = "repro.launch.chunk"
LAUNCH_PREFILL = "repro.launch.prefill"
LAUNCH_DECODE = "repro.launch.decode"
READBACK = "repro.readback"
SAMPLE = "repro.sample"
COMMIT = "repro.commit"
REVIVE = "repro.revive"
BUILD = "repro.build"

# JAX times every executable it obtains under this event, whether the
# backend compiled it or the persistent cache served it
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

RING = 4096             # step records kept per telemetry
REGISTRY_SIZE = 64      # telemetries kept per process


class StepRecord:
    """One ``InferenceEngine.step()``: its wall time, the self seconds of
    each span name in it, and the compiles charged to it."""
    __slots__ = ("index", "step", "wall_s", "self_s", "compiles",
                 "compile_s", "_self_ns", "_spans")

    def __init__(self, index: int, step: int):
        self.index = index              # ordinal among this telemetry's steps
        self.step = step                # the engine's step number
        self.wall_s = 0.0
        self.self_s: Dict[str, float] = {}
        self.compiles = 0
        self.compile_s = 0.0
        self._self_ns: Dict[str, int] = {}
        # (name, parent, t0_ns, t1_ns) while the step is open
        self._spans: List[SpanEntry] = []


class _Span:
    __slots__ = ("tel", "name", "ids", "ann", "t0", "dur_ns", "child_ns",
                 "parent")

    def __init__(self, tel: "Telemetry", name: str, ids: dict):
        self.tel = tel
        self.name = name
        self.ids = ids
        self.child_ns = 0
        self.dur_ns = 0

    def __enter__(self):
        # the annotation is made only while a profiler records: with
        # none active it would cost as much as the rest of the span
        if _Annotation.is_enabled():
            self.ann = _Annotation(self.name, **self.ids)
            self.ann.__enter__()
        else:
            self.ann = None
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter_ns()
        _local.stack.pop()
        self.dur_ns = dur = t1 - self.t0
        parent = self.parent
        if parent is not None:
            parent.child_ns += dur
        tel, name = self.tel, self.name
        own = dur - self.child_ns
        a = tel._agg.get(name)
        if a is None:
            a = tel._agg_of(name)
        a[0] += 1
        a[1] += dur
        a[2] += own
        rec = tel._rec
        if rec is not None:
            rec._self_ns[name] = rec._self_ns.get(name, 0) + own
            rec._spans.append((name, None if parent is None else parent.name,
                               self.t0, t1))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        return self.dur_ns * 1e-9


class _StepSpan(_Span):
    __slots__ = ("step_no", "outer")

    def __init__(self, tel: "Telemetry", step_no: int):
        super().__init__(tel, STEP, {"step": step_no})
        self.step_no = step_no

    def __enter__(self):
        self.outer = self.tel._begin_step(self.step_no)
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.tel._end_step(self.dur_ns, self.outer)
        return False


class Telemetry:
    def __init__(self):
        _listen_for_compiles()
        self.records: Deque[StepRecord] = collections.deque(maxlen=RING)
        self.n_steps = 0                # step records ever made
        # the spans of the last finished step, (name, parent, t0_ns, t1_ns)
        self.last_spans: List[SpanEntry] = []
        # name -> [count, total_ns, self_ns, compiles, compile_s]
        self._agg: Dict[str, list] = {}
        self._rec: Optional[StepRecord] = None

    # -- recording --------------------------------------------------------------

    def span(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids)

    def step(self, step_no: int) -> _StepSpan:
        return _StepSpan(self, step_no)

    @contextlib.contextmanager
    def stage(self, prefix: str, sink: Dict[str, float], key: str):
        """Span ``<prefix>.<key>`` whose seconds are also added to
        ``sink[key]`` (a stage timing the engine reports)."""
        sp = self.span(f"{prefix}.{key}")
        try:
            with sp:
                yield sp
        finally:
            sink[key] = sink.get(key, 0.0) + sp.seconds

    @property
    def step_compiles(self) -> int:
        """Compiles charged so far to the step that is open (0 outside a
        step)."""
        return 0 if self._rec is None else self._rec.compiles

    def _begin_step(self, step_no: int) -> Optional[StepRecord]:
        outer = self._rec
        self._rec = StepRecord(self.n_steps, step_no)
        self.n_steps += 1
        return outer

    def _end_step(self, dur_ns: int, outer: Optional[StepRecord]) -> None:
        rec = self._rec
        rec.wall_s = dur_ns * 1e-9
        rec.self_s = {k: v * 1e-9 for k, v in rec._self_ns.items()}
        rec._self_ns = {}
        self.last_spans, rec._spans = rec._spans, []
        self.records.append(rec)
        self._rec = outer

    def _agg_of(self, name: str) -> list:
        a = self._agg.get(name)
        if a is None:
            a = self._agg[name] = [0, 0, 0, 0, 0.0]
        return a

    def _compile(self, name: str, secs: float) -> None:
        a = self._agg_of(name)
        a[3] += 1
        a[4] += secs
        rec = self._rec
        if rec is not None:
            rec.compiles += 1
            rec.compile_s += secs

    # -- reading ------------------------------------------------------------------

    def summary(self, since: int = 0) -> dict:
        """Per-phase self ms a step, host share and compiles over the kept
        step records whose ordinal is at least ``since`` (pass a former
        ``n_steps`` to leave out a warm-up), and the aggregate of every
        span name so far.  ``host_share`` is the part of the steps' wall
        time in which the host was not waiting on a readback.
        ``last_step`` lays out the last finished step: its spans in start
        order as ``[name, parent, start_ms, ms]`` from the step's start."""
        recs = [r for r in self.records if r.index >= since]
        n = len(recs)
        wall = sum(r.wall_s for r in recs)
        phase: Dict[str, float] = {}
        for r in recs:
            for k, v in r.self_s.items():
                phase[k] = phase.get(k, 0.0) + v
        waited = phase.get(READBACK, 0.0)
        return {
            "steps": n,
            "step_ms": 1e3 * wall / n if n else 0.0,
            "phase_ms": {k: 1e3 * v / n for k, v in sorted(phase.items())},
            "host_share": (wall - waited) / wall if wall > 0 else 0.0,
            "compiles": sum(r.compiles for r in recs),
            "compile_s": sum(r.compile_s for r in recs),
            "spans": {k: {"count": a[0], "total_s": a[1] * 1e-9,
                          "self_s": a[2] * 1e-9, "compiles": a[3],
                          "compile_s": a[4]}
                      for k, a in sorted(self._agg.items())},
            "last_step": self._last_step(),
        }

    def _last_step(self) -> Optional[dict]:
        if not self.records:
            return None
        rec = self.records[-1]
        t0 = min(t for _, _, t, _ in self.last_spans)
        return {"step": rec.step, "wall_ms": 1e3 * rec.wall_s,
                "compiles": rec.compiles,
                "spans": [[n, p, 1e-6 * (a - t0), 1e-6 * (b - a)]
                          for n, p, a, b in sorted(self.last_spans,
                                                   key=lambda s: s[2])]}


# -- process-wide state: the open spans of each thread, the compile listener,
# -- the registry ----------------------------------------------------------------

_Annotation = jax.profiler.TraceAnnotation
_local = threading.local()
_listener_lock = threading.Lock()
_listening = False
_registry: Deque[Telemetry] = collections.deque(maxlen=REGISTRY_SIZE)


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    stack = getattr(_local, "stack", None)
    if stack:
        sp = stack[-1]
        sp.tel._compile(sp.name, secs)


def _listen_for_compiles() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


def register(tel: Telemetry) -> None:
    """Keep ``tel`` readable for the life of the process (the newest
    ``REGISTRY_SIZE`` registrations)."""
    _registry.append(tel)


def registered() -> List[Telemetry]:
    return list(_registry)
