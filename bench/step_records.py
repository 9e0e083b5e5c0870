"""The program's own step records (``repro.serving.telemetry``), paired
with the harness's steps.

The engine registers its telemetry in a process registry when it is
built, so a reader finds it after the run has freed the engine.  Each
``InferenceEngine.step()`` gives one record and the run loop keeps one
``StepRec`` per call, so the i-th record is the i-th step of
``run.steps``.  Nothing is read (None) where the program keeps no such
records, the process registered other than exactly one telemetry, the
counts differ, or the records kept no longer reach back to the window's
first step."""
from __future__ import annotations

from typing import List, Optional


def _telemetry():
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    return telemetry


def window_records(run) -> Optional[List[object]]:
    """The step records of ``run.window_steps()``, in order."""
    T = _telemetry()
    if T is None:
        return None
    tels = T.registered()
    if len(tels) != 1:
        return None
    tel = tels[0]
    if tel.n_steps != len(run.steps):
        return None
    recs = list(tel.records)
    first = tel.n_steps - len(recs)       # ordinal of the oldest kept
    mine = {id(s) for s in run.window_steps()}
    idx = [i for i, s in enumerate(run.steps) if id(s) in mine]
    if not idx or idx[0] < first:
        return None
    return [recs[i - first] for i in idx]


def host_share(run) -> Optional[float]:
    """100 x (summed ``repro.step`` - summed ``repro.readback``) over
    summed ``repro.step``, over the window's steps (%)."""
    recs = window_records(run)
    if not recs:
        return None
    wall = sum(r.wall_s for r in recs)
    if wall <= 0:
        return None
    waited = sum(r.self_s.get(_telemetry().READBACK, 0.0) for r in recs)
    return 100.0 * (wall - waited) / wall


def compiles(run) -> Optional[int]:
    """Compiles, fresh or from the persistent cache, charged to the
    window's steps."""
    recs = window_records(run)
    if not recs:
        return None
    return sum(r.compiles for r in recs)
