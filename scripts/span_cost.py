"""Cost of one step-telemetry span with no profiler active.

Times ``N`` empty spans, best of ``--repeats``, three ways: inside an
open step (the span also lands in the step's record), outside any step
(aggregate only), and a bare ``jax.profiler.TraceAnnotation`` for
scale.  Prints one JSON line of microseconds a span.

Run: ``PYTHONPATH=src python scripts/span_cost.py [--n 200000]``.
"""
from __future__ import annotations

import argparse
import json
from time import perf_counter_ns

import jax

from repro.serving.telemetry import Telemetry


def _best_us(body, n: int, repeats: int) -> float:
    best = None
    for _ in range(repeats):
        t0 = perf_counter_ns()
        body(n)
        dt = perf_counter_ns() - t0
        best = dt if best is None else min(best, dt)
    return best / n / 1e3


def measure(n: int, repeats: int) -> dict:
    tel = Telemetry()

    def in_step(k):
        with tel.step(0):
            for _ in range(k):
                with tel.span("repro.launch", rank=0):
                    pass

    def outside(k):
        for _ in range(k):
            with tel.span("repro.launch", rank=0):
                pass

    def bare(k):
        for _ in range(k):
            with jax.profiler.TraceAnnotation("repro.launch", rank=0):
                pass

    return {"span_in_step_us": _best_us(in_step, n, repeats),
            "span_outside_step_us": _best_us(outside, n, repeats),
            "trace_annotation_us": _best_us(bare, n, repeats),
            "n": n, "repeats": repeats,
            "platform": jax.devices()[0].platform}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    print(json.dumps(measure(args.n, args.repeats)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
