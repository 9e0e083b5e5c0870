"""The control of the correctness check, read on the chip.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 50

For each seed, in one process: a run of the cell (as ``bench/run.py``
makes it, traced off), then on the same sampled requests the reference
with its matrix products rounded to float8 e4m3 (one precision below the
bfloat16 the configuration states), put through the same comparison with
the same limits.  Prints one JSON line per seed with the program's
numbers and verdict and the control's; a limit must lie above the
program's reading on every sound seed and below the control's, and the
control has to come out not correct.  The benchmark's own runs never run
this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(args.workload, seed, args.seconds, False,
                       control=True, t_process=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["checks"],
                          "control_correct": out["control"]["correct"],
                          "control": out["control"]["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
