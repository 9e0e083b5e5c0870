"""Model FLOPs of the tokens the window's steps processed, over the
summed wall time of those steps, as a share of the bf16 peak (%)."""
from bench import readers


def read(run):
    return readers.step_mfu(run)
