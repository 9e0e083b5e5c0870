"""Paged-attention kernel: least time at the chip's peaks for the
FLOPs and bytes its traced calls need (valid KV of each row), over the
device time of its events in the trace (%)."""
from bench import readers

KERNEL = "paged_attention"


def read(run):
    return readers.kernel_roofline(run, KERNEL)
