"""95th percentile of the gap between consecutive output tokens (ms),
over all gaps of all requests inside the window."""
from bench import readers, stats


def read(run):
    v = stats.percentile(readers.itl_s(run), 95)
    return None if v is None else 1e3 * v
