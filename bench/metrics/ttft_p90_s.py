"""90th percentile of time to first token (s) over every request due
in the window, timed from its due time."""
from bench import readers, stats


def read(run):
    return stats.percentile(readers.ttft_s(run), 90)
