"""90th percentile of due time to the first step after which the
request left the waiting queue (s), over every request due in the window."""
from bench import readers, stats


def read(run):
    return stats.percentile(readers.queue_wait_s(run), 90)
