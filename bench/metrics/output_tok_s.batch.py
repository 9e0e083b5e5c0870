"""Output tokens emitted in the window over the window's seconds."""
from bench import readers, stats


def read(run):
    return stats.rate(readers.tokens_in_window(run), run.seconds)
