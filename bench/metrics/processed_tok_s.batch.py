"""Prompt and output tokens the engine processed in the window (prompt
positions computed, output tokens emitted) over the window's seconds."""
from bench import readers, stats


def read(run):
    return stats.rate(readers.processed_in_window(run), run.seconds)
