"""Compiles, fresh or served from the persistent cache, that the
program's telemetry charged to the window's engine steps."""
from bench import step_records


def read(run):
    return step_records.compiles(run)
