"""From the start of the engine step in which the injected fault fires
to the first output token any request receives after the revive (s)."""
from bench import readers


def read(run):
    return readers.recovery_s(run)
