"""Process start to the opening of the window (weights, checkpoint
writes, compilation, warm-up), on the host clock."""


def read(run):
    return run.setup_s
