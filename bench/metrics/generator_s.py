"""The engine build's generator stage (weights placed, checkpoint and
expert shard files written, shards split): ``init_timings["generator"]``."""


def read(run):
    return run.init_timings.get("generator")
