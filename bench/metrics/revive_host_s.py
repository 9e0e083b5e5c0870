"""Host time of the in-place revive of the window's fault,
``RecoveryReport.total_s``."""


def read(run):
    return run.revive_s
