"""Share of the window's engine steps' wall time in which the host was
not waiting on a logits readback (%): 100 x (summed ``repro.step`` -
summed ``repro.readback``) / summed ``repro.step``, from the program's
own step records."""
from bench import step_records


def read(run):
    return step_records.host_share(run)
