"""Mean over the window's engine steps of the requests given a token
over the decode slots (num_dp x max_batch), in %."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.requests_served for s in steps) / (
        len(steps) * run.slots)
