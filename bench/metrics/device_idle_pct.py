"""Share of the traced window in which no operation ran on the device."""


def compute(run):
    if run.trace is None:
        return None
    return run.trace.idle_share * 100.0
