"""Every output token produced inside the window over the window's seconds."""


def compute(run):
    n = sum(1 for s in run.sent for t in s.token_times if run.in_window(t))
    return n / run.seconds
