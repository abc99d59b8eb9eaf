"""95th percentile over every gap between consecutive tokens of one request,
both tokens inside the window."""
from harness.records import percentile


def compute(run):
    gaps = []
    for s in run.sent:
        t = [x for x in s.token_times if run.in_window(x)]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
