"""90th percentile, over every request due in the window, of the time from
its due time to its first token; a request with none counts at the time the
loop stopped."""
from harness.records import percentile


def compute(run):
    p = percentile((run.ttft_s(s) for s in run.window_requests()), 90)
    return None if p is None else p * 1e3
