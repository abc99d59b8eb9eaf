"""90th percentile, over the requests due in the window before any trace
starts (tracing slows the host), of admission time (the wall
``now`` the harness passed to the call that admitted it) less due time; one
not admitted counts at the time the loop stopped."""
from harness.records import percentile


def compute(run):
    waits = []
    for s in run.window_requests(run.host_close):
        start = s.req.start_time if s.req.start_time is not None else run.t_end
        waits.append(start - s.due)
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
