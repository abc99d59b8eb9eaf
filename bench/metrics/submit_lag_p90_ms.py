"""90th percentile, over the requests due in the window before any trace
starts (tracing slows the host), of how late the open-loop
driver called ``submit`` (it waits for the round in flight)."""
from harness.records import percentile


def compute(run):
    lags = [(s.submit_at if s.submit_at is not None else run.t_end) - s.due
            for s in run.window_requests(run.host_close)]
    p = percentile(lags, 90)
    return None if p is None else p * 1e3
