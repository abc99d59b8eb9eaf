"""The open-loop driver: ``drive_orchestrator``'s loop on the wall clock.

``repro.api.planes.drive_orchestrator`` steps a synthetic ``t = rounds*dt``;
this copy takes ``now`` from ``time.perf_counter``.  Each request is submitted
when it is due, whether or not earlier ones have finished, with its due time as
its ``arrival_time``; with nothing in flight the loop sleeps until the next due
time.  After every ``submit`` and ``step`` call it stamps each request whose
output grew: both calls end in a host sync on the new tokens, so a stamp is
never earlier than the token.  Each call and each sleep is wrapped in a
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.round``,
``bench.wait``), and ``bench.traced`` spans the traced part of the window.
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import List, Optional, Tuple


@dataclasses.dataclass
class Sent:
    req: object                       # repro.serving.Request
    due: float
    submit_at: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    engine: Optional[int] = None      # engine that decoded it last


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    rows: List[Tuple[int, List[int]]]  # (engine, cache positions of each row)


@dataclasses.dataclass
class Drive:
    rounds: List[Round]
    t_end: float                       # when the loop stopped (>= close)
    traced: Optional[Tuple[float, float]]   # host times of the traced part
    built_in_window: int               # programs compiled or loaded
    compiles_in_window: int            # of those, compiled


def _snapshot(orch, sent_by_rid) -> List[Tuple[int, List[int]]]:
    """Rows each engine decodes this round: its running requests, and the
    positions each holds (prompt plus tokens so far, less the pending one)."""
    out = []
    for k, eng in enumerate(orch.engines):
        ctx = []
        for req in eng.requests.values():
            ctx.append(len(req.prompt) + len(req.output) - 1)
            s = sent_by_rid.get(req.rid)
            if s is not None:
                s.engine = k
        if ctx:
            out.append((k, ctx))
    return out


def _stamp(live: List[Sent], t: float) -> None:
    from repro.serving import State

    keep = []
    for s in live:
        n = len(s.req.output)
        if n > len(s.token_times):
            s.token_times.extend([t] * (n - len(s.token_times)))
        if s.req.state not in (State.DONE, State.FAILED):
            keep.append(s)
    live[:] = keep


def drive(orch, sent: List[Sent], t_close: float, meter, t_open: float,
          trace_dir: Optional[Path] = None, trace_from: float = 0.0) -> Drive:
    """Serve ``sent`` (sorted by due time) until ``t_close`` seconds after
    the first due time; with ``trace_dir``, trace from ``trace_from`` (same
    clock) to the close.  Stopping the profiler stalls the host for seconds,
    so the trace ends where the window does."""
    import jax

    ann = jax.profiler.TraceAnnotation
    sent_by_rid = {s.req.rid: s for s in sent}
    live: List[Sent] = []
    rounds: List[Round] = []
    i, n = 0, len(sent)
    tracing, traced, window_ann = False, None, None
    mark = None
    t_base = time.perf_counter()

    def clock() -> float:
        return time.perf_counter() - t_base

    while True:
        now = clock()
        if now >= t_close:
            break
        if mark is None and now >= t_open:
            mark = (meter.built, meter.compiles)
        if trace_dir is not None:
            if not tracing and now >= trace_from:
                jax.profiler.start_trace(str(trace_dir))
                window_ann = ann("bench.traced")
                window_ann.__enter__()
                tracing, traced = True, (clock(), None)
                continue
        if i < n and sent[i].due <= now:
            s = sent[i]
            i += 1
            s.submit_at = now
            with ann("bench.submit"):
                orch.submit(s.req, now)
            live.append(s)
            _stamp(live, clock())
            continue
        if orch.queue or any(e.requests for e in orch.engines):
            rows = _snapshot(orch, sent_by_rid)
            t0 = clock()
            with ann("bench.round"):
                orch.step(t0)
                while orch.queue:           # admit whenever capacity frees
                    if not orch._dispatch(orch.queue.peek(), t0):
                        break
                    orch.queue.pop()
            t1 = clock()
            rounds.append(Round(t0, t1, rows))
            _stamp(live, t1)
            continue
        wake = min(sent[i].due if i < n else t_close, t_close)
        if mark is None:
            wake = min(wake, t_open)
        if trace_dir is not None and not tracing:
            wake = min(wake, trace_from)
        with ann("bench.wait"):
            time.sleep(max(0.0, wake - clock()))
    if tracing:
        traced = (traced[0], clock())
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if mark is None:
        mark = (meter.built, meter.compiles)
    return Drive(rounds=rounds, t_end=clock(), traced=traced,
                 built_in_window=meter.built - mark[0],
                 compiles_in_window=meter.compiles - mark[1])
