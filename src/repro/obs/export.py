"""Chrome-trace / Perfetto JSON export for :class:`~repro.obs.RunTrace`.

The output is the Trace Event Format (the ``{"traceEvents": [...]}``
envelope): ``X`` complete events for spans, ``i`` instant events for
markers, ``M`` metadata events naming one process lane per server chain.
Load the file at https://ui.perfetto.dev (or chrome://tracing) and each
chain renders as its own lane with one track per slot; recompose /
scenario / autoscale / shed markers appear on the ``run`` lane.

Timestamps: simulation seconds × 1e6 → microseconds, the unit both
viewers assume.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from .trace import Marker, RunTrace, Span

__all__ = ["export_chrome_trace", "to_chrome_trace"]

_US = 1_000_000.0


def _span_event(s: Span) -> Dict[str, Any]:
    args = dict(s.args)
    if s.sid is not None:
        args.update(id=s.sid, parent=s.parent)
    return {
        "name": s.name,
        "cat": s.cat,
        "ph": "X",
        "ts": s.t0 * _US,
        "dur": (s.t1 - s.t0) * _US,
        "pid": s.pid,
        "tid": s.tid,
        "args": args,
    }


def _marker_event(m: Marker) -> Dict[str, Any]:
    return {
        "name": m.name,
        "cat": m.cat,
        "ph": "i",
        "ts": m.t * _US,
        "pid": m.pid,
        "tid": 0,
        "s": "g",                      # global-scope instant
        "args": dict(m.args),
    }


def to_chrome_trace(trace: RunTrace) -> Dict[str, Any]:
    """Trace Event Format dict for ``trace`` (JSON-safe, ready to dump)."""
    events: List[Dict[str, Any]] = []
    for pid, label in sorted(trace.lanes.items()):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        # sort_index keeps lanes in our order (run, queue, chains) instead
        # of the viewer's default pid-activity ordering
        events.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"sort_index": pid}})
    events.extend(_span_event(s) for s in trace.spans)
    events.extend(_marker_event(m) for m in trace.markers)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": dict(trace.meta),
    }


def export_chrome_trace(trace: RunTrace,
                        path: Optional[str] = None) -> Dict[str, Any]:
    """Serialize ``trace`` to Chrome-trace JSON; write it to ``path`` when
    given.  Returns the trace dict either way."""
    doc = to_chrome_trace(trace)
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
