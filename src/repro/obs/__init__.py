"""Flight recorder: span tracing, streaming metrics, exportable timelines.

Three pieces, usable separately:

* :mod:`repro.obs.trace` — :class:`Tracer` (epoch + marker recording
  during a run) and :class:`RunTrace` (the decoded timeline of
  per-request spans).  Pass ``trace=True`` to :func:`repro.api.run` to
  get one on ``report.trace``; tracing never changes results (traced
  runs are bit-identical to untraced ones) and costs nothing when off —
  the engine hot loops carry no instrumentation either way.
  :class:`SpanRecorder` is the live counterpart for the served path:
  attached to an ``Orchestrator`` (``orch.recorder = SpanRecorder()``),
  it records ``orch.*``, ``engine.*`` and ``req.queue`` spans as they
  happen, also onto a running ``jax.profiler`` trace.
* :mod:`repro.obs.metrics` — :class:`Counter` / :class:`Gauge` /
  :class:`LogHistogram` in a :class:`MetricsRegistry`; streaming p50/p99
  in O(buckets) memory, snapshot + diff for run-to-run comparison.
* :mod:`repro.obs.export` — :func:`export_chrome_trace` writes a
  ``RunTrace`` as Trace Event Format JSON that opens in
  https://ui.perfetto.dev with one lane per server chain.

Numpy-only by design: the CI ``obs-smoke`` job imports this package
without jax installed.
"""
from .metrics import (Counter, Gauge, LogHistogram, MetricsRegistry,
                      MetricsSnapshot)
from .trace import Epoch, Marker, RunTrace, Span, SpanRecorder, Tracer
from .decode import (decode_orchestrator_trace, decode_sim_trace,
                     merge_region_traces)
from .export import export_chrome_trace, to_chrome_trace

__all__ = [
    "Counter", "Gauge", "LogHistogram", "MetricsRegistry", "MetricsSnapshot",
    "Epoch", "Marker", "RunTrace", "Span", "SpanRecorder", "Tracer",
    "decode_orchestrator_trace", "decode_sim_trace",
    "export_chrome_trace", "merge_region_traces", "to_chrome_trace",
]
