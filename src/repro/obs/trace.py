"""Span/timeline types and the flight-recorder :class:`Tracer`.

A traced run produces a :class:`RunTrace`: per-request :class:`Span`\\ s
(``queue`` from arrival to dispatch, ``service`` from dispatch to
completion, ``lost`` for service a recomposition threw away) laid out on
lanes — one lane (``pid``) per server chain plus a queue lane and a run
lane — and instant :class:`Marker`\\ s for run-level events (recompose,
scenario events, autoscale actions, sheds).

The engines are **not** instrumented per event.  Spans carry the engines'
own raw timestamps (``arrival``/``st``/``fin`` arrays on the sim plane,
``Request`` fields on the live plane) and are decoded *after* the run by
:mod:`repro.obs.decode`; the only thing recorded while the run executes is
the epoch history — which chain composition was active when — via
:meth:`Tracer.on_epoch`, called from non-hot code (engine construction and
``reconfigure``).  That is what makes tracing structurally zero-cost when
disabled and bit-neutral when enabled: the hot dispatch loops are
byte-for-byte the same code either way.

The served path on a device is the exception: what its host does between
device programs cannot be decoded afterwards.  :class:`SpanRecorder` records
live spans there, on the host's ``perf_counter_ns`` clock, from the call
sites of the orchestrator and the chain engines that hold one; with none
attached each site costs one ``is None`` test.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = ["Span", "Marker", "Epoch", "RunTrace", "Tracer", "SpanRecorder"]

#: lane (pid) reserved for run-level markers
RUN_LANE = 0
#: lane (pid) for time-in-queue spans
QUEUE_LANE = 1
#: first chain lane; chain lanes are FIRST_CHAIN_LANE + lane index
FIRST_CHAIN_LANE = 2


@dataclasses.dataclass(frozen=True)
class Span:
    """One contiguous interval in a request's life.

    ``t0``/``t1`` are raw simulation/wall timestamps (seconds) exactly as
    the engine computed them — consumers that need bit-exact identities
    (``service.t1 - queue.t0 == response_time``) rely on no arithmetic
    having been done on them.  ``pid``/``tid`` are the Chrome-trace
    process/thread lane the span renders on.
    """

    name: str
    cat: str          # "queue" | "service" | "lost"
    t0: float
    t1: float
    pid: int
    tid: int
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: live spans (:class:`SpanRecorder`): this span's id and its parent's
    sid: Optional[int] = None
    parent: Optional[int] = None

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class Marker:
    """Instant run-level event (recompose, shed, scenario, autoscale)."""

    t: float
    name: str
    cat: str = "event"
    pid: int = RUN_LANE
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Epoch:
    """One composition era: the chain set active from ``t0`` onward."""

    t0: float
    rates: Tuple[float, ...]
    caps: Tuple[int, ...]
    keys: Optional[Tuple[Any, ...]] = None


class Tracer:
    """Collects what the run can't reconstruct afterwards.

    An engine constructed with ``tracer=`` binds itself
    (:meth:`bind_engine`) and reports composition epochs and displaced
    service; the plane layer adds run-level markers from its own event
    log.  Everything else — the per-request spans — is decoded post-hoc
    from the engine's arrays by :mod:`repro.obs.decode`.
    """

    def __init__(self) -> None:
        self.epochs: List[Epoch] = []
        self.markers: List[Marker] = []
        #: (jid, t0, t1, chain_idx_in_epoch, epoch_idx) of service a
        #: restart-mode reconfigure discarded
        self.lost: List[Tuple[int, float, float, int, int]] = []
        self.engine: Any = None

    # ------------------------------------------------------------- hooks
    def bind_engine(self, engine: Any) -> None:
        self.engine = engine

    def on_epoch(self, t0: float, rates: Sequence[float],
                 caps: Sequence[int],
                 keys: Optional[Sequence] = None) -> None:
        self.epochs.append(Epoch(float(t0), tuple(float(r) for r in rates),
                                 tuple(int(c) for c in caps),
                                 tuple(keys) if keys is not None else None))

    def on_marker(self, t: float, name: str, cat: str = "event",
                  **args: Any) -> None:
        self.markers.append(Marker(float(t), name, cat, RUN_LANE, args))

    def on_lost_service(self, jid: int, t0: float, t1: float,
                        chain: int) -> None:
        """Service discarded by a restart-mode reconfigure: job ``jid``
        had been running on ``chain`` (an index into the *current last*
        epoch) since ``t0`` when the recompose at ``t1`` evicted it."""
        self.lost.append((int(jid), float(t0), float(t1), int(chain),
                          len(self.epochs) - 1))

    # ------------------------------------------------------------ lookup
    def epoch_at(self, t: float) -> int:
        """Index of the epoch active at time ``t`` (later epoch wins at
        the boundary, matching re-dispatch at the recompose instant)."""
        i = len(self.epochs) - 1
        while i > 0 and self.epochs[i].t0 > t:
            i -= 1
        return i


@dataclasses.dataclass
class RunTrace:
    """A decoded run timeline: spans + markers + lane labels.

    ``lanes`` maps Chrome-trace pid → human label (``chain[2] rate=0.8
    x4``); ``meta`` carries run context (plane, engine, policy, counts).
    """

    spans: List[Span]
    markers: List[Marker]
    lanes: Dict[int, str]
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def n_spans(self) -> int:
        return len(self.spans)

    def spans_by_request(self) -> Dict[int, List[Span]]:
        """Spans grouped by request id, each group time-ordered."""
        out: Dict[int, List[Span]] = {}
        for s in self.spans:
            jid = s.args.get("jid")
            if jid is not None:
                out.setdefault(int(jid), []).append(s)
        for v in out.values():
            v.sort(key=lambda s: (s.t0, s.t1))
        return out

    def tail_attribution(self, k: int = 3) -> List[Dict[str, Any]]:
        """The ``k`` slowest requests, with their time split between the
        queue and service phases and the chain that served them — the
        "where did the p99 go" answer the aggregate stats can't give."""
        per_req: Dict[int, Dict[str, Any]] = {}
        for s in self.spans:
            jid = s.args.get("jid")
            if jid is None or s.cat == "lost":
                continue
            e = per_req.setdefault(int(jid), {
                "jid": int(jid), "arrival": s.t0, "finish": s.t1,
                "queue_s": 0.0, "service_s": 0.0, "chain": None})
            e["arrival"] = min(e["arrival"], s.t0)
            e["finish"] = max(e["finish"], s.t1)
            if s.cat == "queue":
                e["queue_s"] += s.duration
            elif s.cat == "service":
                e["service_s"] += s.duration
                e["chain"] = s.args.get("chain", e["chain"])
        for e in per_req.values():
            e["response"] = e["finish"] - e["arrival"]
        ranked = sorted(per_req.values(),
                        key=lambda e: e["response"], reverse=True)
        return ranked[:max(0, int(k))]

    def self_check(self) -> None:
        """Assert timeline invariants (used by tests and the smoke job):
        every span well-ordered, queue end == service start per request,
        and span lanes present in the lane table."""
        for s in self.spans:
            if not (s.t1 >= s.t0):
                raise AssertionError(f"span ends before it starts: {s}")
            if s.pid not in self.lanes:
                raise AssertionError(f"span on unlabeled lane {s.pid}: {s}")
        for jid, spans in self.spans_by_request().items():
            queue = [s for s in spans if s.cat == "queue"]
            service = [s for s in spans if s.cat == "service"]
            if queue and service:
                if queue[-1].t1 != service[-1].t0:
                    raise AssertionError(
                        f"request {jid}: queue ends at {queue[-1].t1!r} but "
                        f"service starts at {service[-1].t0!r}")


class SpanRecorder:
    """Live spans of the served path, kept in memory.

    :meth:`begin` opens a span inside the innermost open one and returns its
    id; :meth:`end` closes it, and any span still open inside it, and
    records a :class:`Span` (``cat`` names the layer, ``t0``/``t1`` are
    ``perf_counter_ns`` in seconds, ``sid``/``parent`` link it to the span
    it ran in, ``args`` carry ids and counts).  :meth:`phase` ends the
    innermost span and opens the next step beside it.

    Each open span is also a ``jax.profiler.TraceAnnotation`` of the same
    name where jax is installed.  An annotation records nothing unless a
    profiler trace is running; during one, the span lands on the profiler's
    host plane, on the device trace's clock.

    ``req.queue`` spans run from a request's submission (:meth:`queued`) to
    the start of its admission (:meth:`dequeued`); they end after the fact,
    so they are kept in memory only.
    """

    def __init__(self) -> None:
        self.spans: List[Span] = []
        # stack of open spans: [sid, name, cat, t0_ns, parent, args, annotation]
        self._open: List[list] = []
        self._next = 0
        self._queued: Dict[int, int] = {}     # rid -> submission, ns
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:                   # numpy-only install
            TraceAnnotation = None
        self._annotation = TraceAnnotation

    def begin(self, name: str, cat: str, **args: Any) -> int:
        sid = self._next
        self._next += 1
        parent = self._open[-1][0] if self._open else None
        ann = None
        if self._annotation is not None:
            ann = self._annotation(name)
            ann.__enter__()
        self._open.append([sid, name, cat, time.perf_counter_ns(), parent,
                           args, ann])
        return sid

    def end(self, sid: int, **args: Any) -> None:
        """Close span ``sid`` (adding ``args``) and every span opened inside
        it; a span already closed is left as it is."""
        if not any(o[0] == sid for o in self._open):
            return
        t1 = time.perf_counter_ns() * 1e-9
        while True:
            o = self._open.pop()
            if o[6] is not None:
                o[6].__exit__(None, None, None)
            if o[0] == sid:
                o[5].update(args)
            self.spans.append(Span(o[1], o[2], o[3] * 1e-9, t1, RUN_LANE, 0,
                                   o[5], sid=o[0], parent=o[4]))
            if o[0] == sid:
                return

    def phase(self, name: str, cat: str, **args: Any) -> int:
        """End the innermost open span and begin ``name`` in its place."""
        if self._open:
            self.end(self._open[-1][0])
        return self.begin(name, cat, **args)

    @contextlib.contextmanager
    def span(self, name: str, cat: str, **args: Any) -> Iterator[int]:
        sid = self.begin(name, cat, **args)
        try:
            yield sid
        finally:
            self.end(sid)

    def queued(self, rid: int) -> None:
        self._queued[rid] = time.perf_counter_ns()

    def dequeued(self, rid: int) -> None:
        t0 = self._queued.pop(rid, None)
        if t0 is None:
            return
        sid = self._next
        self._next += 1
        self.spans.append(Span("req.queue", "request", t0 * 1e-9,
                               time.perf_counter_ns() * 1e-9, QUEUE_LANE,
                               int(rid), {"rid": rid}, sid=sid))

    def run_trace(self) -> "RunTrace":
        """The spans recorded so far, laid out for :func:`export_chrome_trace`:
        nested spans on the host lane, queue spans one track per request."""
        return RunTrace(spans=sorted(self.spans, key=lambda s: (s.t0, -s.t1)),
                        markers=[], lanes={RUN_LANE: "host", QUEUE_LANE: "queue"},
                        meta={"clock": "perf_counter_ns"})
