"""The program's own spans on the device trace's clock, and what they say
about the device's idle time and the decode step's operations.

With a ``repro.obs.SpanRecorder`` attached to the orchestrator, every span of
the served path (``orch.*``, ``engine.*``) is also a
``jax.profiler.TraceAnnotation``, so a profiler trace holds it on the host
plane beside the harness's ``bench.*`` spans.  ``load`` reads those spans and
each device operation with its program and its scope path (the
``jax.named_scope`` names ``kv_gather``, ``model_decode`` and ``kv_scatter``
inside ``jit__step_impl``).  The rest works on plain interval lists, so a test
can check it by hand:

* ``label_gaps``: each idle gap of the device named by the innermost host
  span that overlaps it most: a program span where one reaches it, else the
  ``bench.*`` span that ``tracefile.reduce`` names;
* ``split_idle``: idle time cut at every span edge, each piece counted to the
  innermost span that covers it;
* the readings of the span metrics, each from the spans where its work
  happens.
"""
from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import tracefile, xspace
from .tracefile import Interval, WINDOW_SPAN, union

#: name prefixes of the program's spans (``repro.obs.SpanRecorder``)
PROGRAM = ("orch.", "engine.")
#: the decode step program and the scope of the model's work inside it
STEP_PROGRAM = "jit__step_impl"
MODEL_SCOPE = "model_decode"


@dataclasses.dataclass
class Op:
    start: int
    end: int
    module: str          # program name, as ``tracefile.program_name`` gives it
    scope: str           # the op's scope path ("" where the trace has none)


@dataclasses.dataclass
class ProgramTrace:
    window: Tuple[int, int]          # ``bench.traced``, ns on the trace clock
    spans: List[Interval]            # the program's spans on the host plane
    bench: List[Interval]            # the harness's ``bench.*`` spans
    ops: List[Op]                    # device operations (first device)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(path: Path) -> ProgramTrace:
    """The program's spans, the ``bench.*`` spans and the first device's
    operations of a profiler trace, each with its scope path (from the
    event metadata, ``xspace.op_scopes``).  On the CPU, XLA's operations on
    host threads stand in for the device, as in ``tracefile.load``, and
    carry no scope."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    scopes = xspace.op_scopes(path)
    spans: List[Interval] = []
    bench: List[Interval] = []
    cpu_ops: List[Op] = []
    dev_ops: Optional[List[Op]] = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and dev_ops is None:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           tracefile.program_name(e.name))
                          for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            scope = scopes.get(plane.name, {})
            dev_ops = _ops_in_modules(
                [(e.start_ns, e.start_ns + e.duration_ns,
                  scope.get(e.name, ""))
                 for e in lines["XLA Ops"].events], mods)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith(PROGRAM):
                        spans.append(iv)
                    elif e.name.startswith("bench."):
                        bench.append(iv)
                    elif e.duration_ns > 0:
                        st = tracefile._stats(e)
                        if "hlo_op" in st:
                            cpu_ops.append(Op(iv[0], iv[1],
                                              str(st.get("hlo_module", "?")),
                                              ""))
    windows = [(s, e) for s, e, n in bench if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    return ProgramTrace(window=windows[0], spans=sorted(spans),
                        bench=sorted(b for b in bench if b[2] != WINDOW_SPAN),
                        ops=dev_ops if dev_ops is not None else cpu_ops)


def _ops_in_modules(ops: Sequence[Tuple[int, int, str]],
                    mods: Sequence[Interval]) -> List[Op]:
    """Each op with the program execution that holds its start (both lists
    come from one device, where executions do not overlap)."""
    out, p = [], 0
    for s, e, scope in sorted(ops):
        while p < len(mods) and mods[p][1] <= s:
            p += 1
        name = mods[p][2] if p < len(mods) and mods[p][0] <= s else "?"
        out.append(Op(s, e, name, scope))
    return out


# -- idle time ------------------------------------------------------------------

def idle_intervals(ops: Sequence[Op], lo: int, hi: int
                   ) -> List[Tuple[int, int]]:
    """The parts of ``[lo, hi]`` in which no operation ran."""
    merged = union([(o.start, o.end) for o in ops], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def nested(spans: Sequence[Interval]) -> List[Interval]:
    """Spans of one thread in the order ``depths`` needs: by start, and a
    span before the spans it holds where they start together."""
    return sorted(spans, key=lambda iv: (iv[0], -iv[1]))


def depths(spans: Sequence[Interval]) -> List[int]:
    """Nesting depth of each span of one thread (in ``nested`` order): 0 for
    a span no other holds."""
    out: List[int] = []
    stack: List[int] = []                 # ends of the spans holding this one
    for s, e, _ in spans:
        while stack and stack[-1] <= s:
            stack.pop()
        out.append(len(stack))
        stack.append(e)
    return out


class _Tree:
    """Spans of one thread with their depths, and the lookup of those that
    overlap an interval."""

    def __init__(self, spans: Sequence[Interval]):
        self.spans = nested(spans)
        self.depth = depths(self.spans)
        self.starts = [a for a, _, _ in self.spans]
        self.longest = max((b - a for a, b, _ in self.spans), default=0)

    def over(self, s: int, e: int) -> List[Tuple[int, int, str, int]]:
        """(start, end, name, depth) of the spans that overlap ``(s, e)``."""
        lo = bisect.bisect_left(self.starts, s - self.longest)
        hi = bisect.bisect_left(self.starts, e)
        return [(*self.spans[i], self.depth[i]) for i in range(lo, hi)
                if self.spans[i][1] > s]


def label_gaps(gaps: Sequence[Tuple[int, int]], spans: Sequence[Interval]
               ) -> List[str]:
    """For each gap, the innermost host span that overlaps it most: of the
    outermost spans that overlap it, the one that overlaps it most (the
    earlier on a tie), then of that span's children the same way, down to a
    span none of whose children overlaps the gap; ``none`` where no span
    does.  The program's spans run inside the ``bench.*`` spans, so a gap
    the program's spans do not reach keeps its ``bench.*`` label."""
    tree = _Tree(spans)
    out = []
    for s, e in gaps:
        over = tree.over(s, e)
        label, cur, d = "none", None, 0
        while True:
            level = [x for x in over if x[3] == d and (
                cur is None or (cur[0] <= x[0] and x[1] <= cur[1]))]
            if not level:
                break
            cur = max(level, key=lambda x: min(x[1], e) - max(x[0], s))
            label, d = cur[2], d + 1
        out.append(label)
    return out


def split_idle(idle: Sequence[Tuple[int, int]], spans: Sequence[Interval]
               ) -> Dict[str, float]:
    """Idle seconds by host span: every idle interval is cut at the edges
    of the spans inside it, and each piece goes to the innermost span that
    covers it (``none`` where none does)."""
    tree = _Tree(spans)
    out: Dict[str, float] = {}
    for s, e in idle:
        over = tree.over(s, e)
        cuts = sorted({s, e} | {x for a, b, _, _ in over for x in (a, b)
                                if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            held = [(d, n) for x, y, n, d in over if x <= a and b <= y]
            label = max(held)[1] if held else "none"
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def idle_inside_program_s(pt: ProgramTrace) -> float:
    """Idle seconds of the traced window inside any program span."""
    lo, hi = pt.window
    covered = union(pt.spans, lo, hi)
    total = 0
    for s, e in idle_intervals(pt.ops, lo, hi):
        for a, b in covered:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                total += ov
    return total * 1e-9


# -- the decode step's operations ----------------------------------------------

def self_times(ops: Sequence[Op]) -> List[int]:
    """Each operation's own time: a TPU trace lists a loop (``while``) and
    the operations of its body, which run inside it, on one line, so an
    op's duration less that of the ops it holds."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


def kv_move_share(pt: ProgramTrace) -> Optional[float]:
    """Share of the decode step program's device time in operations outside
    the ``model_decode`` scope (the page gather, the write-back and the copies
    XLA adds around them), over the executions that start in the traced
    window, each operation counted for its own time (``self_times``); None
    where no operation of the program carries that scope (a program without
    the scopes)."""
    lo, hi = pt.window
    ops = [o for o in pt.ops if o.module == STEP_PROGRAM and lo <= o.start < hi]
    if not any(MODEL_SCOPE in o.scope for o in ops):
        return None
    own = self_times(ops)
    total = sum(own)
    moved = sum(t for o, t in zip(ops, own) if MODEL_SCOPE not in o.scope)
    return moved / total if total > 0 else None


def for_run(run) -> Optional[ProgramTrace]:
    """The program trace of a traced run: read from the profile the run
    wrote (``runner.TRACE_DIR``) once, and kept on the run as ``program``;
    None for an untraced run, or where the profile's window is not the
    run's."""
    if run.trace is None:
        return None
    pt = getattr(run, "program", None)
    if pt is None:
        from .runner import TRACE_DIR

        pt = run.program = load(tracefile.find_trace(TRACE_DIR))
    if abs(pt.window_s - run.trace.window_s) > 1e-9:
        return None
    return pt


# -- the recorder's own spans (host clock) ---------------------------------------

def clock_offset(spans: Sequence, rounds: Sequence) -> Optional[float]:
    """Recorder clock less the harness's clock: the first ``orch.round``
    span starts a few microseconds after the first round of the open-loop
    loop (``Round.t0``)."""
    first = min((s.t0 for s in spans if s.name == "orch.round"), default=None)
    if first is None or not rounds:
        return None
    return first - rounds[0].t0


def engine_host_ms_mean(spans: Sequence, lo: float, hi: float
                        ) -> Optional[float]:
    """Mean host milliseconds of an engine step outside its wait for the
    device (``engine.step`` less its ``engine.fetch`` child), over the steps
    that start in ``[lo, hi)`` (recorder clock)."""
    fetch = {s.parent: s.duration for s in spans if s.name == "engine.fetch"}
    own = [s.duration - fetch.get(s.sid, 0.0) for s in spans
           if s.name == "engine.step" and lo <= s.t0 < hi]
    return sum(own) / len(own) * 1e3 if own else None


def prefill_pad_share(spans: Sequence, lo: float, hi: float
                      ) -> Optional[float]:
    """Padding positions over all prefilled positions, from the args of
    the ``engine.admit`` spans that start in ``[lo, hi)``."""
    pad = real = 0
    for s in spans:
        if s.name == "engine.admit" and lo <= s.t0 < hi:
            pad += s.args["pad"]
            real += s.args["prompt"]
    return pad / (pad + real) if pad + real else None
