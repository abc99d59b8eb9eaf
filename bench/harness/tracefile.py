"""From a profiler trace to device busy time, per-program device time and
idle gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU the
device planes (``/device:TPU:<n>``) carry an ``XLA Ops`` line (each operation
as it ran) and an ``XLA Modules`` line (each program execution).  On the CPU,
where a test records its trace, XLA's operations run on host threads and carry
``hlo_op`` and ``hlo_module`` stats; they stand in for one device.  The host's
``bench.*`` spans come from ``jax.profiler.TraceAnnotation``; ``bench.traced``
spans the traced window itself.

``reduce`` works on plain interval lists, so a test can check it by hand.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class RawTrace:
    devices: List[Tuple[List[Interval], List[Interval]]]  # (ops, modules)
    spans: List[Interval]                                  # host bench.* spans
    on_device: bool                                        # a real device plane


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # mean over devices
    programs: Dict[str, Tuple[float, int]]   # name -> (device seconds, runs)
    gaps: List[Tuple[str, float]]        # idle gaps, longest first
    idle_by_span: Dict[str, float]       # idle seconds by host span
    on_device: bool

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def program_name(name: str) -> str:
    """``jit__step_impl(12)`` -> ``jit__step_impl``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def _stats(event) -> Dict[str, object]:
    with warnings.catch_warnings():     # the stats type warns as it is read
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            return dict(event.stats)
        except (TypeError, ValueError):
            return {}


def load(path: Path) -> RawTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: List[Interval] = []
    cpu_ops: List[Interval] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
            mods = [(e.start_ns, e.start_ns + e.duration_ns,
                     program_name(e.name))
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            if ops or mods:
                devices.append((ops or mods, mods or ops))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                        str(st.get("hlo_module", "?"))))
    on_device = bool(devices)
    if not on_device and cpu_ops:
        devices = [(cpu_ops, cpu_ops)]
    return RawTrace(devices=devices, spans=spans, on_device=on_device)


def union(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _labels(gaps: Sequence[Tuple[int, int]], spans: Sequence[Interval]
            ) -> List[str]:
    """For each gap (sorted), the host span that overlaps it most (``none``
    where no span does).  The ``bench.*`` spans follow one another, so one
    sweep over both sorted lists finds them."""
    spans = sorted(spans)
    out, p = [], 0
    for s, e in gaps:
        while p < len(spans) and spans[p][1] <= s:
            p += 1
        best, label, q = 0, "none", p
        while q < len(spans) and spans[q][0] < e:
            ov = min(spans[q][1], e) - max(spans[q][0], s)
            if ov > best:
                best, label = ov, spans[q][2]
            q += 1
        out.append(label)
    return out


def reduce(raw: RawTrace) -> TraceSummary:
    windows = [(s, e) for s, e, n in raw.spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    spans = [sp for sp in raw.spans if sp[2] != WINDOW_SPAN]
    busy = []
    programs: Dict[str, List[float]] = {}
    gaps: List[Tuple[str, float]] = []
    idle_by: Dict[str, float] = {}
    for ops, mods in raw.devices:
        merged = union(ops, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for s, e, name in mods:
            if lo <= s < hi:
                acc = programs.setdefault(name, [0.0, 0])
                acc[0] += (min(e, hi) - s) / len(raw.devices) * 1e-9
                acc[1] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), label in zip(idle, _labels(idle, spans)):
            gaps.append((label, (e - s) * 1e-9))
            idle_by[label] = idle_by.get(label, 0.0) + (e - s) * 1e-9 \
                / len(raw.devices)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / max(1, len(busy)) * 1e-9,
        programs={k: (v[0], int(v[1]) // max(1, len(raw.devices)))
                  for k, v in programs.items()},
        gaps=gaps, idle_by_span=idle_by, on_device=raw.on_device)


def find_trace(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]
