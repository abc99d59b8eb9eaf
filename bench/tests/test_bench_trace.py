"""The trace reduction, on hand-made intervals and on a small trace recorded
on the CPU (``data/cpu_trace.xplane.pb``: three rounds of two jitted programs,
``matmul_sum`` and ``tanh_twice``, each followed by a 20 ms sleep in
``bench.wait``, then one ``matmul_sum`` in ``bench.submit``, all inside
``bench.traced``)."""
import bench_paths  # noqa: F401  (puts bench/ and src/ on sys.path)
from pathlib import Path

import pytest

from harness import tracefile
from harness.tracefile import RawTrace

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def test_union_merges_overlaps_and_clips():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (38, 50, "d"), (60, 70, "e")]
    assert tracefile.union(ivs, 2, 65) == [(2, 20), (30, 50), (60, 65)]


def test_reduce_by_hand():
    ops = [(100, 200, "x"), (150, 300, "y"), (500, 600, "z")]
    mods = [(100, 300, "jit_step"), (500, 600, "jit_prefill")]
    spans = [(0, 1000, "bench.traced"), (90, 310, "bench.round"),
             (310, 480, "bench.wait"), (480, 620, "bench.submit")]
    s = tracefile.reduce(RawTrace(devices=[(ops, mods)], spans=spans,
                                  on_device=True))
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(300e-9)          # 100..300 and 500..600
    assert s.idle_share == pytest.approx(0.7)
    assert s.programs == {"jit_step": (pytest.approx(200e-9), 1),
                          "jit_prefill": (pytest.approx(100e-9), 1)}
    # gaps: 0..100 (round 10 of it, none else), 300..500 (wait 170, submit 20,
    # round 10), 600..1000 (submit 20, nothing else): the longest first
    assert [g[0] for g in s.gaps] == ["bench.submit", "bench.wait", "bench.round"]
    assert s.gaps[0][1] == pytest.approx(400e-9)
    assert s.idle_by_span["bench.wait"] == pytest.approx(200e-9)


def test_program_name_strips_the_run_id():
    assert tracefile.program_name("jit__step_impl(123)") == "jit__step_impl"
    assert tracefile.program_name("jit_prefill") == "jit_prefill"


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tracefile.reduce(RawTrace(devices=[([], [])], spans=[], on_device=True))


def test_recorded_cpu_trace():
    raw = tracefile.load(DATA)
    assert not raw.on_device and len(raw.devices) == 1
    names = [n for _, _, n in raw.spans]
    assert names.count("bench.round") == 3 and names.count("bench.wait") == 3
    assert names.count("bench.submit") == 1 and names.count("bench.traced") == 1
    s = tracefile.reduce(raw)
    lo, hi = next((a, b) for a, b, n in raw.spans if n == "bench.traced")
    ops = raw.devices[0][0]
    # busy time: the union of the operations, worked out here point by point
    edges = sorted({max(lo, min(hi, x)) for a, b, _ in ops for x in (a, b)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s0 <= a and b <= e0 for s0, e0, _ in ops))
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert set(s.programs) == {"jit_matmul_sum", "jit_tanh_twice"}
    total = sum(min(b, hi) - a for a, b, _ in ops if lo <= a < hi) * 1e-9
    assert sum(v[0] for v in s.programs.values()) == pytest.approx(total)
    # the three sleeps are the three longest idle gaps, each about 20 ms
    assert [g[0] for g in s.gaps[:3]] == ["bench.wait"] * 3
    assert all(0.015 < g[1] < 0.2 for g in s.gaps[:3])
    assert s.window_s == pytest.approx(s.busy_s + sum(g for _, g in s.gaps))
