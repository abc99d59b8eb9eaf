"""The program's spans in a profiler trace (``harness.spans``): gap labels and
the idle split on hand-made intervals, the span metrics on hand-built runs,
and a small trace recorded on the CPU with the recorder attached
(``data/cpu_spans.xplane.pb``, with the recorder's own copy of its spans in
``data/cpu_spans.json``; ``python bench/tests/test_bench_spans.py`` records
both anew)."""
import bench_paths  # noqa: F401  (puts bench/ and src/ on sys.path)
import json
import sys
from pathlib import Path

import pytest

from harness import spans, spec, xspace
from harness.driver import Round
from harness.records import Run
from harness.spans import Op, ProgramTrace
from harness.tracefile import TraceSummary
from repro.obs import Span

DATA = Path(__file__).parent / "data"


def test_depths_follow_nesting():
    ivs = [(0, 100, "a"), (10, 50, "b"), (20, 30, "c"), (60, 90, "d"),
           (200, 300, "e")]
    assert spans.depths(ivs) == [0, 1, 2, 1, 0]


def test_gaps_take_the_innermost_program_span():
    prog = [(0, 1000, "orch.round"), (100, 600, "engine.step"),
            (100, 200, "engine.plan"), (200, 300, "engine.launch"),
            (300, 550, "engine.fetch"), (550, 600, "engine.commit"),
            (700, 900, "orch.dispatch")]
    bench = [(-10, 1000, "bench.round"), (1000, 2000, "bench.wait")]
    gaps = [(120, 180),        # inside plan, inside step, inside round
            (250, 350),        # 50 in launch, 50 in fetch: the earlier
            (280, 400),        # more of it in fetch than in launch
            (610, 690),        # the round alone
            (950, 1500)]       # 50 in bench.round's round, 500 in bench.wait
    assert spans.label_gaps(gaps, prog + bench) == [
        "engine.plan", "engine.launch", "engine.fetch", "orch.round",
        "bench.wait"]
    assert spans.label_gaps(gaps, prog)[4] == "orch.round"
    # where no program span reaches, the bench.* label stands
    assert spans.label_gaps([(1200, 1300)], prog + bench) == ["bench.wait"]
    assert spans.label_gaps([(2500, 2600)], prog + bench) == ["none"]


def test_idle_split_cuts_at_span_edges():
    prog = [(0, 1000, "orch.round"), (100, 600, "engine.step"),
            (200, 300, "engine.launch"), (300, 550, "engine.fetch")]
    bench = [(-10, 1000, "bench.round"), (1000, 2000, "bench.wait")]
    idle = [(250, 350), (580, 620), (950, 1100), (2100, 2200)]
    got = spans.split_idle(idle, prog + bench)
    want = {"engine.launch": 50, "engine.fetch": 50, "engine.step": 20,
            "orch.round": 20 + 50, "bench.wait": 100, "none": 100}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in idle) * 1e-9)


def _pt(ops, prog=(), window=(0, 1000)):
    return ProgramTrace(window=window, spans=sorted(prog), bench=[],
                        ops=list(ops))


def test_idle_inside_program_spans():
    ops = [Op(0, 100, "jit__step_impl", ""), Op(300, 400, "jit_prefill", ""),
           Op(800, 900, "jit__step_impl", "")]
    prog = [(50, 350, "orch.round"), (120, 200, "engine.step"),
            (600, 700, "orch.dispatch")]
    pt = _pt(ops, prog)
    # idle: 100..300, 400..800, 900..1000; inside spans: 100..300 (round)
    # and 600..700 (dispatch)
    assert spans.idle_intervals(pt.ops, 0, 1000) == [(100, 300), (400, 800),
                                                    (900, 1000)]
    assert spans.idle_inside_program_s(pt) == pytest.approx(300e-9)


def test_kv_move_share_reads_the_scopes():
    step = "jit__step_impl"
    ops = [Op(0, 30, step, "jit(_step_impl)/kv_gather/gather:"),
           Op(30, 40, step, ""),                        # a copy XLA added
           # the layer loop holds its body's operations on the same line
           Op(40, 90, step, "jit(_step_impl)/model_decode/while:"),
           Op(45, 70, step, "jit(_step_impl)/model_decode/dot_general:"),
           Op(70, 80, step, "jit(_step_impl)/model_decode/add:"),
           Op(90, 100, step, "jit(_step_impl)/kv_scatter/scatter:"),
           Op(100, 200, "jit_prefill", "jit(prefill)/dot_general:"),
           Op(2000, 2100, step, "")]                   # outside the window
    assert spans.self_times(ops[2:5]) == [15, 25, 10]
    assert spans.kv_move_share(_pt(ops)) == pytest.approx(0.5)
    unscoped = [Op(o.start, o.end, o.module, "") for o in ops]
    assert spans.kv_move_share(_pt(unscoped)) is None


def test_op_scopes_come_from_the_event_metadata(tmp_path):
    """A TPU trace keeps an op's scope path in its event metadata's stats
    (``tf_op``), which ``ProfileData`` does not show; ``xspace`` reads it,
    as a string or as a reference to an interned stat name."""
    space = xspace.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "flops"),
                      (3, "jit(_step_impl)/kv_scatter/scatter:")):
        dev.stat_metadata.add(key=key, value={"id": key, "name": name})
    md = dev.event_metadata.add(key=7).value
    md.name, md.display_name = "%fusion.3 = bf16[8] fusion(...)", "fusion.3"
    md.stats.add(metadata_id=2, int64_value=100)
    md.stats.add(metadata_id=1, str_value="jit(_step_impl)/kv_gather/gather:")
    ref = dev.event_metadata.add(key=8).value
    ref.name = "%scatter.1"
    ref.stats.add(metadata_id=1, ref_value=3)
    dev.event_metadata.add(key=9).value.name = "%copy.2"   # no scope
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "orch.round"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert xspace.op_scopes(path) == {"/device:TPU:0": {
        "%fusion.3 = bf16[8] fusion(...)": "jit(_step_impl)/kv_gather/gather:",
        "fusion.3": "jit(_step_impl)/kv_gather/gather:",
        "%scatter.1": "jit(_step_impl)/kv_scatter/scatter:"}}


def _run(trace_window_s=1000e-9, rounds=()):
    return Run(m={"max_seq": 64}, peak={}, sent=[], rounds=list(rounds),
               t_open=0.0, t_close=10.0, t_end=10.0, setup_s=0.0,
               built_in_window=0,
               trace=TraceSummary(window_s=trace_window_s, busy_s=0.0,
                                  programs={}, gaps=[], idle_by_span={},
                                  on_device=True))


def test_step_kv_move_readers_on_a_hand_built_run():
    step = "jit__step_impl"
    run = _run()
    run.program = _pt([Op(0, 25, step, "x/kv_gather/gather"),
                       Op(25, 100, step, "x/model_decode/dot")])
    for name in ("step_kv_move_pct", "step_kv_move_pct.tokens"):
        assert spec.metric_function(name)(run) == pytest.approx(25.0)
    stale = _run(trace_window_s=2000e-9)     # the profile is another run's
    stale.program = run.program
    assert spans.for_run(stale) is None
    assert spec.metric_function("step_kv_move_pct")(stale) is None
    untraced = _run()
    untraced.trace = None
    assert spec.metric_function("step_kv_move_pct")(untraced) is None


def _span(name, t0, t1, sid, parent=None, **args):
    return Span(name, "engine", t0, t1, 0, 0, args, sid=sid, parent=parent)


def test_recorder_span_readings_by_hand():
    rec = [_span("orch.round", 100.0, 100.05, 0),
           _span("engine.step", 100.001, 100.031, 1, 0, rows=2, pad=0),
           _span("engine.fetch", 100.010, 100.030, 2, 1),
           _span("engine.admit", 100.032, 100.049, 3, 0, rid=4, prompt=100,
                 pad=28),
           _span("orch.round", 101.0, 101.01, 4),
           _span("engine.step", 101.001, 101.009, 5, 4, rows=1, pad=0),
           _span("engine.admit", 105.0, 105.01, 6, rid=5, prompt=16, pad=0)]
    rounds = [Round(99.999, 100.06, []), Round(100.999, 101.02, [])]
    off = spans.clock_offset(rec, rounds)
    assert off == pytest.approx(0.001)
    # engine steps less their wait: 30 - 20 ms and 8 ms
    assert spans.engine_host_ms_mean(rec, 0.0, 200.0) == pytest.approx(9.0)
    assert spans.engine_host_ms_mean(rec, 101.0, 200.0) == pytest.approx(8.0)
    assert spans.engine_host_ms_mean(rec, 102.0, 200.0) is None
    assert spans.prefill_pad_share(rec, 0.0, 200.0) == pytest.approx(28 / 144)
    assert spans.prefill_pad_share(rec, 104.0, 200.0) == 0.0
    assert spans.prefill_pad_share(rec, 200.0, 300.0) is None


def test_recorded_cpu_trace_lines_up_with_the_recorder():
    pt = spans.load(DATA / "cpu_spans.xplane.pb")
    mem = json.loads((DATA / "cpu_spans.json").read_text())
    names = [n for _, _, n in pt.spans]
    assert names.count("orch.round") == mem["rounds"]
    assert {"engine.step", "engine.plan", "engine.launch", "engine.fetch",
            "engine.commit", "engine.admit", "engine.prefill",
            "engine.write", "orch.dispatch"} <= set(names)
    assert pt.ops and {o.module for o in pt.ops} >= {"jit__step_impl",
                                                     "jit_prefill"}
    # the profiler's copies, after the offset of bench.traced, lie within
    # 1 ms of the recorder's own spans
    offset = pt.window[0] - mem["traced_ns"]
    copies = {}
    for s, e, n in pt.spans:
        copies.setdefault(n, []).append((s, e))
    mine = {}
    for n, t0, t1 in mem["spans"]:
        mine.setdefault(n, []).append((t0, t1))
    assert set(mine) == set(copies)
    for n, ivs in mine.items():
        assert len(ivs) == len(copies[n])
        for (t0, t1), (s, e) in zip(sorted(ivs), sorted(copies[n])):
            assert abs(s - offset - t0) < 1e6 and abs(e - offset - t1) < 1e6
    # the program's spans label every idle gap inside a round
    lo, hi = pt.window
    idle = spans.idle_intervals(pt.ops, lo, hi)
    labels = spans.label_gaps(idle, pt.spans + pt.bench)
    rounds = [(s, e) for s, e, n in pt.spans if n == "orch.round"]
    for (s, e), label in zip(idle, labels):
        if any(a <= s and e <= b for a, b in rounds):
            assert label.startswith(("orch.", "engine."))


def record(out: Path = DATA) -> None:
    """Serve a tiny model for a few rounds with the recorder attached and
    the profiler on, on the CPU; keep the trace and the recorder's spans."""
    import os
    import shutil
    import tempfile
    import time
    from functools import partial

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from repro.configs import get
    from repro.core import Server
    from repro.models import Model
    from repro.obs import SpanRecorder
    from repro.serving import (Orchestrator, OrchestratorConfig,
                               PagedChainEngine, Request, service_spec_for)

    cfg = get("stablelm-1.6b").reduced(num_layers=1, d_model=64, num_heads=2,
                                       num_kv_heads=2, d_ff=128,
                                       vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sp = service_spec_for(cfg, max_seq=32)
    orch = Orchestrator(
        [Server("s0", sp.block_size_gb + 3 * sp.cache_size_gb, 0.05, 0.02)],
        sp, model, params, 0.5,
        OrchestratorConfig(max_seq=32, engine_factory=partial(
            PagedChainEngine, page_size=8)))
    rng = np.random.default_rng(0)

    def request(rid, n):
        return Request(rid=rid, prompt=rng.integers(1, 60, n).astype(np.int32),
                       max_new_tokens=4)

    # compile every shape first, so the trace holds no compilation
    orch.submit(request(100, 5))
    orch.drain()
    orch.recorder = rec = SpanRecorder()
    tmp = Path(tempfile.mkdtemp())
    ann = jax.profiler.TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # the program's spans, not Python's
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    with ann("bench.traced"):
        traced_ns = time.perf_counter_ns()
        with ann("bench.submit"):
            orch.submit(request(0, 5))
        rounds = 0
        while any(e.requests for e in orch.engines):
            with ann("bench.round"):
                orch.step()
            rounds += 1
            time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(next(tmp.glob("plugins/profile/*/*.xplane.pb")),
                out / "cpu_spans.xplane.pb")
    shutil.rmtree(tmp)
    (out / "cpu_spans.json").write_text(json.dumps({
        "traced_ns": traced_ns, "rounds": rounds,
        "spans": [[s.name, round(s.t0 * 1e9), round(s.t1 * 1e9)]
                  for s in rec.spans if s.name != "req.queue"]}))


if __name__ == "__main__":
    record(Path(sys.argv[1]) if len(sys.argv) > 1 else DATA)
