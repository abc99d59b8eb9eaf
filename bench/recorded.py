#!/usr/bin/env python3
"""One run of a cell with the program's span recorder attached.

    python bench/recorded.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``bench/run.py``'s: the same set-up, traffic, window, check and
result line.  A ``repro.obs.SpanRecorder`` and a ``repro.obs.MetricsRegistry``
are attached to the orchestrator from its construction on (``run_cell`` builds
it through ``harness.model.build_orchestrator``, which this command wraps).
With ``--trace 0`` the line's end-to-end metrics show what recording costs,
against ``bench/run.py`` on the same seed.  With ``--trace 1`` the line also
holds, under ``recorded``:

* the readings that need the program's spans: ``device_idle_host_pct``,
  ``engine_host_ms_mean`` (window before the trace), ``step_kv_move_pct``
  and ``prefill_pad_pct`` (the window's admissions), beside
  ``prefill_pad_reckoned_pct``, the same share reckoned from the prompts of
  the requests those admissions took and ``kv_span``;
* the ten longest idle gaps, each named by the innermost program span that
  overlaps it most, and the idle seconds split by span;
* the ``engine.*`` counters.

The spans go to ``.bench/runs/<cell>-<seed>-<trace>.trace.json`` (Chrome trace
format).  The benchmark's own runs never attach the recorder.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def recorded(run, rec, reg, page: int) -> dict:
    """The span readings of a traced run (see the module's docstring)."""
    from harness import spans
    from repro.serving.kv_cache import kv_span

    pt = spans.for_run(run)
    off = spans.clock_offset(rec.spans, run.rounds)
    out = {"counters": {k: v for k, v in reg.snapshot().as_dict().items()
                        if k.startswith("engine.")}}
    if pt is None or off is None:
        return out
    lo, hi = pt.window
    idle = spans.idle_intervals(pt.ops, lo, hi)
    host = pt.spans + pt.bench
    labels = spans.label_gaps(idle, host)
    gaps = sorted(((lab, (e - s) * 1e-9) for (s, e), lab in zip(idle, labels)),
                  key=lambda g: -g[1])
    lo_s, hi_s = run.t_open + off, run.t_close + off
    pad = spans.prefill_pad_share(rec.spans, lo_s, hi_s)
    # the same share from the admitted requests' own prompts and kv_span
    # (a request admitted again after a preemption prefills its output too)
    prompt = {s.req.rid: len(s.req.prompt) for s in run.sent}
    admitted = [prompt[a.args["rid"]] for a in rec.spans
                if a.name == "engine.admit" and lo_s <= a.t0 < hi_s]
    padded = [kv_span(n, page, run.m["max_seq"]) for n in admitted]
    share = spans.kv_move_share(pt)
    own = spans.engine_host_ms_mean(rec.spans, lo_s, run.host_close + off)
    out.update({
        "device_idle_host_pct": spans.idle_inside_program_s(pt)
        / pt.window_s * 100.0,
        "engine_host_ms_mean": own,
        "step_kv_move_pct": None if share is None else share * 100.0,
        "prefill_pad_pct": None if pad is None else pad * 100.0,
        "prefill_pad_reckoned_pct": (sum(padded) - sum(admitted))
        / sum(padded) * 100.0 if admitted else None,
        "idle_gaps": [[k, v] for k, v in gaps[:10]],
        "idle_by_span": spans.split_idle(idle, host),
    })
    return out


def main(argv=None) -> int:
    sys.path.insert(0, str(BENCH))
    import run as bench_run

    args = bench_run.parse(argv)
    from harness import model, runner, spec

    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.obs import MetricsRegistry, SpanRecorder, export_chrome_trace

    rec, reg = SpanRecorder(), MetricsRegistry()
    build = model.build_orchestrator

    def build_recorded(*a, **kw):
        orch = build(*a, **kw)
        orch.recorder, orch.metrics = rec, reg
        return orch

    model.build_orchestrator = build_recorded
    cell = spec.load_cell(args.workload)
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    run = result.pop("_run")
    if args.trace:
        page = int(cell.config["cluster"]["page_size"])
        result["recorded"] = recorded(run, rec, reg, page)
    runner.RUNS_DIR.mkdir(parents=True, exist_ok=True)
    export_chrome_trace(rec.run_trace(), str(
        runner.RUNS_DIR / f"{cell.name}-{args.seed}-{args.trace}.trace.json"))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
