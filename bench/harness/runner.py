"""One run of one cell: set-up, the measured window, the check, the result."""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from typing import Dict, Optional

from . import check, flops, model, spec, tracefile, traffic, warmup
from .compile_meter import CompileMeter
from .driver import Sent, drive
from .records import Run

#: seconds at the end of the window that the profiler traces
TRACE_S = 8.0
#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = spec.ROOT / ".jax_cache"
#: where a traced run writes its profile (overwritten by the next one)
TRACE_DIR = spec.ROOT / ".bench" / "trace"
#: where each run leaves its records
RUNS_DIR = spec.ROOT / ".bench" / "runs"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(require_device: bool) -> Dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_device and d.platform == "cpu":
        raise SystemExit("bench: JAX finds no accelerator (first device is "
                         "the CPU); this benchmark runs only on a chip")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def within(gap: Optional[float], limit: float) -> bool:
    """The comparison that decides ``correct``, for the program and for its
    control alike: a widest gap was read and lies within the limit."""
    return gap is not None and gap <= limit


def save_records(run: Run, cell: str, seed: int, trace: bool) -> None:
    """The run's per-request and per-round records, for reading its tails
    afterwards: ``.bench/runs/<cell>-<seed>-<trace>.json``."""
    RUNS_DIR.mkdir(parents=True, exist_ok=True)
    out = {"t_open": run.t_open, "t_close": run.t_close, "t_end": run.t_end,
           "requests": [{"due": s.due, "submit_at": s.submit_at,
                         "start": s.req.start_time, "engine": s.engine,
                         "prompt": len(s.req.prompt),
                         "max_new": s.req.max_new_tokens,
                         "tokens": s.token_times} for s in run.sent],
           "rounds": [[r.t0, r.t1, [len(c) for _, c in r.rows]]
                      for r in run.rounds]}
    path = RUNS_DIR / f"{cell}-{seed}-{int(trace)}.json"
    path.write_text(json.dumps(out))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_device: bool = True,
             conf: Optional[dict] = None, rate: Optional[float] = None,
             lead: Optional[float] = None, control: bool = False) -> Dict:
    """Everything after the argument check.  ``conf``, ``rate`` and ``lead``
    replace the cell's own (the CPU rehearsal and the knee sweep use them);
    ``control`` adds the float8 control's reading on the same sample and
    ``control_correct``, the same comparison applied to it."""
    import jax

    from repro.models import Model
    from repro.serving import Request, State
    from repro.serving.engine import DECODE_SHAPE_LIMIT

    dev = device_info(require_device)
    if dev["count"] < cell.chips:
        raise SystemExit(f"bench: cell {cell.name} needs {cell.chips} chips, "
                         f"JAX finds {dev['count']}")
    peak = flops.peaks(dev["kind"]) if dev["platform"] != "cpu" else None
    if trace and peak is None:
        raise SystemExit("bench: device metrics need a chip")
    if dev["platform"] != "cpu":
        enable_compile_cache()
    meter = CompileMeter()
    conf = conf or cell.config
    m = model.dims(conf)
    cfg = model.model_config(conf)
    mdl = Model(cfg)
    weights = model.make_weights(m, seed)
    model.check_layout(mdl, weights)
    jax.block_until_ready(weights)
    log(f"{cell.name}: {conf['name']} {m['layers']} layers d_model "
        f"{m['d_model']} vocab {m['vocab']}, {flops.param_bytes(m)} parameter "
        f"bytes, weights made at {time.perf_counter() - t_start:.3f} s")

    mix = cell.mix
    rate = float(cell.cell["rate_rps"] if rate is None else rate)
    lead = float(mix["lead_s"] if lead is None else lead)
    tr = traffic.generate(mix, rate, lead + seconds, seed, m["vocab"],
                          m["max_seq"])
    sent = [Sent(req=Request(rid=i, prompt=tr.prompts[i],
                             max_new_tokens=int(tr.output_len[i]),
                             arrival_time=float(tr.due[i])),
                 due=float(tr.due[i])) for i in range(len(tr.due))]
    log(f"traffic {mix['name']} at {rate} req/s: {len(sent)} requests over "
        f"{lead + seconds:.1f} s, clipped {tr.clipped}")

    orch = model.build_orchestrator(conf, cfg, mdl, weights)
    page = int(conf["cluster"]["page_size"])
    spans = warmup.prefill_spans(mix, page, m["max_seq"])
    for k, eng in enumerate(orch.engines):
        slots = eng.cache.num_slots
        pairs = warmup.decode_pairs(mix, slots, DECODE_SHAPE_LIMIT, page,
                                    m["max_seq"])
        log(f"engine {k}: chain {list(eng.chain.servers)} blocks "
            f"{list(eng.chain.blocks)} capacity {eng.capacity} slots {slots} "
            f"pages {eng.cache.total_pages}; warm prefill {spans}, decode "
            f"(rows, span) {pairs}")
        warmup.warm_engine(eng, spans, pairs, slots, page, m["vocab"],
                           int(mix["prompt"]["min"]))
    jax.block_until_ready(orch.engines[0].cache.leaves)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {meter.compiles} programs compiled, "
        f"{meter.cache_loads} read from the cache, {meter.seconds:.3f} s")

    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        trace_dir = TRACE_DIR
    d = drive(orch, sent, lead + seconds, meter, t_open=lead,
              trace_dir=trace_dir, trace_from=lead + seconds - TRACE_S)
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {len(d.rounds)} rounds, loop stopped {d.t_end - lead - seconds:.3f}"
        f" s after the close; {d.built_in_window} programs built in the window "
        f"({d.compiles_in_window} compiled); peak_bytes_in_use {mem_peak}")

    run = Run(m=m, peak=peak, sent=sent, rounds=d.rounds, t_open=lead,
              t_close=lead + seconds, t_end=d.t_end, setup_s=setup_s,
              built_in_window=d.built_in_window, traced=d.traced)
    if trace:
        run.trace = tracefile.reduce(tracefile.load(tracefile.find_trace(TRACE_DIR)))
        if not run.trace.on_device:
            raise SystemExit("bench: the trace holds no device plane")

    window = run.window_requests()
    finished = [s for s in sent if s.req.state == State.DONE]
    failed = sum(1 for s in window if s.req.state == State.FAILED)
    # free the program's state before the reference runs: the engines stay
    # reachable from JAX's jit caches, so their page pools are deleted here
    for eng in orch.engines:
        for leaf in eng.cache.leaves:
            leaf.delete()
    del orch
    gc.collect()

    picked = check.sample(finished, seed)
    gap, ctrl_gap, n_tok = check.compare(m, weights, picked, control=control)
    limit = float(cell.cell["limits"]["max_logit_gap"])
    correct = within(gap, limit)
    log(f"check: {len(picked)} of {len(finished)} finished requests, {n_tok} "
        f"served tokens against the float32 reference"
        + (f"; control (float8 weights) widest gap {ctrl_gap}" if control else ""))

    fns = spec.metric_functions(cell.per_layer if trace else cell.end_to_end)
    metrics = {}
    units = {x["name"]: x["unit"] for x in cell.per_layer + cell.end_to_end}
    for name, fn in fns.items():
        value = fn(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units.get(name, "")}
    device = dict(dev, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": len(window),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        progs = sorted(run.trace.programs.items(), key=lambda kv: -kv[1][0])
        result["breakdown"] = {
            "device_ops": [[k, v[0]] for k, v in progs[:10]],
            "idle_gaps": [[k, v] for k, v in run.trace.gaps[:10]]}
        log(f"trace: {len(run.traced_rounds())} rounds, "
            f"{sum(len(r.rows) for r in run.traced_rounds())} engine steps; "
            f"{run.trace.window_s:.3f} s traced, busy "
            f"{run.trace.busy_s:.6f} s; idle by host span "
            f"{run.trace.idle_by_span}; programs "
            f"{ {k: v for k, v in progs} }")
    save_records(run, cell.name, seed, trace)
    checks = {"max_logit_gap": {"value": gap, "limit": limit}}
    if control:
        checks["control_max_logit_gap"] = {"value": ctrl_gap, "limit": limit}
        result["control_correct"] = within(ctrl_gap, limit)
    result["checks"] = checks
    result["_run"] = run
    return result
