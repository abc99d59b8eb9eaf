#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates.

    python bench/sweep.py --workload <cell> --rates 0.5,1,1.5 --seconds 30 [--seed n]

Each rate is one run of the cell, in a process of its own (a process keeps the
engines it built), at that rate instead of the cell's.  Each prints a line of
what shows whether the system keeps up: requests due, finished, and with no
first token at the close; time to first token at the median and the cell's
percentile; the gap between tokens; tokens per second; rows per engine step.
The knee is the highest rate with no growing backlog; a cell's rate is fixed at
a share of it in ``bench/cells/<cell>.json``.  The benchmark's runs never sweep.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def child(args) -> int:
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    from harness import runner, spec
    from harness.records import percentile

    cell = spec.load_cell(args.workload)
    res = runner.run_cell(cell, args.seed, args.seconds, False,
                          t_start=T_START, rate=args.rate, lead=args.lead)
    run = res.pop("_run")
    win = run.window_requests()
    rows = [len(c) for r in run.window_rounds() for _, c in r.rows]
    print("sweep " + json.dumps({
        "rate_rps": args.rate, "due": len(win),
        "finished": sum(1 for s in win if s.req.finish_time is not None),
        "no_first_token": sum(1 for s in win if not s.token_times),
        "ttft_p50_ms": percentile((run.ttft_s(s) for s in win), 50) * 1e3,
        "rows_mean": sum(rows) / max(1, len(rows)),
        "max_logit_gap": res["checks"]["max_logit_gap"]["value"],
        **{k: v["value"] for k, v in res["metrics"].items()}}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--rate", type=float, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--lead", type=float, default=None)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if args.rate is not None:
        return child(args)
    rc = 0
    for rate in args.rates.split(","):
        cmd = [sys.executable, __file__, "--workload", args.workload,
               "--rate", rate, "--seconds", str(args.seconds),
               "--seed", str(args.seed)]
        if args.lead is not None:
            cmd += ["--lead", str(args.lead)]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(proc.stderr[-3000:])
        print(proc.stdout.strip().splitlines()[-1] if proc.stdout.strip()
              else f"sweep rate {rate} failed: exit {proc.returncode}",
              flush=True)
        rc = rc or proc.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
