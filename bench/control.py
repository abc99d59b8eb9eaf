#!/usr/bin/env python3
"""Read a cell's correctness number for the program and for its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds s]

Each seed is one run of the cell as ``bench/run.py`` makes it (same weights,
traffic, window and sample), in a process of its own.  It prints the widest
gap of the served tokens (the program's reading) and, at the same positions of
the same sample, the widest gap of the token that the float8 control puts
first (the control's reading).  The control is the float32 reference with
every matrix stored in float8 e4m3, the precision below the configuration's
bfloat16.  A cell's limit in ``bench/cells/<cell>.json`` is set between the
program's largest reading and the control's smallest.  Both readings go
through the comparison that decides ``correct``; the command exits non-zero
where the control comes out correct (or the program does not).  The
benchmark's own runs never run the control.
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

    cell = spec.load_cell(args.workload)
    seconds = args.seconds or json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    res = runner.run_cell(cell, args.seed, seconds, False, t_start=T_START,
                          control=True)
    run = res.pop("_run")
    print("control " + json.dumps({
        "seed": args.seed,
        "program": res["checks"]["max_logit_gap"]["value"],
        "control": res["checks"]["control_max_logit_gap"]["value"],
        "limit": res["checks"]["max_logit_gap"]["limit"],
        "correct": res["correct"], "control_correct": res["control_correct"],
        "finished": sum(1 for s in run.sent if s.req.finish_time is not None),
        **{k: v["value"] for k, v in res["metrics"].items()}}), flush=True)
    return 0 if res["correct"] and not res["control_correct"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--seed", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    if args.seed is not None:
        return child(args)
    rc = 0
    for seed in args.seeds.split(","):
        cmd = [sys.executable, __file__, "--workload", args.workload,
               "--seed", seed]
        if args.seconds:
            cmd += ["--seconds", str(args.seconds)]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(proc.stderr[-3000:])
        print(proc.stdout.strip().splitlines()[-1] if proc.stdout.strip()
              else f"control seed {seed} failed: exit {proc.returncode}",
              flush=True)
        rc = rc or proc.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
