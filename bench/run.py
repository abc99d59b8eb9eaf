#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` ``workloads``.  The run makes the
weights and the traffic from ``--seed``, composes the chains and warms up the
cell's shapes (set-up), serves the traffic open loop on the wall clock for
``--seconds`` after a lead-in, checks a sample of the served tokens against a
float32 reference, and prints one JSON line: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics from a profiler
trace of a few seconds in the middle of the window.  Without an accelerator,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(BENCH))
    from harness import runner, spec

    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {spec.ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = spec.load_cell(args.workload)
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    result.pop("_run")
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
