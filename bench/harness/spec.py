"""Find a cell's files by name and read them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

#: the benchmark's own directory (``<checkout>/bench``)
BENCH_DIR = Path(__file__).resolve().parents[1]
#: the checkout's root, which holds ``BENCHMARK.json`` and the program's ``src``
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A cell, file or metric named in ``BENCHMARK.json`` is missing or malformed."""


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path.relative_to(ROOT)}") from e


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/mixes/<traffic>.json
    cell: dict            # bench/cells/<name>.json: rate and limits
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    conf = next((c for c in bench["configs"] if c["name"] == w["config"]), None)
    if conf is None:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload,
        config=_load_json(root / conf["file"]),
        mix=_load_json(BENCH_DIR / "mixes" / f"{w['traffic']}.json"),
        cell=_load_json(BENCH_DIR / "cells" / f"{workload}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def metric_function(name: str) -> Callable:
    """``compute`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no metric reader bench/metrics/{name}.py")
    module = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


def metric_functions(metrics: List[dict]) -> Dict[str, Callable]:
    return {m["name"]: metric_function(m["name"]) for m in metrics}
