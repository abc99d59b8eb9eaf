"""The harness end to end on the CPU at a tiny size, through the same loop a
chip run takes: the cell's files with the model and the mix cut down, the
look for a chip skipped, and the float8 control put through the same
comparison.  Then the same run with the timed path broken
underneath (a token altered where the engine produces it; a decode step that
leaves the page pool as it was), which must come out not correct.  And the
command itself, which must refuse the CPU."""
import bench_paths  # noqa: F401  (puts bench/ and src/ on sys.path)
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from harness import model, runner, spec

CELL = "qwen2-7b-half.chat"
SEED = 2**31 + 77
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            max_position_embeddings=64)
#: the tiny model's own limit: on the CPU the program's widest gap read
#: 0.0-0.030 and the float8 control's 0.090-0.132 over three seeds (the
#: cell's limit is set for its own size and would pass this control)
TINY_LIMIT = 0.06


def tiny_cell() -> spec.Cell:
    from repro.serving import service_spec_for

    cell = spec.load_cell(CELL)
    conf = dict(cell.config, **TINY)
    sp = service_spec_for(model.model_config(conf), max_seq=64)
    # two servers, each holding both layers and four slots: two chains
    conf["cluster"] = dict(conf["cluster"], servers=2, page_size=16,
                           memory_gb=2 * (sp.block_size_gb
                                          + 4.5 * sp.cache_size_gb))
    mix = dict(cell.mix, lead_s=1.0,
               prompt=dict(cell.mix["prompt"], mean=12, min=4, max=24),
               output=dict(cell.mix["output"], mean=6, min=2, max=8))
    return dataclasses.replace(cell, config=conf, mix=mix,
                               cell=dict(cell.cell, rate_rps=60.0,
                                         limits={"max_logit_gap": TINY_LIMIT}))


def rehearse(control: bool = False) -> dict:
    return runner.run_cell(tiny_cell(), SEED, 3.0, False,
                           t_start=time.perf_counter(), require_device=False,
                           control=control)


@pytest.fixture(scope="module")
def clean():
    return rehearse(control=True)


def test_rehearsal_serves_and_checks(clean):
    run = clean["_run"]
    assert clean["correct"] is True
    assert clean["attempted"] == len(run.window_requests()) > 5
    assert clean["failed"] == 0
    names = {m["name"] for m in spec.load_cell(CELL).end_to_end}
    assert set(clean["metrics"]) == names
    assert all(v["value"] > 0 for v in clean["metrics"].values())
    assert clean["device"]["platform"] == "cpu"
    assert list(clean)[-2:] == ["checks", "_run"]
    gap = clean["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    # both chains served, and every served token was stamped
    assert len({s.engine for s in run.sent if s.engine is not None}) == 2
    assert all(len(s.token_times) == len(s.req.output) for s in run.sent)
    assert run.built_in_window == 0


def test_float8_control_reads_far_above_the_program(clean):
    prog = clean["checks"]["max_logit_gap"]["value"]
    ctrl = clean["checks"]["control_max_logit_gap"]["value"]
    assert ctrl > 3 * prog


def test_float8_control_is_not_correct(clean):
    """The control, put through the comparison that decides ``correct`` at
    the limit the run holds the program to, comes out not correct."""
    ctrl = clean["checks"]["control_max_logit_gap"]
    assert ctrl["limit"] == clean["checks"]["max_logit_gap"]["limit"] == TINY_LIMIT
    assert clean["correct"] is True
    assert clean["control_correct"] is False
    assert ctrl["value"] > ctrl["limit"]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import PagedChainEngine

    step = PagedChainEngine.step

    def altered(self, now=0.0):
        done = step(self, now)
        for req in list(self.requests.values()) + done:
            req.output[-1] = (req.output[-1] + 1) % TINY["vocab_size"]
        return done

    monkeypatch.setattr(PagedChainEngine, "step", altered)
    res = rehearse()
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_unchanged_cache_state_is_not_correct(monkeypatch):
    from repro.serving.kv_cache import PagedCache

    monkeypatch.setattr(PagedCache, "scatter",
                        lambda self, leaves, *args: list(leaves))
    res = rehearse()
    assert res["correct"] is False


def _command(cwd: Path, trace: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "3",
         "--seconds", "1", "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return not isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return True


@pytest.mark.parametrize("trace", [0, 1])
def test_command_refuses_the_cpu(trace):
    proc = _command(spec.ROOT, trace)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no accelerator" in proc.stderr


def test_command_needs_the_program(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    proc = _command(tmp_path, 0)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
