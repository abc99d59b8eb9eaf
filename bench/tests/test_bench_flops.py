"""The operation and byte functions against hand reckonings."""
import bench_paths  # noqa: F401  (puts bench/ and src/ on sys.path)
import json
from pathlib import Path

import pytest

from harness import flops, model

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return model.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,params,kv,block", [
    # 24 x (4 x 2048^2 + 3 x 2048 x 5632 + 2 x 2048 + 3 x 2048) + 2 x 100352 x 2048 + 2048
    ("stablelm-1.6b", 1_644_414_976, 196_608, 51_390_464),
    # 14 x 233.1e6 + 2 x 152064 x 3584 + 3584; KV 14 x 2 x 4 x 128 x 2 B
    ("qwen2-7b-half", 4_352_807_424, 28_672, 233_057_792),
])
def test_sizes(name, params, kv, block):
    m = dims(name)
    assert flops.block_params(m) == block
    assert flops.param_count(m) == params
    assert flops.param_bytes(m) == 2 * params
    assert flops.kv_bytes_per_token(m) == kv


def test_published_sizes_in_gigabytes():
    assert flops.param_bytes(dims("stablelm-1.6b")) / 1e9 == pytest.approx(3.29, abs=0.005)
    assert flops.param_bytes(dims("qwen2-7b-half")) / 1e9 == pytest.approx(8.71, abs=0.005)


def test_prefill_and_decode_work():
    m = dims("stablelm-1.6b")
    # 2 x 1.23e9 block parameters x 1024 tokens, plus attention and the head
    assert flops.prefill_flops(m, 1024) / 1e12 == pytest.approx(2.6, abs=0.1)
    # one decode row: 2 x (block matmuls + head) + attention over its context
    one = flops.decode_step_flops(m, [511])
    assert one == 2 * (24 * flops.block_matmul_params(m) + 2048 * 100352) \
        + 4 * 24 * 32 * 64 * 512
    # bytes: every block, the head, one embedding row, and the row's keys and
    # values (511 read, one written)
    b = flops.decode_step_bytes(m, [511])
    assert b == (24 * flops.block_params(m) + 2048 + 2048 * 100352 + 2048) * 2 \
        + 196_608 * 512
    assert flops.decode_step_bytes(m, [511, 100]) - b == 2048 * 2 + 196_608 * 101


def test_roofline_and_peaks():
    peak = flops.peaks("TPU v5 lite")
    assert peak == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
