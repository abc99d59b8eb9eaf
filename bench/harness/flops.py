"""Operations and bytes the algorithm needs, from a configuration's sizes.

Counted for a dense decoder of SwiGLU blocks with grouped-query attention, in
the dtype it is served in.  These are the work a step must do, not what the
program happens to move: the decode step's dense copy of the paged cache is
not counted, so a program that drops it gets closer to the roofline.
"""
from __future__ import annotations

from typing import Dict, Iterable

#: peaks of one chip, keyed by ``device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"to PEAKS with their source")
    return PEAKS[device_kind]


def _bytes_per(m) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def block_matmul_params(m) -> int:
    """Weights one token multiplies in one block (projections and MLP)."""
    D, H, KV, hd, F = (m[k] for k in ("d_model", "heads", "kv_heads",
                                      "head_dim", "d_ff"))
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def block_params(m) -> int:
    n = block_matmul_params(m) + 2 * m["d_model"]
    if m["qkv_bias"]:
        n += (m["heads"] + 2 * m["kv_heads"]) * m["head_dim"]
    return n


def param_count(m) -> int:
    D, V = m["d_model"], m["vocab"]
    head = 0 if m["tied"] else D * V
    return V * D + head + D + m["layers"] * block_params(m)


def param_bytes(m) -> int:
    return param_count(m) * _bytes_per(m)


def kv_bytes_per_token(m) -> int:
    """Keys and values of one position, all layers."""
    return m["layers"] * 2 * m["kv_heads"] * m["head_dim"] * _bytes_per(m)


def token_flops(m, context: int) -> float:
    """One token through every block and the head, attending to ``context``
    positions (itself included)."""
    L, H, hd, D, V = (m[k] for k in ("layers", "heads", "head_dim", "d_model",
                                     "vocab"))
    matmul = 2 * (L * block_matmul_params(m) + D * V)
    attention = 4 * L * H * hd * context
    return float(matmul + attention)


def decode_step_flops(m, contexts: Iterable[int]) -> float:
    """A decode step over rows whose caches hold ``contexts`` positions before
    the step (each row attends to one more)."""
    return float(sum(token_flops(m, c + 1) for c in contexts))


def decode_weight_bytes(m, rows: int) -> int:
    """Weights a decode step must read: every block, the final norm, the head,
    and one embedding row per decoded row."""
    D, V = m["d_model"], m["vocab"]
    return (m["layers"] * block_params(m) + D + D * V + rows * D) * _bytes_per(m)


def decode_step_bytes(m, contexts: Iterable[int]) -> float:
    """Weights plus the live keys and values of the real rows, each read once,
    plus the one position each row writes."""
    contexts = list(contexts)
    kv = kv_bytes_per_token(m)
    return float(decode_weight_bytes(m, len(contexts))
                 + kv * sum(contexts) + kv * len(contexts))


def prefill_flops(m, tokens: int) -> float:
    """A causal prefill of ``tokens`` positions (the head on the last only)."""
    L, H, hd, D, V = (m[k] for k in ("layers", "heads", "head_dim", "d_model",
                                     "vocab"))
    matmul = 2 * L * block_matmul_params(m) * tokens + 2 * D * V
    attention = 4 * L * H * hd * tokens * (tokens + 1) / 2
    return float(matmul + attention)


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
