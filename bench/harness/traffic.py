"""The one traffic generator: a mix file's parameters and a seed in, requests out.

Every seed gets the same set of sizes and gaps, drawn once from the mix's own
``base_seed``; the run's ``--seed`` permutes them inside consecutive pairs
(``SHUFFLE_BLOCK``) and draws the prompt tokens.  So the load over time is the
same for every seed: a seed changes only the order inside each pair and the
prompt tokens, which keeps seed-to-seed spread close to run-to-run spread.

Arrivals are a Poisson process whose rate is a piecewise-constant wave
(``arrivals.phases``: seconds and multiple of the mean rate, repeated; one
phase for steady traffic): unit-rate exponential gaps in
operational time, mapped back through the integrated rate.  That is
``repro.core.workload.phased_poisson``'s process, built so that a fixed set of
gaps can be permuted.  Lengths are gamma draws rounded down and clipped to the
mix's ``[min, max]`` (the arithmetic of ``azure_like_trace_np``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

#: base draws per stream; the first n of them are the same for every n
BASE_DRAWS = 8192
#: a seed permutes sizes and gaps inside consecutive blocks of this many
SHUFFLE_BLOCK = 2


@dataclasses.dataclass
class Traffic:
    due: np.ndarray            # (n,) seconds after traffic starts
    prompt_len: np.ndarray     # (n,) int
    output_len: np.ndarray     # (n,) int
    prompts: List[np.ndarray]  # int32 token ids
    clipped: Dict[str, int]    # draws raised to min / cut to max, per stream


class Wave:
    """A piecewise-constant rate: ``phases`` of ``(seconds, multiple)`` repeat
    every period, and their time-weighted mean multiple is 1."""

    def __init__(self, arrivals: dict, mean_rate: float):
        phases = arrivals.get("phases", [[1.0, 1.0]])
        self.d = np.asarray([p[0] for p in phases], float)
        self.r = np.asarray([p[1] for p in phases], float) * mean_rate
        self.period = float(self.d.sum())
        mean = float((self.d * self.r).sum() / self.period)
        if not np.isclose(mean, mean_rate, rtol=1e-9):
            raise ValueError(f"wave phases average {mean} req/s, not the mean "
                             f"rate {mean_rate}")
        self.t_edges = np.concatenate([[0.0], np.cumsum(self.d)])
        self.l_edges = np.concatenate([[0.0], np.cumsum(self.d * self.r)])
        self.per_period = float(self.l_edges[-1])

    def integral(self, t: float) -> float:
        k, u = divmod(t, self.period)
        i = np.searchsorted(self.t_edges, u, side="right") - 1
        return k * self.per_period + self.l_edges[i] + (u - self.t_edges[i]) * self.r[i]

    def inverse(self, lam: np.ndarray) -> np.ndarray:
        k, u = np.divmod(np.asarray(lam, float), self.per_period)
        i = np.searchsorted(self.l_edges, u, side="right") - 1
        i = np.minimum(i, len(self.r) - 1)
        return k * self.period + self.t_edges[i] + (u - self.l_edges[i]) / self.r[i]


def _lengths(rng, dist: dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Lengths, and which draws were raised to ``min`` or cut to ``max``."""
    shape = float(dist["shape"])
    raw = np.floor(rng.gamma(shape, float(dist["mean"]) / shape, BASE_DRAWS))
    low = raw < dist["min"]
    high = raw > dist["max"]
    out = np.clip(raw, dist["min"], dist["max"]).astype(np.int64)
    return out, low, high


def _block_permutation(rng, n: int, block: int) -> np.ndarray:
    perm = np.arange(n)
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        perm[lo:hi] = lo + rng.permutation(hi - lo)
    return perm


def generate(mix: dict, rate_rps: float, horizon_s: float, seed: int,
             vocab_size: int, max_seq: int) -> Traffic:
    """Requests due in ``[0, horizon_s)`` at mean rate ``rate_rps``."""
    if mix["prompt"]["max"] + mix["output"]["max"] > max_seq:
        raise ValueError(f"mix {mix['name']}: prompt max + output max exceeds "
                         f"max_seq {max_seq}")
    wave = Wave(mix.get("arrivals", {}), rate_rps)
    base = int(mix["base_seed"])
    gaps = np.random.default_rng([base, 0]).exponential(1.0, BASE_DRAWS)
    sizes_rng = np.random.default_rng([base, 1])
    plen, p_low, p_high = _lengths(sizes_rng, mix["prompt"])
    olen, o_low, o_high = _lengths(sizes_rng, mix["output"])

    total = wave.integral(horizon_s)
    n = int(np.searchsorted(np.cumsum(gaps), total))
    if n >= BASE_DRAWS:
        raise ValueError(f"{n} requests exceed the {BASE_DRAWS} base draws")
    run = np.random.default_rng(seed)
    op = np.cumsum(gaps[:n][_block_permutation(run, n, SHUFFLE_BLOCK)])
    keep = op < total
    due = wave.inverse(op[keep])
    n = len(due)
    order = _block_permutation(run, n, SHUFFLE_BLOCK)
    prompt_len = plen[:n][order]
    output_len = olen[:n][order]
    prompts = [run.integers(1, vocab_size, int(m), dtype=np.int32)
               for m in prompt_len]
    clipped = {"prompt_raised": int(p_low[:n].sum()),
               "prompt_cut": int(p_high[:n].sum()),
               "output_raised": int(o_low[:n].sum()),
               "output_cut": int(o_high[:n].sum())}
    return Traffic(due=due, prompt_len=prompt_len, output_len=output_len,
                   prompts=prompts, clipped=clipped)
