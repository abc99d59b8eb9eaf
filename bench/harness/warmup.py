"""Warm up the shapes a cell's traffic reaches, through the engines' own path.

Prefill: one admission per prompt-length bucket of the mix (the prefill, its
boundary re-feed and the page write).  Decode: the (row bucket, span) pairs the
mix reaches, most likely first, at most the engine's live-shape limit
(``DECODE_SHAPE_LIMIT``: a further shape clears the engine's jit cache), and a
step at every row count the engine can hold (the host's token read slices the
logits to the real rows).  Each warm-up admits requests of its own and evicts
them again.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

#: Monte Carlo draws per (row bucket, row count) when ranking spans
DRAWS = 2000


def pow2(n: int) -> int:
    return max(1, 1 << (n - 1).bit_length())


def kv_span(n_tokens: int, page: int, max_seq: int) -> int:
    """Positions of the dense view that holds ``n_tokens`` (the program's
    rule: pages rounded up to a power of two, capped at ``max_seq``)."""
    return min(max(page, pow2(max(n_tokens, 1))), max_seq)


def prefill_spans(mix: dict, page: int, max_seq: int) -> List[int]:
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return sorted({kv_span(n, page, max_seq) for n in range(lo, hi + 1)})


def decode_pairs(mix: dict, slots: int, limit: int, page: int, max_seq: int,
                 seed: int = 0) -> List[Tuple[int, int]]:
    """The (row bucket, span) pairs a decode step of this mix reaches, most
    likely first: each bucket's likeliest span, then the rest by likelihood,
    ``limit`` in all.  Rows hold a prompt and part of its output."""
    rng = np.random.default_rng(seed)
    p, o = mix["prompt"], mix["output"]

    def draw(shape):
        k = float(p["shape"])
        pr = np.clip(np.floor(rng.gamma(k, p["mean"] / k, shape)), p["min"], p["max"])
        k = float(o["shape"])
        ou = np.clip(np.floor(rng.gamma(k, o["mean"] / k, shape)), o["min"], o["max"])
        return pr + np.floor(rng.random(shape) * ou)

    prob: Dict[Tuple[int, int], float] = {}
    buckets = sorted({pow2(r) for r in range(1, slots + 1)})
    for nb in buckets:
        counts = range(nb // 2 + 1, min(nb, slots) + 1)
        for rows in counts:
            ctx = draw((DRAWS, rows)).max(axis=1)
            for c in ctx:
                key = (nb, kv_span(int(c) + 1, page, max_seq))
                prob[key] = prob.get(key, 0.0) + 1.0 / (DRAWS * len(counts))
    first = [max((kv for kv in prob if kv[0] == nb), key=prob.get)
             for nb in buckets]
    rest = sorted((kv for kv in prob if kv not in first), key=lambda kv: -prob[kv])
    return (first + rest)[:limit]


def _admit(engine, rid: int, length: int, vocab: int, rng):
    from repro.serving import Request

    req = Request(rid=rid, prompt=rng.integers(1, vocab, length, dtype=np.int32),
                  max_new_tokens=1 << 20)
    if not engine.admit(req, 0.0):
        raise RuntimeError(f"warm-up could not admit a {length}-token prompt")
    return req


def warm_engine(engine, spans: List[int], pairs: List[Tuple[int, int]],
                slots: int, page: int, vocab: int, filler: int) -> None:
    """``filler``: prompt length of the rows that only fill a row bucket
    (the mix's shortest prompt, so its prefill bucket is one the mix uses)."""
    rng = np.random.default_rng(0)
    rid = -1
    for s in spans:                          # prefill buckets
        _admit(engine, rid, max(1, s - 1), vocab, rng)
        engine.evict_all()
    by_bucket = {}
    for nb, span in pairs:
        by_bucket.setdefault(nb, span)
    plan = [(nb // 2 + 1, span) for nb, span in pairs]
    covered = {rows for rows, _ in plan}
    plan += [(rows, by_bucket[pow2(rows)]) for rows in range(1, slots + 1)
             if rows not in covered and pow2(rows) in by_bucket]
    for rows, span in plan:                  # decode shapes and row counts
        _admit(engine, rid, span // 2 if span > page else page // 2, vocab, rng)
        for _ in range(rows - 1):
            _admit(engine, rid, filler, vocab, rng)
        engine.step(0.0)
        engine.evict_all()
