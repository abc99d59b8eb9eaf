"""What decides ``correct``: served tokens against the float32 reference.

Once the window has closed, a sample of the finished requests, drawn from the
seed, goes through the reference: the longest finished request, one from each
engine that finished any, then others until ``TARGET_TOKENS`` served tokens.
The reference runs over each prompt with its served tokens; the number compared
is the widest gap by which a served token's logit lies below the reference's
best at its position (every served token is greedy).  The first served token
comes from the admission's prefill, the rest from decode steps through the
paged cache.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .driver import Sent
from .reference import served_gaps

#: served tokens the sample aims for ("some hundreds")
TARGET_TOKENS = 400
#: and the most requests it takes, which bounds the reference's time
MAX_REQUESTS = 24


def sample(finished: List[Sent], seed: int) -> List[Sent]:
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 7])
    pool = sorted(finished, key=lambda s: s.req.rid)
    longest = max(pool, key=lambda s: (len(s.req.output),
                                       len(s.req.prompt), -s.req.rid))
    picked = [longest]
    for k in sorted({s.engine for s in pool if s.engine is not None}):
        if not any(s.engine == k for s in picked):
            choices = [s for s in pool if s.engine == k]
            picked.append(choices[int(rng.integers(len(choices)))])
    rest = [s for s in pool if s not in picked]
    for i in rng.permutation(len(rest)):
        if sum(len(s.req.output) for s in picked) >= TARGET_TOKENS \
                or len(picked) >= MAX_REQUESTS:
            break
        picked.append(rest[i])
    return picked


def compare(m: Dict, weights, picked: List[Sent], control: bool = False
            ) -> Tuple[Optional[float], Optional[float], int]:
    """``(widest program gap, widest control gap, tokens compared)``."""
    if not picked:
        return None, None, 0
    samples = [(np.asarray(s.req.prompt, np.int32), list(s.req.output))
               for s in picked]
    prog, ctrl = served_gaps(m, weights, samples, control=control)
    return (float(prog.max()), None if ctrl is None else float(ctrl.max()),
            int(prog.size))
