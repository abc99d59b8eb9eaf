"""Model operations of the tokens the window's decode steps produced, over
their rounds' wall time times the chip's bf16 peak; rounds before any trace
starts (tracing slows the host)."""
from harness import flops


def compute(run):
    rounds = run.window_rounds(run.host_close)
    wall = sum(r.t1 - r.t0 for r in rounds)
    if wall <= 0:
        return None
    work = sum(flops.decode_step_flops(run.m, ctx)
               for r in rounds for _, ctx in r.rows)
    return work / (wall * run.peak["bf16_flops"]) * 100.0
