"""Share of its roofline the decode step program (``jit__step_impl``: page
gather, model decode step, write-back) reaches in the traced part of the
window: the least time for the step's work (weights plus the live keys and
values of the real rows, each read once; operations from shapes) over its
device time.  The dense copy of the pages is not work."""
from harness import flops


def compute(run):
    device, runs = run.program_seconds("jit__step_impl")
    steps = [ctx for r in run.traced_rounds() for _, ctx in r.rows]
    if runs == 0 or device <= 0:
        return None
    least = sum(flops.least_seconds(flops.decode_step_flops(run.m, c),
                                    flops.decode_step_bytes(run.m, c),
                                    run.peak) for c in steps)
    return least / device * 100.0
