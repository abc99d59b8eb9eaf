"""Wall milliseconds inside ``Orchestrator.step`` per round, over the
window's rounds before any trace starts (each round decodes every engine
once and admits into the slots it frees)."""


def compute(run):
    rounds = run.window_rounds(run.host_close)
    if not rounds:
        return None
    return sum(r.t1 - r.t0 for r in rounds) / len(rounds) * 1e3
