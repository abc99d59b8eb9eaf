"""Rows an engine decodes per step, over the window's engine steps before
any trace starts (an engine with no running request does not step)."""


def compute(run):
    rows = [len(ctx) for r in run.window_rounds(run.host_close) for _, ctx in r.rows]
    return sum(rows) / len(rows) if rows else None
