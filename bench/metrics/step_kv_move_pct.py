"""Share of the decode step program's device time (``jit__step_impl``) in
operations outside its ``model_decode`` scope, in the traced part of the
window: the page gather (``kv_gather``), the write-back (``kv_scatter``) and
the copies XLA adds around them.  Read from each operation's scope path in
the device trace; nothing where the program's operations carry no scopes."""
from harness import spans


def compute(run):
    pt = spans.for_run(run)
    share = spans.kv_move_share(pt) if pt is not None else None
    return None if share is None else share * 100.0
