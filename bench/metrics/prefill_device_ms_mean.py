"""Device milliseconds per admission in the traced part of the window: the
prefill programs (``jit_prefill``) and the boundary re-feed
(``jit_decode_step``), over the prefills traced."""


def compute(run):
    prefill, runs = run.program_seconds("jit_prefill")
    refeed, _ = run.program_seconds("jit_decode_step")
    if runs == 0:
        return None
    return (prefill + refeed) / runs * 1e3
