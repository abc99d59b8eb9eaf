"""``compiles_in_window`` read in the cells judged on ``output_tokens_per_s``."""
from harness import spec

compute = spec.metric_function("compiles_in_window")
