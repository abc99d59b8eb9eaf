"""``step_kv_move_pct`` read in the cells judged on ``output_tokens_per_s``."""
from harness import spec

compute = spec.metric_function("step_kv_move_pct")
