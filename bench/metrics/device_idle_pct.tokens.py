"""``device_idle_pct`` read in the cells judged on ``output_tokens_per_s``."""
from harness import spec

compute = spec.metric_function("device_idle_pct")
