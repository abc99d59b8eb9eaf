"""``prefill_device_ms_mean`` read in the cells judged on ``output_tokens_per_s``."""
from harness import spec

compute = spec.metric_function("prefill_device_ms_mean")
