"""``decode_rows_mean`` read in the cells judged on ``itl_p95_ms``."""
from harness import spec

compute = spec.metric_function("decode_rows_mean")
