"""``device.idle_in_engine_pct.r80``'s reading, in the closed-loop cell, where it moves
``read_qps``."""

from harness import spec

read = spec.load_metric_reader("device.idle_in_engine_pct.r80")
