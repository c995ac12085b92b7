"""``frontdoor.self_us_per_read.r80``'s reading, in the closed-loop cell, where it moves
``read_qps``."""

from harness import spec

read = spec.load_metric_reader("frontdoor.self_us_per_read.r80")
