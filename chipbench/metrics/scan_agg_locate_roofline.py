"""Fused locate+scan kernel's share of its roofline, in %.

The kernel does no matrix work, so its least time is its HBM bytes over
the chip's HBM bandwidth. Each launch in the trace (an ``XLA Ops`` event
of the ``_fused_call`` custom call) streams the replica's padded
resident key lanes and value tile once, plus its per-query operands
(``devtrace.fused_scan_bytes``; the query count is read from the
launch's (Q, 128) output shape). Summed over the launches and divided
by their summed device time."""

import re

from harness import devtrace

_LAUNCH = re.compile(r"^%_fused_call(\.\d+)? = \(f32\[(\d+),128\]")


def read(run):
    if run.trace is None or not run.shapes:
        return None
    shape = run.shapes[0]
    if any(s["keys"] != shape["keys"] or s["values"] != shape["values"] for s in run.shapes):
        return None  # launches cannot be told apart by replica
    launches = run.trace.op_events(lambda name: _LAUNCH.match(name) and "custom-call" in name)
    seconds = sum(t for _, t in launches)
    if seconds <= 0:
        return None
    total_bytes = sum(
        devtrace.fused_scan_bytes(shape, int(_LAUNCH.match(name).group(2))) for name, _ in launches
    )
    return 100.0 * total_bytes / run.peaks["hbm_bytes_per_s"] / seconds
