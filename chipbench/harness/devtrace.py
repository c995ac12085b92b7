"""Reduce a JAX profiler trace to device busy time, kernel time and
labelled idle gaps, and count the bytes a kernel launch streams.

The device planes are ``/device:<PLATFORM>:<n>``; their ``XLA Ops`` line
holds one event per operation that ran, with its HLO text as the name.
Host annotations (``jax.profiler.TraceAnnotation``) sit on the host
plane's ``python`` line, on the same clock. The window is the host
annotation named :data:`WINDOW`.
"""

from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceTrace:
    """Device operation intervals (ns) per chip inside the window, and
    the harness's host annotations."""

    window: tuple[float, float]
    ops: list[list[tuple[str, float, float]]]  # per chip: (name, start, end)
    labels: list[tuple[str, float, float]]  # host annotations in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, chip: int) -> np.ndarray:
        """Union of the chip's operation intervals, clipped to the window."""
        w0, w1 = self.window
        iv = sorted((max(s, w0), min(e, w1)) for _, s, e in self.ops[chip] if e > w0 and s < w1)
        merged: list[list[float]] = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return np.array(merged, np.float64).reshape(-1, 2)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [float(np.sum(b[:, 1] - b[:, 0])) for b in map(self.busy_intervals, range(len(self.ops)))]
        return float(np.mean(per_chip)) * 1e-9 if per_chip else 0.0

    def op_events(self, match) -> list[tuple[str, float]]:
        """``(name, seconds)`` of each operation inside the window whose
        name ``match`` accepts, over all chips."""
        w0, w1 = self.window
        return [
            (name, (e - s) * 1e-9)
            for chip_ops in self.ops
            for name, s, e in chip_ops
            if s >= w0 and e <= w1 and match(name)
        ]

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, float] = {}
        w0, w1 = self.window
        for chip_ops in self.ops:
            for name, s, e in chip_ops:
                if e > w0 and s < w1:
                    key = op_label(name)
                    tot[key] = tot.get(key, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of chip 0 inside the window, each
        named by the innermost host annotation covering its middle."""
        if not self.ops:
            return []
        w0, w1 = self.window
        busy = self.busy_intervals(0)
        edges = np.concatenate([[w0], busy.ravel(), [w1]]).reshape(-1, 2)
        gaps = [(float(s), float(e)) for s, e in edges if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            covering = [lab for lab in self.labels if lab[1] <= mid <= lab[2]]
            name = min(covering, key=lambda lab: lab[2] - lab[1])[0] if covering else "none"
            out.append([name, (e - s) * 1e-9])
        return out


def op_label(hlo_text: str) -> str:
    """``%name = ...`` HLO text to ``name`` without its ``.N`` suffix."""
    head = hlo_text.split("=", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(trace_dir: str, platform: str = "TPU") -> DeviceTrace:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: list[list[tuple[str, float, float]]] = []
    labels: list[tuple[str, float, float]] = []
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith(f"/device:{platform}:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    labels.append((ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
    for plane in sorted(device_planes, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        chip_ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                chip_ops.extend(
                    (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                )
        ops.append(chip_ops)
    windows = [lab for lab in labels if lab[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found {len(windows)}")
    window = (windows[0][1], windows[0][2])
    ours = [lab for lab in labels if lab[0].startswith(("chipbench.", "client.")) and lab[2] > window[0] and lab[1] < window[1]]
    return DeviceTrace(window, ops, ours)


def fused_scan_bytes(shape: dict, n_queries_padded: int) -> int:
    """HBM bytes one launch of the fused locate+scan kernel moves: every
    row block of the padded resident key lanes and value tile, read once,
    plus the per-query bound operands (4 x (Q, 8) lanes, limits and the
    value selector) and the two (Q, 128) outputs."""
    keys = int(np.prod(shape["keys"])) * shape["keys_itemsize"]
    values = int(np.prod(shape["values"])) * shape["values_itemsize"]
    k_pad = max(8, -(-shape["keys"][0] // 8) * 8)
    per_query = 4 * (4 * k_pad + 2 + 1) + 2 * 4 * 128
    return keys + values + n_queries_padded * per_query
