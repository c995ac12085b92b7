"""The program's own spans in a profiler trace, and the time a set of
host annotations covers with or without the device's busy time.

A ``repro.obs`` tracer on the wall clock writes each span it times into
the profiler's host trace (``repro.obs.trace``, "Profiler clock"), on
the clock of the device's operations. The names of the read path's
spans start with one of :data:`PROGRAM`; the front door's own spans run
on a virtual clock and never reach the trace. Intervals are
``(start, end)`` in ns, as :mod:`harness.devtrace` keeps them.
"""

from __future__ import annotations

import glob
import os

import numpy as np

PROGRAM = ("frontdoor.", "engine.", "view.", "kernel.")
SERVE = "client.serve"


def load(trace_dir: str, window: tuple[float, float]) -> list[tuple[str, float, float]]:
    """The program's host annotations that overlap ``window``:
    ``(name, start, end)`` in ns, ordered by start."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    w0, w1 = window
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name.startswith(PROGRAM) and e > w0 and s < w1:
                    out.append((ev.name, s, e))
    return sorted(out, key=lambda a: a[1])


def intervals(annotations, name: str) -> list[tuple[float, float]]:
    """``(start, end)`` of each annotation called ``name``."""
    return [(s, e) for n, s, e in annotations if n == name]


def merge(pairs) -> np.ndarray:
    """The union of ``(start, end)`` pairs as sorted disjoint rows."""
    merged: list[list[float]] = []
    for s, e in sorted((float(s), float(e)) for s, e in pairs):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return np.array(merged, np.float64).reshape(-1, 2)


def overlap_ns(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two :func:`merge` results."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def covered_s(pairs, minus=()) -> float:
    """Seconds that the union of ``pairs`` covers outside the union of
    ``minus`` (device busy intervals, say, or inner annotations)."""
    a = merge(pairs)
    return (float(np.sum(a[:, 1] - a[:, 0])) - overlap_ns(a, merge(minus))) * 1e-9


def idle_in_s(trace, pairs) -> float:
    """Seconds inside the union of ``pairs`` in which no operation runs
    on the device, averaged over the chips."""
    per_chip = [covered_s(pairs, trace.busy_intervals(c)) for c in range(len(trace.ops))]
    return float(np.mean(per_chip)) if per_chip else covered_s(pairs)


def idle_gaps_at(trace, k: int = 10) -> list[tuple[float, float]]:
    """``(start, end)`` of chip 0's ``k`` longest idle gaps in the
    window, longest first: the gaps ``DeviceTrace.idle_gaps`` names, in
    its order."""
    if not trace.ops:
        return []
    w0, w1 = trace.window
    edges = np.concatenate([[w0], trace.busy_intervals(0).ravel(), [w1]]).reshape(-1, 2)
    gaps = [(float(s), float(e)) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:k]


def chain(program, labels, t: float) -> str:
    """The program spans covering time ``t``, innermost first; where
    none does, ``front door`` inside a ``client.serve`` call, else the
    innermost harness annotation, else ``none``."""
    covering = sorted((a for a in program if a[1] <= t <= a[2]), key=lambda a: a[2] - a[1])
    if covering:
        return " < ".join(a[0] for a in covering)
    outer = sorted((a for a in labels if a[1] <= t <= a[2]), key=lambda a: a[2] - a[1])
    if not outer:
        return "none"
    return "front door" if outer[0][0] == SERVE else outer[0][0]


def front_door_s(trace, stages) -> float | None:
    """Seconds of the window's ``client.serve`` calls spent outside
    ``engine.read_many``: the annotations' total less the program's
    ``engine.read_many`` span total, since the front door calls
    ``read_many`` only from inside ``client.serve``. None without a
    trace, a ``client.serve`` call or a ``read_many`` span."""
    engine = (stages or {}).get("engine.read_many")
    if trace is None or not engine or not engine["count"]:
        return None
    serve = intervals(trace.labels, SERVE)
    if not serve:
        return None
    return covered_s(serve) - engine["total"]
