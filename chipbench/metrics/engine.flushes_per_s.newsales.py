"""Replica flushes per second: what the engine's ``memtable_flushes``
counter gained over the window, over the traced window's length."""


def read(run):
    flushes = run.counters.get("engine.memtable_flushes")
    if not flushes or run.trace is None or run.trace.window_s <= 0:
        return None
    return flushes / run.trace.window_s
