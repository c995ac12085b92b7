"""The client loop: one thread that hands each batch of due reads to the
program's ``FrontDoor.serve`` and times every read on the host clock.

Open loop: a read is due at its arrival time; the loop takes every read
that is due, up to ``max_batch``, and times each from its due time to
the moment the loop holds its ``Response``. Closed loop: ``clients``
clients each send their next read when their reply lands, as
``cassandra-stress -rate threads=N`` does; a read is timed from when its
client sent it. Every read is waited for. The loop labels its phases
with profiler annotations, which name the device's idle gaps in a trace.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

SPIN_S = 1e-3  # sleep until this close to a due time, then spin


OK, REFUSED, MISSING = 0, 1, 2


class Window:
    """What the window produced, per read: when it was sent, its latency
    (NaN where no response came), and its answer kept as plain arrays:
    status (:data:`OK`, :data:`REFUSED` or :data:`MISSING`), value,
    matched count, serving replica, and a select's row indices. A client
    drops each ``Response`` once it has read it; keeping them would grow
    the heap the collector walks all through the window."""

    def __init__(self, n: int):
        self.sent_s = np.zeros(n)
        self.latency_s = np.full(n, np.nan)
        self.status = np.full(n, MISSING, np.int8)
        self.value = np.zeros(n)
        self.matched = np.zeros(n, np.int64)
        self.replica = np.full(n, -1, np.int64)
        self.selected: list = [None] * n
        self.n = n
        self.length_s = 0.0
        self.batches = 0
        self.lateness_s = np.zeros(0)
        self.t0 = 0.0  # the clock's reading at the window's start
        self.batch_log: list[tuple[float, float, int]] = []  # (start, seconds, reads)

    def served(self, start: float, done: float, size: int) -> None:
        self.batches += 1
        self.batch_log.append((start, done - start, size))

    def slowest(self, k: int) -> list[tuple[float, float, int]]:
        """The ``k`` slowest ``FrontDoor.serve`` calls: (start in the
        window, seconds, reads), slowest first."""
        return sorted(self.batch_log, key=lambda b: -b[1])[:k]

    def record(self, k: int, r, sent: float, done: float) -> None:
        self.sent_s[k] = sent
        if r is None or (r.ok and r.result is None):
            return
        self.latency_s[k] = done - sent
        if not r.ok:
            self.status[k] = REFUSED
            return
        self.status[k] = OK
        self.value[k] = r.result.value
        self.matched[k] = r.result.rows_matched
        self.replica[k] = r.report.replica_id
        if r.result.selected is not None:
            self.selected[k] = np.asarray(r.result.selected, np.int64)

    _ARRAYS = ("sent_s", "latency_s", "status", "value", "matched", "replica")

    def resize(self, n: int) -> None:
        """Room for exactly ``n`` reads (the closed loop learns its count
        as it goes); new reads start as never answered."""
        fresh = Window(n)
        keep = min(n, self.n)
        for name in self._ARRAYS:
            getattr(fresh, name)[:keep] = getattr(self, name)[:keep]
            setattr(self, name, getattr(fresh, name))
        self.selected = self.selected[:keep] + fresh.selected[keep:]
        self.n = n


def settle() -> None:
    """End of set-up: collect, then move every object set-up made (the
    engine's state, the harness's query pool and data) out of the
    collector's reach, as a server does before it serves. Without it a
    full collection in the window re-scans the harness's pool of reads,
    which a deployment's server does not hold."""
    gc.collect()
    gc.freeze()


class GcPauses:
    """Collector pauses while in use: ``(generation, seconds)`` each."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t0 = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        return False

    def summary(self) -> str:
        secs = [s for _, s in self.pauses]
        full = sum(1 for g, _ in self.pauses if g == 2)
        return (f"{len(secs)} collector pauses ({full} full), total {sum(secs) * 1e3:.1f} ms, "
                f"longest {max(secs, default=0.0) * 1e3:.1f} ms")


def _annotate(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def make_frontdoor(engine, max_batch: int, tracer=None):
    """A front door whose admission guards and degradation ladder never
    engage: no token bucket, no bulkheads, a queue of one batch and no
    priority shed. The loop itself batches on the wall clock, so the
    front door's own batching window is one nanosecond."""
    from repro.serving.frontdoor import FrontDoor

    return FrontDoor(
        engine, max_batch=max_batch, max_wait=1e-9, max_queue=max_batch,
        shed_fill=1.0, rate=None, bulkhead_inflight=None, tracer=tracer,
    )


def requests(cf: str, queries: list, waited_s, timeout_s: float):
    from repro.serving.frontdoor import Request

    return [
        Request(cf, q, arrival_s=0.0, deadline_s=max(1e-9, timeout_s - w))
        for q, w in zip(queries, waited_s)
    ]


def open_loop(fd, cf: str, queries: list, due_s: np.ndarray, *, max_batch: int,
              timeout_s: float, trace: bool = False, clock=time.perf_counter) -> Window:
    n = len(queries)
    label = _annotate(trace)
    win = Window(n)
    late: list[float] = []
    t0 = win.t0 = clock()
    i = 0
    while i < n:
        now = clock() - t0
        if due_s[i] > now:
            with label("client.idle"):
                if due_s[i] - now > SPIN_S:
                    time.sleep(due_s[i] - now - SPIN_S)
                while clock() - t0 < due_s[i]:
                    pass
            late.append(clock() - t0 - due_s[i])
            continue
        j = i + 1
        while j < n and j - i < max_batch and due_s[j] <= now:
            j += 1
        reqs = requests(cf, queries[i:j], now - due_s[i:j], timeout_s)
        start = clock() - t0
        with label("client.serve"):
            out = fd.serve(reqs)
        done = clock() - t0
        win.served(start, done, j - i)
        for k, r in zip(range(i, j), out):
            win.record(k, r, float(due_s[k]), done)
        i = j
    win.sent_s[:] = due_s
    win.length_s = clock() - t0
    win.lateness_s = np.asarray(late, float)
    return win


def closed_loop(fd, cf: str, queries: list, *, clients: int, seconds: float,
                timeout_s: float, trace: bool = False, clock=time.perf_counter) -> Window:
    """Every client's read completes when the batch holding it does, so
    the clients send together and each batch holds ``clients`` reads.
    The window runs from the first send to the end of the last batch
    sent before ``seconds``. Read ``i`` is ``queries[i % len(queries)]``:
    a system fast enough to exhaust the pool sends it again."""
    label = _annotate(trace)
    pool = len(queries)
    win = Window(pool)
    t0 = win.t0 = clock()
    now = 0.0
    i = 0
    while now < seconds:
        batch = [queries[(i + k) % pool] for k in range(clients)]
        reqs = requests(cf, batch, [0.0] * clients, timeout_s)
        with label("client.serve"):
            out = fd.serve(reqs)
        done = clock() - t0
        win.served(now, done, clients)
        if i + clients > win.n:
            win.resize(2 * win.n)
        for k in range(clients):
            win.record(i + k, out[k] if k < len(out) else None, now, done)
        i += clients
        now = done
    win.resize(i)
    win.length_s = now
    return win
