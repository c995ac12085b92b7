"""The client loop: one thread that hands each batch of due reads to the
program's ``FrontDoor.serve`` and times every read on the host clock.

Open loop: a read is due at its arrival time; the loop takes every read
that is due, up to ``max_batch``, and times each from its due time to
the moment the loop holds its ``Response``. Closed loop: ``clients``
clients each send their next read when their reply lands, as
``cassandra-stress -rate threads=N`` does; a read is timed from when its
client sent it. Every read is waited for. The loop labels its phases
with profiler annotations, which name the device's idle gaps in a trace.

Where the mix writes (:class:`Writes`), each turn of either loop first
sends every row that has come due, in one call, then its reads; each read
records how many written rows were acknowledged when its batch went
out (its watermark), which is what read-your-writes lets it see.
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
        self.watermark = np.zeros(n, np.int64)
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

    _ARRAYS = ("sent_s", "latency_s", "status", "value", "matched", "replica", "watermark")

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


class Writes:
    """The window's writes. Row ``i`` of ``key_cols`` / ``value_cols`` is
    due at ``due_s[i]`` (ascending) on the window's clock. Each time the
    loop calls :meth:`send_due`, every row due and not yet sent goes out
    as one ``HREngine.write`` call (group commit, no cap): one
    commit-log record, staged on every live replica. A row is timed
    from its due time to the return of that call, which is its
    acknowledgement. A call that raises leaves its rows failed."""

    def __init__(self, engine, cf: str, key_cols: dict, value_cols: dict, due_s: np.ndarray,
                 *, tracer=None, clock=time.perf_counter):
        self.engine, self.cf, self.tracer, self.clock = engine, cf, tracer, clock
        self.key_cols, self.value_cols = key_cols, value_cols
        self.due_s = np.asarray(due_s, float)
        n = self.due_s.size
        self.latency_s = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.errors: list[str] = []
        self.sent = 0  # rows handed to the program, in order
        self.calls = 0
        self.t0 = 0.0

    @property
    def n(self) -> int:
        return int(self.due_s.size)

    @property
    def next_due(self) -> float:
        return float(self.due_s[self.sent]) if self.sent < self.n else np.inf

    def send_due(self, now: float) -> bool:
        """Send every row due by ``now`` (seconds into the window) in one
        call; whether there was any."""
        hi = int(np.searchsorted(self.due_s, now, side="right"))
        if hi <= self.sent:
            return False
        self._send(hi)
        return True

    def send_rest(self) -> None:
        """After the window: send every row not yet sent."""
        if self.sent < self.n:
            self._send(self.n)

    def _send(self, hi: int) -> None:
        lo = self.sent
        kc = {c: v[lo:hi] for c, v in self.key_cols.items()}
        vc = {c: v[lo:hi] for c, v in self.value_cols.items()}
        span = self.tracer.root("client.write", rows=hi - lo) if self.tracer is not None else None
        try:
            self.engine.write(self.cf, kc, vc, trace=span)
        except Exception as e:  # a refused write is a result, not a crash
            self.failed[lo:hi] = True
            self.errors.append(f"{type(e).__name__}: {e}")
        finally:
            if span is not None:
                span.end()
        done = self.clock() - self.t0
        self.latency_s[lo:hi] = done - self.due_s[lo:hi]
        self.sent = hi
        self.calls += 1


def requests(cf: str, queries: list, waited_s, timeout_s: float):
    from repro.serving.frontdoor import Request

    return [
        Request(cf, q, arrival_s=0.0, deadline_s=max(1e-9, timeout_s - w))
        for q, w in zip(queries, waited_s)
    ]


def open_loop(fd, cf: str, queries: list, due_s: np.ndarray, *, max_batch: int,
              timeout_s: float, trace: bool = False, clock=time.perf_counter,
              writes: Writes | None = None) -> Window:
    n = len(queries)
    label = _annotate(trace)
    win = Window(n)
    late: list[float] = []
    t0 = win.t0 = clock()
    if writes is not None:
        writes.t0 = t0
    i = 0
    while i < n:
        now = clock() - t0
        if writes is not None and writes.send_due(now):
            continue
        wake = due_s[i] if writes is None else min(due_s[i], writes.next_due)
        if wake > now:
            with label("client.idle"):
                if wake - now > SPIN_S:
                    time.sleep(wake - now - SPIN_S)
                while clock() - t0 < wake:
                    pass
            late.append(clock() - t0 - wake)
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
        if writes is not None:
            win.watermark[i:j] = writes.sent
        i = j
    win.sent_s[:] = due_s
    win.length_s = clock() - t0
    win.lateness_s = np.asarray(late, float)
    if writes is not None:
        writes.send_rest()
    return win


def closed_loop(fd, cf: str, queries: list, *, clients: int, seconds: float,
                timeout_s: float, trace: bool = False, clock=time.perf_counter,
                writes: Writes | None = None) -> Window:
    """Every client's read completes when the batch holding it does, so
    the clients send together and each batch holds ``clients`` reads.
    The window runs from the first send to the end of the last batch
    sent before ``seconds``. Read ``i`` is ``queries[i % len(queries)]``:
    a system fast enough to exhaust the pool sends it again. Writes due
    go out at the start of each turn, before its reads, and the rest
    once the window has closed."""
    label = _annotate(trace)
    pool = len(queries)
    win = Window(pool)
    t0 = win.t0 = clock()
    if writes is not None:
        writes.t0 = t0
    now = 0.0
    i = 0
    while now < seconds:
        if writes is not None and writes.send_due(clock() - t0):
            now = clock() - t0
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
        if writes is not None:
            win.watermark[i:i + clients] = writes.sent
        i += clients
        now = done
    win.resize(i)
    win.length_s = now
    if writes is not None:
        writes.send_rest()
    return win
