"""The one general load generator: a traffic file's parameters in, the
window's client-side sample out.

Closed loop: `clients` threads, each sending its next request when its last
returns, until the window closes. Open loop: arrivals on a schedule fixed
before the window, whatever the server does; a request waits in the
generator's own queue when all `clients` connections are busy, and its
latency counts from when it was DUE.

Every seed gets the same multiset of gaps, burst sizes and request shapes in
another order, so that seeds change the order of the work and not its
amount. All threads live in this one process; each owns one keep-alive
connection. Bodies are serialised before the window.
"""

from __future__ import annotations

import http.client
import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from benchmark.child import Connection, RunFailure

LATE_ANSWER_S = 60.0      # an answer that comes late is late, not wrong
GATE_S = 120.0            # every client connected by then, or the run fails


@dataclass
class Item:
    """One request, ready to send."""
    index: int
    method: str
    path: str
    body: bytes
    weight: float = 1.0


@dataclass
class Sample:
    """What the generator saw, one entry a request, in send order."""
    t0: float = 0.0
    seconds: float = 0.0
    index: List[int] = field(default_factory=list)
    weight: List[float] = field(default_factory=list)
    due: List[float] = field(default_factory=list)
    sent: List[float] = field(default_factory=list)
    done: List[Optional[float]] = field(default_factory=list)
    status: List[int] = field(default_factory=list)
    raw: List[bytes] = field(default_factory=list)

    def add(self, item: Item, due: float, sent: float,
            done: Optional[float], status: int, raw: bytes) -> None:
        self.index.append(item.index)
        self.weight.append(item.weight)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(done)
        self.status.append(status)
        self.raw.append(raw)


def arrival_offsets(rate_per_s: float, seconds: float, seed: int,
                    burst: Optional[dict] = None) -> np.ndarray:
    """Seconds after the window opens at which each request is due.

    Poisson arrivals as a FIXED multiset of exponential gaps (the
    distribution's quantiles), shuffled by the seed. `burst`, if given,
    adds every `every_s` seconds a burst whose sizes are spread evenly over
    `size` = [low, high], in an order drawn from the seed."""
    rng = np.random.default_rng([int(seed), 3])
    n = int(round(rate_per_s * seconds))
    offsets = np.zeros(0)
    if n > 0:
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate_per_s
        rng.shuffle(gaps)
        offsets = np.cumsum(gaps)
        offsets *= seconds * n / (n + 0.5) / offsets[-1]
    if burst:
        times = np.arange(float(burst["every_s"]), seconds,
                          float(burst["every_s"]))
        low, high = burst["size"]
        sizes = np.linspace(low, high, num=len(times)).round().astype(int)
        rng.shuffle(sizes)
        offsets = np.concatenate(
            [offsets] + [np.full(s, t) for t, s in zip(times, sizes)])
    return np.sort(offsets)


def _send(conn: Connection, item: Item):
    try:
        status, raw = conn.request(item.method, item.path, item.body)
        return time.monotonic(), status, raw
    except (OSError, http.client.HTTPException) as e:
        conn.close()
        return None, 0, f"{type(e).__name__}: {e}".encode()


def closed_loop(port: int, clients: int, seconds: float,
                next_item: Callable[[], Item],
                at: Sequence = ()) -> Sample:
    """`at`: (offset_s, callable) pairs run on a thread of their own at
    that offset into the window (the trace switch)."""
    sample = Sample(seconds=seconds)
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1, timeout=GATE_S)
    go = threading.Event()

    def client():
        conn = Connection(port)
        try:
            # connect before the window opens
            if not _connect(conn, barrier):
                return
            go.wait()
            time.sleep(max(0.0, sample.t0 - time.monotonic()))
            t_end = sample.t0 + seconds
            while True:
                now = time.monotonic()
                if now >= t_end:
                    return
                item = next_item()
                done, status, raw = _send(conn, item)
                with lock:
                    sample.add(item, now, now, done, status, raw)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    _open_gate(barrier)
    sample.t0 = time.monotonic() + 0.05
    go.set()
    timers = _timers(sample.t0, at)
    _join(threads + timers, sample.t0 + seconds + LATE_ANSWER_S)
    return sample


def open_loop(port: int, clients: int, seconds: float,
              offsets: np.ndarray, items: Sequence[Item],
              at: Sequence = ()) -> Sample:
    sample = Sample(seconds=seconds)
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1, timeout=GATE_S)
    todo: "queue.Queue" = queue.Queue()

    def worker():
        conn = Connection(port)
        try:
            if not _connect(conn, barrier):
                return
            while True:
                got = todo.get()
                if got is None:
                    return
                item, due = got
                sent = time.monotonic()
                done, status, raw = _send(conn, item)
                with lock:
                    sample.add(item, due, sent, done, status, raw)
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    _open_gate(barrier)
    sample.t0 = time.monotonic() + 0.05
    timers = _timers(sample.t0, at)
    for item, off in zip(items, offsets):
        due = sample.t0 + float(off)
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put((item, due))
    for _ in threads:
        todo.put(None)
    _join(threads + timers, sample.t0 + seconds + LATE_ANSWER_S)
    # what never answered is in no thread's record: it was due all the same
    seen = set(sample.index)
    for item, off in zip(items, offsets):
        if item.index not in seen:
            due = sample.t0 + float(off)
            sample.add(item, due, due, None, 0, b"never answered")
    return sample


def _connect(conn: Connection, barrier: threading.Barrier) -> bool:
    """One client's connection, made before the window opens. A client that
    cannot connect breaks the barrier, so that nobody waits for it."""
    try:
        conn.request("GET", "/")
        barrier.wait()
        return True
    except (OSError, http.client.HTTPException,
            threading.BrokenBarrierError):
        barrier.abort()
        return False


def _open_gate(barrier: threading.Barrier) -> None:
    try:
        barrier.wait()
    except threading.BrokenBarrierError:
        raise RunFailure("a client of the load generator could not connect "
                         f"to the server within {GATE_S:.0f}s")


def _timers(t0: float, at: Sequence) -> list:
    def run(offset, fn):
        time.sleep(max(0.0, t0 + offset - time.monotonic()))
        fn()
    timers = [threading.Thread(target=run, args=(off, fn), daemon=True)
              for off, fn in at]
    for t in timers:
        t.start()
    return timers


def _join(threads: list, deadline: float) -> None:
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))


class ItemSource:
    """Hands the closed loop its next request: from the pool serialised
    before the window, then (a faster program than the pool foresaw) made
    on the spot by the same function."""

    def __init__(self, make: Callable[[int, int], List[Item]], pool: int):
        self._make = make
        self._items = make(0, pool)
        self._next = 0
        self._lock = threading.Lock()

    def __call__(self) -> Item:
        with self._lock:
            i = self._next
            self._next += 1
            if i >= len(self._items):
                self._items.extend(self._make(len(self._items),
                                              max(256, len(self._items) // 4)))
            return self._items[i]


def pool_size(traffic: dict, seconds: float) -> int:
    return int(math.ceil(float(traffic.get("pool_per_s", 1000)) * seconds))
