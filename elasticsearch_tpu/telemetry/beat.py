"""The server's heartbeat: one thread that looks at the process from
outside every request.

A stage times a boundary a request crosses, work and wait alike, and only
while a request is there to cross it. Three things no stage can see are
read here, by ONE daemon thread a process, `telemetry-beat`, at a fixed
period of 10 ms (no setting: 100 wake-ups a second that wait for the
interpreter lock like any other thread; at 20 ms the steady median read
no lower on the chip, `PERF.md` §6, PR 37):

(1) **The wait for the interpreter lock**: histogram `runtime.lock_wait`.
    A beat reads the clock, sleeps the period, reads again. What it
    overslept is the time a thread that became runnable waited until it
    ran Python again: the scheduler's wake-up (tens of microseconds on a
    quiet host) and the interpreter lock. A pool worker pays the same at
    EVERY return from a blocking call (the batcher's future, `np.asarray`
    of a board, `send`); the beat samples it at moments the server does
    not choose. It is not the time a request waited in all, and not the
    time the lock was held.
(2) **A stall and what held the server in it**: counters
    `runtime.stalls`, `runtime.stall_nanos`, and a record. Each beat
    reads what the server already counts: the responses that left
    (`http.responses.worker` + `http.responses.loop`) and the pools'
    `active` + `queued`. A stall OPENS at the beat that finds work in
    flight and no response out for 50 ms, and CLOSES at the beat that
    sees a response leave, or nothing in flight: `runtime.stalls` += 1,
    `runtime.stall_nanos` += its length from the last response seen. A
    beat that is itself 50 ms late with work in flight, and finds that
    a response has left all the same, slept through a stall (a full
    collection, a frozen host: no Python ran while it waited): that
    counts as one, of the beat's lateness. At the beat that opens a
    stall, once, while it is going on, a record is taken (`_record`):
    the pools, the device dispatches in flight, the collector's mark,
    the last beats' lock waits, and every thread's name, subsystem and
    top frames. A beat that is itself late by 50 ms takes the same
    record at once: the thread that held the lock is then still in, or
    just out of, the call (of late beats in a row, the first: one
    record a second at most). The newest 8 stand at `GET _nodes/stats`
    -> `telemetry.stalls`, and each is one line of the node's log: WARN
    where work was in flight, INFO where a late beat found none.
(3) **One clock.** While a `jax.profiler` session is on, a beat's sleep
    is an event `es.runtime.beat` of the host plane that carries the
    `time.monotonic_ns()` reading of its start as the stat `mono_ns`:
    one anchor every 10 ms between the clock of every `stage_done` wait,
    every span of `GET _nodes/traces` and every stall record, and the
    clock of the device's `XLA Ops` line (`anchor_offset_ns`). The
    event's length less the period is that beat's lock wait.

JAX is never imported from here, and nothing here runs on a request's
path or on the asyncio loop's thread.
"""

from __future__ import annotations

import collections
import logging
import sys
import threading
import time
import weakref
from typing import Iterable, List, Optional, Tuple

from elasticsearch_tpu.monitor import hot_threads
from elasticsearch_tpu.telemetry import metrics, stages

logger = logging.getLogger("elasticsearch_tpu.telemetry")

THREAD_NAME = "telemetry-beat"
PERIOD_NS = 10_000_000      # a beat's sleep
STALL_NS = 50_000_000       # no response for so long, or a beat so late
RECORDS = 8                 # the newest records kept
RECORD_GAP_NS = 1_000_000_000   # late beats in a row: one record of them
RECORD_THREADS = 32         # a record's bounds
RECORD_FRAMES = 3
RECORD_WAITS = 8

LOCK_WAIT = "runtime.lock_wait"
STALLS = "runtime.stalls"
STALL_NANOS = "runtime.stall_nanos"
# the HTTP front's own counters (`rest/http_server.py`), read, never written
RESPONSES = ("http.responses.worker", "http.responses.loop")
INFLIGHT_DISPATCHES = "serving.inflight_dispatches"


class Beat:
    """The heartbeat's state. `BEAT` is the process's; a test makes its
    own, hands `tick` the clock readings it wants and never starts the
    thread."""

    def __init__(self) -> None:
        self._pools: "weakref.WeakSet" = weakref.WeakSet()
        self._lock = threading.Lock()       # the start, and the ring
        self._thread: Optional[threading.Thread] = None
        self._waits: "collections.deque[int]" = collections.deque(
            maxlen=RECORD_WAITS)
        self._records: "collections.deque[dict]" = collections.deque(
            maxlen=RECORDS)
        self._responses = -1        # the count at the last response seen
        self._last_out_ns = 0       # that beat's clock reading
        self._open: Optional[dict] = None   # the open stall's record
        self._record_ns = 0         # when the newest record was taken

    # -- wiring ----------------------------------------------------------
    def watch(self, thread_pool) -> None:
        """Count this node's pools as work in flight (held weakly: a node
        that is gone takes its pools along) and see that the thread
        runs. The counters exist from here on, so that one which never
        moved reads 0."""
        self._pools.add(thread_pool)
        for name in (STALLS, STALL_NANOS, stages.HANDLE_CPU_NANOS):
            metrics.counter(name)
        metrics.histogram(LOCK_WAIT)
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=THREAD_NAME, daemon=True)
                self._thread.start()

    def _run(self) -> None:
        period_s = PERIOD_NS / 1e9
        while True:
            start_ns = time.monotonic_ns()
            ann = stages._annotation("runtime.beat", mono_ns=start_ns)
            time.sleep(period_s)
            end_ns = time.monotonic_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            try:
                self.tick(start_ns, end_ns)
            except Exception:       # the one thread must outlive a fault
                logger.exception("telemetry beat failed")

    # -- one beat --------------------------------------------------------
    def tick(self, start_ns: int, end_ns: int) -> None:
        """What one beat does once its sleep from `start_ns` has ended
        at `end_ns` (`time.monotonic_ns()` readings)."""
        wait = max(0, end_ns - start_ns - PERIOD_NS)
        metrics.record(LOCK_WAIT, wait)
        self._waits.append(wait)
        responses = sum(metrics.counter(name).value for name in RESPONSES)
        pools = self._executors()
        in_flight = sum(p.active + p.queued for p in pools)
        late = wait >= STALL_NS
        opened, slept_through = False, 0
        if responses != self._responses or not in_flight:
            # a response left, or there is nothing to wait for
            if self._open is not None:
                self._stalled(end_ns - self._last_out_ns, self._open)
                self._open = None
            elif late and in_flight:
                # no Python ran while this beat waited, so nothing left
                # either: a stall the beat slept through, though a
                # response went out before it could look
                slept_through = wait
            self._responses, self._last_out_ns = responses, end_ns
        elif self._open is None and end_ns - self._last_out_ns >= STALL_NS:
            opened = True
        record = None
        if opened or (late and end_ns - self._record_ns >= RECORD_GAP_NS):
            self._record_ns = end_ns
            record = self._record(end_ns, wait, in_flight, pools)
            if opened:
                self._open = record
            with self._lock:
                self._records.append(record)
            # a late beat that found nothing in flight kept nobody waiting
            logger.log(logging.WARNING if in_flight else logging.INFO,
                       "%s", log_line(record))
        if slept_through:
            self._stalled(slept_through, record)

    @staticmethod
    def _stalled(nanos: int, record: Optional[dict]) -> None:
        """A stall has ended: count it, and say on its record how long
        it was in the end."""
        metrics.counter(STALLS).inc()
        metrics.counter(STALL_NANOS).inc(nanos)
        if record is not None:
            record["stall_nanos"] = nanos

    def _executors(self) -> list:
        """Every executor a watched node has spun up."""
        return [pool for tp in list(self._pools)
                for pool in list(tp._pools.values())]

    def _record(self, now_ns: int, wait: int, in_flight: int,
                pools: Iterable) -> dict:
        """What held the server, as far as a look from outside tells.
        `stall_nanos` is written when the stall closes."""
        gc_start, gc_end = stages.gc_mark()
        frames = sys._current_frames()
        threads = [(_parked(frames[t.ident]), t.name, frames[t.ident])
                   for t in threading.enumerate()
                   if t.ident in frames and t.name != THREAD_NAME]
        # a pool's worker that waits for work says nothing: it goes last,
        # and first when the bound cuts
        threads.sort(key=lambda item: item[:2])
        return {
            "cause": "late_beat" if wait >= STALL_NS else "no_response",
            "start_ns": self._last_out_ns,      # the last response seen
            "at_ns": now_ns,
            "in_flight": in_flight,
            "pools": {p.name: {"active": p.active, "queued": p.queued}
                      for p in pools if p.active or p.queued},
            "inflight_dispatches":
                metrics.gauge(INFLIGHT_DISPATCHES).value,
            "gc": {"running": gc_start > gc_end,
                   "last_start_ns": gc_start,
                   "last_nanos": max(0, gc_end - gc_start)},
            "lock_wait_nanos": list(self._waits),
            "threads_total": len(threads),
            "threads": [
                {"name": name, "subsystem": hot_threads.subsystem_of(name),
                 "frames": hot_threads.frame_keys(frame, RECORD_FRAMES)}
                for _, name, frame in threads[:RECORD_THREADS]],
        }

    # -- readers ---------------------------------------------------------
    def snapshot(self) -> dict:
        """`GET _nodes/stats` -> `telemetry.stalls`: the two counters and
        the newest records, newest first."""
        with self._lock:
            records = [dict(r) for r in reversed(self._records)]
        return {"count": metrics.counter(STALLS).value,
                "nanos": metrics.counter(STALL_NANOS).value,
                "records": records}


def _parked(frame) -> bool:
    """Is this a pool's worker waiting for work (the executor's
    `work_queue.get`)?"""
    code = frame.f_code
    return code.co_name == "_worker" and \
        code.co_filename.endswith("concurrent/futures/thread.py")


def log_line(record: dict) -> str:
    """A record as the one line the node's log gets, in the manner of
    upstream's `JvmGcMonitorService` (`[gc][young] overhead, spent
    [..]`)."""
    gc_ = record["gc"]
    pools = " ".join(f"{name}={p['active']}+{p['queued']}"
                     for name, p in sorted(record["pools"].items()))
    threads = "; ".join(
        f"'{t['name']}' [{t['subsystem']}] " + " < ".join(t["frames"])
        for t in record["threads"])
    return (
        f"[beat][{record['cause']}] beat late by "
        f"[{record['lock_wait_nanos'][-1] / 1e6:.0f}ms], last response "
        f"seen [{(record['at_ns'] - record['start_ns']) / 1e6:.0f}ms] ago, "
        f"[{record['in_flight']}] in flight (active+queued: "
        f"{pools or '-'}), device dispatches "
        f"[{record['inflight_dispatches']:.0f}], gc "
        f"[{'running' if gc_['running'] else 'idle'}, last "
        f"{gc_['last_nanos'] / 1e6:.1f}ms], lock waits ms "
        f"[{' '.join(f'{w / 1e6:.1f}' for w in record['lock_wait_nanos'])}"
        f"] at monotonic [{record['at_ns'] / 1e9:.3f}s], "
        f"{record['threads_total']} threads: {threads}")


def anchor_offset_ns(beats: Iterable[Tuple[float, int]]) -> float:
    """From `es.runtime.beat` events, each as (the event's start on the
    profiler's clock, its `mono_ns` stat), the nanoseconds to ADD to a
    `time.monotonic_ns()` reading to place it on the profiler's clock.
    The stat is read just before the event starts, so an event can only
    start late against it (the thread lost the processor in between):
    the least difference is the true one."""
    diffs: List[float] = [start - mono for start, mono in beats]
    if not diffs:
        raise ValueError("no es.runtime.beat event to anchor on")
    return min(diffs)


BEAT = Beat()
