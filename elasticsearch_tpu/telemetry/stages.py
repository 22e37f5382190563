"""The one way to time a stage: `stage` and `stage_done`.

A stage is a boundary a request (or a batch of them) crosses inside the
server. One call does, for that boundary, everything the telemetry
package offers:

(a) records the duration in the always-on histogram `name`
    (`_nodes/stats telemetry`; exact `count` and `sum_nanos`);
(b) where the request is sampled, appends a span with its REAL start, its
    end, its parent and the request's trace id (`GET _nodes/traces`);
(c) `stage` only (work that begins and ends on one thread): while a
    `jax.profiler` session is on, the stretch is an event `es.<name>` of
    the host plane, on the clock of the device's `XLA Ops` line. With no
    session that costs one atomic load; JAX is never imported from here.

`stage(name)` is a context manager. `stage_done(name, start_ns, end_ns,
ctx)` is for a wait that begins on one thread and ends on another (queue
wait, pool wait, the loop's wake-up) and for a stretch whose two ends the
caller had to read anyway; both readings are `time.monotonic_ns()`.

Which request a stage belongs to is its context: by default the calling
thread's (`telemetry.use`), or the `ctx` given — what `capture()`
returned on the submitting thread (the batch leader's, for a batch-level
stage).

The HTTP server's five stages of a request (`http.read`,
`http.pool_wait`, `rest.handle`, `http.loop_wake`, `http.respond`) are
read as clock marks on the request's `Front` and filed by `stage_done`
in two goes: on the hot path a mark is one attribute, and the loop's one
thread, which under load is the scarcest thing the server has, files only
what no worker can (the last two of a response it had to write itself).
A sampled request's trace starts with its request line in hand and is
finished after the response: socket to socket.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import threading
import time
from typing import Optional

from elasticsearch_tpu.telemetry import metrics
from elasticsearch_tpu.telemetry.trace import _CTX, TRACER, Trace, _new_id

new_span_id = _new_id

_ANNOTATION = None   # jax.profiler.TraceAnnotation, once JAX is loaded
_SESSION_ON = None   # its `is_enabled`: one atomic load


def _session_on() -> bool:
    """Is a profiler session on? A process that has not imported JAX
    has none (and JAX is never imported from here)."""
    global _ANNOTATION, _SESSION_ON
    on = _SESSION_ON
    if on is None:
        if "jax" not in sys.modules:
            return False
        import jax
        _ANNOTATION = jax.profiler.TraceAnnotation
        on = _SESSION_ON = _ANNOTATION.is_enabled
    return on()


def _annotation(name: str, **stats):
    """An ENTERED profiler annotation `es.<name>`, or None with no
    session on. `stats` ride the event (the reader finds them under
    `event.stats`)."""
    if not _session_on():
        return None
    ann = _ANNOTATION("es." + name, **stats)
    ann.__enter__()
    return ann


_NO_ANNOTATION = contextlib.nullcontext()   # `annotation()`, no session on


def annotation(name: str):
    """`with annotation("http.read"):` — the profiler event `es.<name>`
    alone, for a stretch whose stage is filed later from clock marks
    (the HTTP front's; see `Front`)."""
    return _ANNOTATION("es." + name) if _session_on() else _NO_ANNOTATION


UNSAMPLED = (None, None, None)   # a context that is explicitly no trace

# the handlers' own processor time (`time.thread_time_ns()` around
# `rest.handle`): over that histogram's `sum_nanos`, the share of a
# handler's wall time in which its thread ran; the rest it waited (the
# batcher's future, the device, the interpreter lock)
HANDLE_CPU_NANOS = "rest.handle.cpu_nanos"


class Front:
    """One HTTP request as the server's front sees it: the clock marks
    of its way through the loop and the pool, filed as five stages: those
    known when the handler returns on the worker, there; the last two
    where the response ends (`finish`): on that worker, which sends the
    answer itself, or on the loop where the loop had to write it (TLS, a
    short write, a 429). The server sets the marks as plain attributes;
    `with front:` on the pool's worker marks the handler's start and
    return and puts the front on the thread, where the handler's
    `rest_request` finds it when it samples the request (`adopt`).

        idle_ns    the connection's last response out (or accept)
        start_ns   request line in hand           -> http.read
        read_ns    body complete, query parsed, and the connection's
                   previous response out (a client that pipelines waits
                   here)
        submit_ns  thread_pool.submit             -> http.pool_wait
        handle_ns  the handler's first instruction -> rest.handle
        return_ns  (status, payload) returned     -> http.loop_wake
        wake_ns    the first instruction of responding, on whichever
                   thread responds: microseconds later on the worker,
                   the loop's lag where the loop has to -> http.respond
        (finish)   the bytes handed to the socket whole (worker), or
                   written and drained (loop)

    Inside `rest.handle` the worker's own processor time is read
    (`time.thread_time_ns()`, twice a request) into the counter
    `rest.handle.cpu_nanos`: what is left of the stage is wait.
    """

    __slots__ = ("idle_ns", "start_ns", "read_ns", "submit_ns", "handle_ns",
                 "return_ns", "wake_ns", "trace", "status", "handle_id",
                 "_ann", "_cpu_ns")

    def __init__(self, idle_ns: int, start_ns: int):
        self.idle_ns = idle_ns
        self.start_ns = self.read_ns = self.submit_ns = start_ns
        self.handle_ns = 0          # 0: no worker ever took the request
        self.trace: Optional[Trace] = None
        self.status: Optional[str] = None

    def __enter__(self) -> "Front":
        _CTX.front = self
        self._ann = _annotation("rest.handle")
        self.handle_ns = time.monotonic_ns()
        # the processor time is read INSIDE the wall time it is a share of
        self._cpu_ns = time.thread_time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        cpu_ns = time.thread_time_ns() - self._cpu_ns
        self.return_ns = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _CTX.front = None
        # what is known by now is filed here, on the worker: the loop's
        # one thread serves every connection, and under load it is the
        # scarcest thing the server has
        ctx = self._ctx()
        self._file_read(ctx)
        stage_done("http.pool_wait", self.submit_ns, self.handle_ns, ctx)
        stage_done("rest.handle", self.handle_ns, self.return_ns, ctx,
                   span_id=None if self.trace is None else self.handle_id)
        metrics.counter(HANDLE_CPU_NANOS).inc(cpu_ns)
        return False

    def _ctx(self):
        tr = self.trace
        return UNSAMPLED if tr is None else (tr, tr.root.span_id, None)

    def _file_read(self, ctx) -> None:
        # a connection between two requests precedes the one it ends: a
        # histogram, never a span
        stage_done("http.keepalive_gap", self.idle_ns, self.start_ns,
                   UNSAMPLED)
        stage_done("http.read", self.start_ns, self.read_ns, ctx)

    def adopt(self, trace: Trace) -> str:
        """The handler sampled this request. Returns the id that the
        `rest.handle` span will have, under which the handler's own
        spans hang."""
        self.trace = trace
        self.handle_id = _new_id()
        return self.handle_id

    def finish(self, end_ns: int) -> None:
        """Where the response ended, on whichever thread: file the last
        two stages, finish the request's trace (if any)."""
        ctx = self._ctx()
        if self.handle_ns:
            stage_done("http.loop_wake", self.return_ns, self.wake_ns, ctx)
            stage_done("http.respond", self.wake_ns, end_ns, ctx)
        else:                   # no worker ever took it (429)
            self._file_read(ctx)
            stage_done("http.respond", self.read_ns, end_ns, ctx)
        tr = self.trace
        if tr is not None:
            self.trace = None
            TRACER.finish(tr, status=self.status, end_ns=end_ns)


def stage_done(name: str, start_ns: int, end_ns: int, ctx=None,
               status: str = "ok", span_id: Optional[str] = None,
               **attrs) -> Optional[str]:
    """File a stage both of whose ends are already read. Returns the
    span's id where one was recorded. `span_id`: one handed out before
    the stage ended (`new_span_id`), where something had to refer to the
    span while it was still running."""
    start_ns, end_ns = int(start_ns), int(end_ns)
    metrics.record(name, end_ns - start_ns)
    if ctx is None:
        tr = _CTX.trace
        if tr is None:
            return None             # the usual case: unsampled
        parent = _CTX.span_id
    else:
        tr, parent = ctx[0], ctx[1]
        if tr is None:
            return None
    return tr.add_span(name, start_ns, end_ns, parent_id=parent,
                       status=status, span_id=span_id, **attrs)


class stage:
    """`with stage("dispatch.h2d"): ...` — see the module docstring.

    `ctx`: file under this context (a capture-tuple) and not the
    thread's; it also becomes the thread's context for the block, so that
    stages nested inside hang under this one. `section`: tag the thread's
    name (`»batcher-drain`) for `_nodes/hot_threads`, so that hot threads
    and spans cannot name one stretch two ways. Set `status` inside the
    block to file a failure that does not raise through it. After the
    block `span_id` is the recorded span's id (None where unsampled),
    `start_ns` the clock reading at its start and `nanos` its duration.

    This is code on the hot path of every request: the unsampled case
    pays two clock reads, one histogram record and one atomic load."""

    __slots__ = ("name", "attrs", "span_id", "nanos", "status", "start_ns",
                 "_ctx", "_section", "_trace", "_span", "_prev", "_ann",
                 "_thread", "_thread_name")

    def __init__(self, name: str, ctx=None, section: Optional[str] = None,
                 **attrs):
        self.name = name
        self.attrs = attrs
        self.span_id = self.status = self._span = None
        self._ctx = ctx
        self._section = section

    def __enter__(self) -> "stage":
        if self._section is not None:
            t = self._thread = threading.current_thread()
            self._thread_name = t.name
            t.name = f"{t.name}»{self._section}"
        on = _SESSION_ON
        self._ann = _annotation(self.name) if on is None or on() else None
        ctx = self._ctx
        if ctx is None:
            tr, parent = _CTX.trace, _CTX.span_id
        else:
            tr, parent = ctx[0], ctx[1]
        if tr is None:
            self.start_ns = time.monotonic_ns()
            return self
        self.start_ns = now = time.monotonic_ns()
        self._trace = tr
        self._span = tr.begin_span(self.name, parent_id=parent,
                                   start_ns=now, **self.attrs)
        self._prev = (_CTX.trace, _CTX.span_id)
        _CTX.trace, _CTX.span_id = tr, self._span.span_id
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end_ns = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self.nanos = nanos = end_ns - self.start_ns
        metrics.record(self.name, nanos)
        span = self._span
        if span is not None:
            self._trace.end_span(
                span, end_ns=end_ns, status=self.status or (
                    "error" if exc_type is not None else "ok"))
            self.span_id = span.span_id
            _CTX.trace, _CTX.span_id = self._prev
        if self._section is not None:
            self._thread.name = self._thread_name
        return False


# ---------------------------------------------------------------------------
# The interpreter's collector: every thread of the server stands still for it
# ---------------------------------------------------------------------------

GC_COUNTERS = tuple(f"runtime.gc_collections.gen{g}" for g in range(3))
_gc_lock = threading.Lock()
_gc_hooked = False


class _GcMark:
    """The newest collection the hook saw. Written only inside the
    collector's callbacks (one collection runs at a time, and holds the
    interpreter), read by the heartbeat."""

    __slots__ = ("start_ns", "stop_ns")

    def __init__(self) -> None:
        self.start_ns = self.stop_ns = 0


_GC_MARK = _GcMark()


def gc_mark():
    """(start_ns, stop_ns) of the newest collection the hook saw; one is
    running while the start is the later of the two."""
    return _GC_MARK.start_ns, _GC_MARK.stop_ns


def time_gc() -> None:
    """Time the collector's pauses from inside the program, always on:
    histogram `runtime.gc_pause` (collector start to stop), counters
    `runtime.gc_collections.gen0/1/2`, and a profiler event
    `es.runtime.gc_pause` (start and stop run on the thread that tripped
    the collection), and the mark a stall's record reads (`gc_mark`).
    Hooked once a process, when the first node starts; the counters
    exist from then on, so one that never moved reads 0."""
    global _gc_hooked
    for name in GC_COUNTERS:
        metrics.counter(name)
    metrics.histogram("runtime.gc_pause")
    with _gc_lock:
        if _gc_hooked:
            return
        _gc_hooked = True
    running = [None]    # the running collection's annotation

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            running[0] = _annotation("runtime.gc_pause")
            _GC_MARK.start_ns = time.monotonic_ns()
            return
        _GC_MARK.stop_ns = end_ns = time.monotonic_ns()
        ann, running[0] = running[0], None
        if ann is not None:
            ann.__exit__(None, None, None)
        # resolved per call: a test-time `REGISTRY.reset()` must not
        # detach the hook from the registry
        metrics.record("runtime.gc_pause", end_ns - _GC_MARK.start_ns)
        metrics.counter(GC_COUNTERS[min(info.get("generation", 0),
                                        2)]).inc()

    gc.callbacks.append(on_gc)
