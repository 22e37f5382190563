"""End-to-end request telemetry: traces, metrics, live tasks.

Three coupled pieces (ISSUE 14), one always-on low-overhead layer, ONE
call form that feeds them all (`telemetry.stage`, ISSUE 26), and one
heartbeat that looks at the server from outside a request (ISSUE 37):

* `telemetry.stage` — `with stage(name):` / `stage_done(name, start_ns,
  end_ns, ctx)` time one boundary: the histogram `name`, a span of that
  name where the request is sampled, and (same-thread form) an event
  `es.<name>` in a `jax.profiler` trace, on the device's clock. No site
  writes a histogram or a span by hand.
* `telemetry.trace` — distributed tracing. Every search/write request
  gets a trace (sampled by `telemetry.tracing.sample_rate`, forced by
  `?trace=true` or a `profile` body) whose spans cover REST parse,
  coordinator fan-out, each scatter-gather leg (context rides the PR-12
  deadline envelope), remote queue wait, device dispatch, the deferred
  device sync at finalize, hydrate and merge. Completed traces land in a
  bounded per-node ring (`GET _nodes/traces`) and attach (trace id +
  top-3 spans) to slow-log breaches.
* `telemetry.metrics` — process-wide counters/gauges/log2-bucket latency
  histograms; `_nodes/stats telemetry` reports live p50/p90/p99/p999 for
  end-to-end search latency, queue wait, device dispatch/sync and
  fan-out leg latency without a bench harness.
* `telemetry.beat` — the one look at the server from OUTSIDE a request
  (ISSUE 37): a daemon thread, `telemetry-beat`, every 10 ms. Histogram
  `runtime.lock_wait` (what a beat overslept: the wait of a runnable
  thread for the interpreter lock); counters `runtime.stalls` and
  `runtime.stall_nanos` with a record of what held the server (work in
  flight and no response out for 50 ms, or a beat 50 ms late:
  `_nodes/stats telemetry.stalls`, and a WARN line); the profiler event
  `es.runtime.beat` whose stat `mono_ns` ties `time.monotonic_ns()` to
  the profiler's clock. Beside it the counter `rest.handle.cpu_nanos`
  (`Front`): the handlers' own processor time, so that `rest.handle`
  splits into work and wait.
* the tasks binding below — `rest_request` registers every instrumented
  REST request with the node's TaskManager (action, opaque id, trace id,
  current span); `GET _tasks` lists them live, and `POST
  _tasks/_cancel` flips the task's `cancelled` flag, which the
  continuous batcher's EDF queue observes at admission (cancelled
  entries shed exactly like expired deadlines).

`X-Opaque-ID` threads through all three: the REST layer captures the
header once and it travels on the task, the trace, and any slow-log
entry the request breaches.

Settings (node-level; process-wide like the dispatcher — only an
explicit setting reconfigures, so a second in-process node without one
never clobbers an earlier node's choice):

    telemetry.tracing.sample_rate   head-sampling rate (default 0.01)
    telemetry.traces.ring_size      completed-trace ring bound (256)
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional, Tuple

from elasticsearch_tpu.telemetry import metrics
from elasticsearch_tpu.telemetry import trace as trace_mod
from elasticsearch_tpu.telemetry.metrics import REGISTRY
from elasticsearch_tpu.telemetry.beat import BEAT
from elasticsearch_tpu.telemetry.stages import (
    UNSAMPLED,
    Front,
    annotation,
    new_span_id,
    stage,
    stage_done,
    time_gc,
)
from elasticsearch_tpu.telemetry.trace import (
    TRACER,
    Trace,
    capture,
    current_span_id,
    current_task,
    current_trace,
    use,
)

__all__ = [
    "metrics", "trace_mod", "BEAT", "REGISTRY", "TRACER", "Trace", "Front",
    "UNSAMPLED", "annotation", "capture", "current_span_id",
    "current_task", "current_trace",
    "new_span_id", "stage", "stage_done", "time_gc", "use",
    "rest_request", "configure_from_settings",
]


def configure_from_settings(settings: Optional[dict]) -> None:
    """Wire `telemetry.*` node settings into the process-wide tracer.
    Explicit settings only — absent keys leave the current (possibly
    earlier-node-configured) policy untouched."""
    s = settings or {}
    rate = s.get("telemetry.tracing.sample_rate")
    ring = s.get("telemetry.traces.ring_size")
    if rate is not None:
        TRACER.configure(sample_rate=float(rate))
    if ring is not None:
        TRACER.configure(ring_size=int(ring))


@contextmanager
def rest_request(node, action: str, *, opaque_id: Optional[str] = None,
                 force_trace: bool = False, description: str = "",
                 parsed: Optional[Tuple[int, int]] = None):
    """Instrument one REST request end to end: register a live task
    (visible in `GET _tasks`, cancellable into the batcher queue), open
    a trace when sampled/forced, and install both on the thread so every
    layer below (batcher entries, fan-out envelopes, slow logs) can see
    them. Yields the Trace (or None when unsampled). `parsed` is the
    (start_ns, end_ns) of the body's parse, which has to come before the
    sampling decision (a `profile` body forces a trace): stage
    `rest.parse`.

    Under the HTTP server the thread carries the request's `Front`: a
    sampled trace then starts where the front did (request line in
    hand), hangs the handler's spans under the `rest.handle` span the
    front will file, and is finished by the server after
    `http.respond`, not here."""
    tracer = TRACER
    front = trace_mod._CTX.front
    if front is not None and front.trace is not None:
        front = None      # a nested instrumented call: the outer one owns it
    tr = tracer.start(action, node_id=getattr(node, "node_id", "?"),
                      forced=force_trace, opaque_id=opaque_id,
                      started_ns=front.start_ns if front is not None
                      else None)
    parent = None
    if tr is not None and front is not None:
        parent = front.adopt(tr)
    tasks = getattr(node, "tasks", None)
    task = None
    if tasks is not None:
        task = tasks.register(action, description=description,
                              opaque_id=opaque_id, trace=tr)
    status = None
    try:
        with use(trace=tr, span_id=parent, task=task):
            if parsed is not None:
                stage_done("rest.parse", parsed[0], parsed[1])
            yield tr
    except BaseException:
        status = "error"
        raise
    finally:
        if task is not None:
            tasks.unregister(task)
        if tr is not None:
            if front is not None:
                front.status = status
            else:
                tracer.finish(tr, status=status)
