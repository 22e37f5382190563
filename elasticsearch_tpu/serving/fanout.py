"""Deadline-budgeted scatter-gather: the cross-node production query path.

Everything below the process boundary already sheds on deadlines — the
continuous batcher (serving/batcher.py) orders its queue earliest-deadline-
first and times a request out the moment it can no longer be served. But the
CLUSTER coordinator's fan-outs (`cluster_node._query_phase` and friends)
waited for `pending == 0` with no timer: one slow or dead data node hung the
whole accumulator, and the request's deadline died at the coordinator
instead of traveling into the per-shard sub-requests.

This module is the reference's layers 5–7 shape (action/transport/
coordination — AbstractSearchAsyncAction + SearchTimeProvider + the
per-shard timeout accounting of `SearchResponse._shards`), rebuilt on the
injected transport/scheduler pair so one implementation serves the
deterministic simulator and the asyncio TCP deployment:

* `ScatterGather` — one fan-out phase under a time budget. Every launched
  sub-request gets its OWN timeout accounting (a dead node can never hang
  the phase); responses/failures/timeouts resolve each item exactly once;
  when the last item resolves (or times out) the phase summary fires.
  All items share the phase's absolute expiry instant, so ONE sweep
  timer per phase enforces every per-shard timeout — the asyncio
  deployment would otherwise accumulate an uncancellable TimerHandle per
  replica per write for the full budget. Late responses — a slow node
  answering after its timeout — are counted and fed to the caller's
  latency observer (ARS) but can no longer change the response.

* deadline envelopes — `attach_deadline` stamps a sub-request with the
  request's ABSOLUTE deadline in coordinator-clock ms (`scheduler.now_ms`
  domain: virtual time under the simulator, CLOCK_MONOTONIC-based loop
  time over TCP — comparable across processes on one host, the gRPC
  absolute-deadline convention). The remote handler reads
  `remaining_ms` on arrival and routes it into its own admission layer:
  the continuous batcher's EDF queue sheds the sub-request *remotely*, so
  the coordinator's per-shard timer is a backstop for dead nodes, not the
  primary shedding mechanism. The coordinator therefore waits
  `deadline_grace_ms` PAST the propagated deadline — a remote shed beats
  the local timer and carries honest attribution.

* `FanoutStats` — per-phase fan-out counters, per-node slow/fail tallies
  (the same signal the ARS observer ranks copies by), remote-shed
  attribution, and partial-response counts; surfaced under
  `_nodes/stats fanout` and, per-request, `profile.fanout`.

Settings (cluster-level, dynamic via `PUT /_cluster/settings`):

    search.fanout.query_budget_ms     per-shard QUERY-phase budget (15000)
    search.fanout.fetch_budget_ms     per-shard FETCH-phase budget (10000)
    search.fanout.deadline_grace_ms   how long the coordinator waits past a
                                      propagated deadline for the remote's
                                      own shed to arrive (1000)
    search.fanout.partial_results     true: budget expiry returns partial
                                      results with `timed_out: true` and
                                      `_shards.failed` accounting; false:
                                      a timed-out phase is a 503 error
                                      (allow_partial_search_results=false)
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

from elasticsearch_tpu.telemetry import new_span_id as _new_span_id
from elasticsearch_tpu.telemetry import stage_done as _stage_done

# key under which a sub-request carries its deadline envelope; "_"-prefixed
# so it can never collide with a user-visible request field
ENVELOPE_KEY = "_fanout"

DEFAULT_QUERY_BUDGET_MS = 15_000
DEFAULT_FETCH_BUDGET_MS = 10_000
DEFAULT_DEADLINE_GRACE_MS = 1_000

# outcome vocabulary — exactly one per launched item
OK = "ok"
FAILED = "failed"
TIMED_OUT = "timed_out"
SHED = "shed"          # the remote's own admission layer rejected it

_PHASE_KEYS = ("launched", OK, FAILED, TIMED_OUT, SHED,
               "late_responses", "phase_timeouts")


def budgets_from_settings(settings: Optional[dict]) -> dict:
    """Resolve the `search.fanout.*` knobs from a (cluster) settings dict.
    Values may arrive as strings through the REST settings API."""
    from elasticsearch_tpu.common.settings import setting_bool
    s = settings or {}

    def _ms(key: str, default: int) -> int:
        try:
            return max(int(float(s.get(key, default))), 0)
        except (TypeError, ValueError):
            return default

    return {
        "query_budget_ms": _ms("search.fanout.query_budget_ms",
                               DEFAULT_QUERY_BUDGET_MS),
        "fetch_budget_ms": _ms("search.fanout.fetch_budget_ms",
                               DEFAULT_FETCH_BUDGET_MS),
        "deadline_grace_ms": _ms("search.fanout.deadline_grace_ms",
                                 DEFAULT_DEADLINE_GRACE_MS),
        "partial_results": setting_bool(
            s.get("search.fanout.partial_results", True)),
    }


def attach_deadline(request: dict, deadline_at_ms: Optional[int],
                    now_ms: int) -> dict:
    """Stamp a sub-request with the absolute deadline (coordinator-clock
    ms). No-op when the request carries no deadline."""
    if deadline_at_ms is not None:
        request[ENVELOPE_KEY] = {"deadline_at_ms": int(deadline_at_ms),
                                 "sent_at_ms": int(now_ms)}
    return request


def attach_trace(request: dict, trace, parent_span_id: str) -> dict:
    """Ride the trace context on the deadline envelope: the remote node
    opens a trace SEGMENT with the same trace id whose spans parent under
    `parent_span_id` (the coordinator's per-leg span), so the merged
    trace reads as one tree across the transport. No-op when the request
    isn't traced."""
    if trace is not None:
        request.setdefault(ENVELOPE_KEY, {})["trace"] = {
            "trace_id": trace.trace_id,
            "parent_span_id": parent_span_id,
            "opaque_id": trace.opaque_id,
        }
    return request


def trace_ctx_of(request: Optional[dict]) -> Optional[dict]:
    """The trace context an arriving sub-request carries, or None."""
    return ((request or {}).get(ENVELOPE_KEY) or {}).get("trace")


def remaining_ms(request: Optional[dict], now_ms: int) -> Optional[float]:
    """Budget left on an arriving sub-request, or None when it carries no
    deadline. Negative = already expired — shed at admission."""
    env = (request or {}).get(ENVELOPE_KEY) or {}
    at = env.get("deadline_at_ms")
    if at is None:
        return None
    return float(at) - float(now_ms)


def shed_response(shard: Any, shed_by: str) -> dict:
    """The structured rejection a remote node returns when a propagated
    deadline expired before (or while) the sub-request was admitted.
    Travels as a RESPONSE, not a transport failure, so the coordinator
    can attribute it (deadline shed, not node death)."""
    return {"shard": shard, "rejected": "deadline_exceeded",
            "shed_by": shed_by}


def is_shed(resp: Any) -> bool:
    return isinstance(resp, dict) and \
        resp.get("rejected") == "deadline_exceeded"


class FanoutStats:
    """Counters for the cross-node serving path. Mutated only from the
    owning node's scheduler thread (simulator task / asyncio loop), so no
    locking — same single-threaded-actor discipline as the transport."""

    def __init__(self) -> None:
        self.phases: Dict[str, Dict[str, int]] = {}
        self.per_node: Dict[str, Dict[str, int]] = {}
        self.partial_responses = 0
        # data-plane side: sub-requests THIS node shed on arrival because
        # the propagated deadline had expired — `batcher` means the
        # continuous batcher's EDF queue did the shedding
        self.remote = {"sheds_admission": 0, "sheds_batcher": 0}

    def phase(self, name: str) -> Dict[str, int]:
        pc = self.phases.get(name)
        if pc is None:
            pc = self.phases[name] = {k: 0 for k in _PHASE_KEYS}
        return pc

    def node(self, node_id: str) -> Dict[str, int]:
        nc = self.per_node.get(node_id)
        if nc is None:
            nc = self.per_node[node_id] = {"slow": 0, "failed": 0}
        return nc

    def snapshot(self) -> dict:
        return {
            "phases": {p: dict(c) for p, c in sorted(self.phases.items())},
            "per_node": {n: dict(c)
                         for n, c in sorted(self.per_node.items())},
            "partial_responses": self.partial_responses,
            "remote": dict(self.remote),
        }


class ScatterGather:
    """One fan-out phase: launch sub-requests, resolve each exactly once
    (response / failure / per-shard timer), fire `on_done(summary)` when
    the last one resolves.

    Usage::

        sg = ScatterGather(scheduler, phase="query", budget_ms=15_000,
                           stats=node.fanout_stats, on_done=finish)
        for target in targets:
            sg.launch(key, target.node_id, send, on_item=fold)
        sg.seal()

    `send(on_response, on_failure)` performs the actual RPC (or local
    direct call); `on_item(outcome, payload, err)` folds one result into
    the caller's accumulator. `seal()` marks the launch set complete —
    a phase with zero launches completes at seal time.

    The per-shard timeouts make the no-hang guarantee structural: every
    launched item is resolved by the phase's sweep timer at the latest,
    so `on_done` ALWAYS fires within the budget (+ one scheduler hop),
    regardless of what the network drops. One timer serves the whole
    phase because every item expires at the same absolute instant
    (phase start + budget); the sweep resolves each still-pending item
    individually, so per-shard timeout accounting is unchanged.
    """

    def __init__(self, scheduler, *, phase: str, budget_ms: int,
                 stats: Optional[FanoutStats] = None,
                 on_done: Optional[Callable[[dict], None]] = None,
                 observe: Optional[Callable[[str, float], None]] = None,
                 trace=None, trace_parent: Optional[str] = None):
        self._scheduler = scheduler
        self.phase = phase
        self.budget_ms = max(int(budget_ms), 0)
        self.stats = stats if stats is not None else FanoutStats()
        self._on_done = on_done
        # request trace (telemetry.trace.Trace) of the search this phase
        # serves: each leg is one stage `fanout.leg`, filed at its
        # resolution — resolution is structural (response/failure/sweep
        # timer), so a dead node produces an ERROR span, never a leak
        self._trace = trace
        self._trace_parent = trace_parent
        # latency observer (ARS EWMA feed): called with (node_id, took_ms)
        # for on-time responses AND late arrivals; timeouts feed a penalty
        self._observe = observe
        self._started_ms = scheduler.now_ms
        self._pending: Dict[Any, str] = {}
        # key -> timeout resolver, installed per launch, popped on
        # resolution (so resolved items' closures free immediately);
        # the single sweep timer drains whatever is left at budget end
        self._timeout_resolvers: Dict[Any, Callable[[], None]] = {}
        self._timer_armed = False
        self._launched = 0
        self._sealed = False
        self._finished = False
        self._counts = {OK: 0, FAILED: 0, TIMED_OUT: 0, SHED: 0}

    # ------------------------------------------------------------ launching
    def launch(self, key: Any, node_id: str,
               send: Callable[[Callable, Callable], None],
               on_item: Optional[Callable[[str, Any, Any], None]] = None,
               request: Optional[dict] = None) -> None:
        pc = self.stats.phase(self.phase)
        pc["launched"] += 1
        self._launched += 1
        self._pending[key] = node_id
        sent_ms = self._scheduler.now_ms
        sent_ns = time.monotonic_ns()
        leg_id = None
        if self._trace is not None and request is not None:
            # the remote's segment parents under THIS leg's span, so
            # the merged tree shows coordinator leg → remote work: the
            # span's id goes out with the request, before the leg ends
            leg_id = _new_span_id()
            attach_trace(request, self._trace, leg_id)

        def resolve(outcome: str, payload=None, err=None) -> None:
            if self._pending.pop(key, None) is None:
                return  # already resolved (timer raced a late response)
            self._timeout_resolvers.pop(key, None)
            self._counts[outcome] += 1
            pc[outcome] += 1
            # one stage per leg, on every outcome (launch -> response,
            # failure or sweep timer): a dead node's leg is an ERROR
            # span in the trace, not a leak
            _stage_done("fanout.leg", sent_ns, time.monotonic_ns(),
                        (self._trace, self._trace_parent, None),
                        status=outcome, span_id=leg_id, phase=self.phase,
                        node=node_id, shard=str(key))
            try:
                if on_item is not None:
                    on_item(outcome, payload, err)
            finally:
                # the phase must complete even if the caller's fold raised
                self._maybe_finish()

        def on_response(resp) -> None:
            took = max(self._scheduler.now_ms - sent_ms, 0)
            if key not in self._pending:
                # late: the timer already resolved this shard. Observe the
                # true latency (the ARS signal that makes the next request
                # prefer another copy) but never mutate the response.
                pc["late_responses"] += 1
                if self._observe is not None:
                    self._observe(node_id, float(took))
                return
            if self._observe is not None:
                self._observe(node_id, float(took))
            if is_shed(resp):
                resolve(SHED, resp)
            else:
                resolve(OK, resp)

        def on_failure(err) -> None:
            if key in self._pending:
                self.stats.node(node_id)["failed"] += 1
            resolve(FAILED, None, err)

        def on_timeout() -> None:
            if key not in self._pending:
                return
            self.stats.node(node_id)["slow"] += 1
            if self._observe is not None:
                # a timed-out shard observed at the full budget: the ARS
                # EWMA ranks this node behind every copy that answered
                self._observe(node_id, float(self.budget_ms))
            resolve(TIMED_OUT)

        self._timeout_resolvers[key] = on_timeout
        # one sweep timer per PHASE, armed at the first launch: every
        # item shares the same absolute expiry (phase start + budget),
        # and per-launch timers would pile up uncancellable handles on
        # the asyncio deployment (one per replica per write, alive for
        # the full budget)
        if not self._timer_armed:
            self._timer_armed = True
            delay = max(self._started_ms + self.budget_ms
                        - self._scheduler.now_ms, 0)
            self._scheduler.schedule_in(
                delay, self._sweep_expired, f"fanout:{self.phase}")
        send(on_response, on_failure)

    def _sweep_expired(self) -> None:
        """Budget expiry: resolve every still-pending item as timed out
        (each individually, so per-shard accounting is identical to a
        per-item timer)."""
        for resolver in [self._timeout_resolvers[k]
                         for k in list(self._timeout_resolvers)
                         if k in self._pending]:
            resolver()

    def seal(self) -> None:
        """No more launches; a zero-target phase completes here."""
        self._sealed = True
        self._maybe_finish()

    # ------------------------------------------------------------ completion
    @property
    def timed_out(self) -> bool:
        """Reference `timed_out` semantics: a shard timer expired, or a
        remote shed its sub-request on the propagated deadline."""
        return self._counts[TIMED_OUT] > 0 or self._counts[SHED] > 0

    def _maybe_finish(self) -> None:
        if self._finished or not self._sealed or self._pending:
            return
        self._finished = True
        pc = self.stats.phase(self.phase)
        if self._counts[TIMED_OUT] > 0:
            pc["phase_timeouts"] += 1
        summary = {
            "phase": self.phase,
            "launched": self._launched,
            "budget_ms": self.budget_ms,
            "elapsed_ms": max(self._scheduler.now_ms - self._started_ms, 0),
            # counts per outcome: ok / failed / timed_out / shed
            **dict(self._counts),
            # reference `timed_out` semantics (bool): a shard timer
            # expired, or a remote shed on the propagated deadline
            "any_timed_out": self.timed_out,
        }
        if self._on_done is not None:
            self._on_done(summary)
