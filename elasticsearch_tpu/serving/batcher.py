"""Continuous-batching dispatch for vector search.

The round-3 serving path dispatched ONE query per device round-trip, so
end-to-end latency was ~100x the device time and tiny-corpus hybrid queries
lost to the reference's host-side BulkScorer (`QueryPhase.java:171`). The
r06 closed-loop rows then showed the NEXT bottleneck: both 8-client rows
blew the p99 <= 3x p50 gate (6.18x / 5.95x) because the batcher was a
single admit-or-429 drain loop — a request arriving just after a drain
waited a full service cycle plus queue, and host post-processing of batch
N serialized with the device dispatch of batch N+1. This module is the
continuous-batching scheduler (the Orca/vLLM iteration-level shape,
adapted to the shape-bucketed dispatcher):

* `CombiningBatcher` — a combining-lock queue: the first thread in becomes
  the runner and executes whatever requests accumulated while the previous
  dispatch was in flight. Under load, batch size grows adaptively with no
  added idle latency (an idle submit executes immediately, no timer). On
  top of that base it now schedules:

  - deadline-aware admission: queued requests order earliest-deadline-
    first, and shedding happens at SCHEDULE time — a request is timed out
    exactly when it can no longer meet its deadline, not only at
    enqueue-time queue-depth admission;
  - in-flight bucket top-up: a drained batch that lands below its
    dispatch bucket boundary (`ops/dispatch.bucket_queries`) has free
    padded rows anyway — late arrivals claim them (optionally waiting a
    bounded `target_batch_latency_ms` window) so they ride THIS dispatch
    instead of the next service cycle. Snapping to bucket boundaries
    means a top-up costs zero recompiles;
  - async dispatch pipelining: with a (dispatch_fn, finalize_fn) executor
    pair, the runner holds the lock only for the device dispatch (which
    returns un-synced arrays) and finalizes — device sync, host
    rescore/hydrate — OUTSIDE the lock, so the next runner's dispatch
    overlaps with this batch's host work. `async_depth` bounds how many
    batches may be in flight un-finalized.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Sequence, Tuple

from elasticsearch_tpu.common.errors import TaskCancelledError
from elasticsearch_tpu.common.threadpool import EsRejectedExecutionError
from elasticsearch_tpu.telemetry import UNSAMPLED as _UNSAMPLED
from elasticsearch_tpu.telemetry import metrics as _metrics
from elasticsearch_tpu.telemetry import stage as _stage
from elasticsearch_tpu.telemetry import stage_done as _stage_done
from elasticsearch_tpu.telemetry import trace as _tt

IDLE_NO_REQUEST = "serving.idle_no_request_nanos"
IDLE_PICKUP = "serving.idle_pickup_nanos"


class IdleClock:
    """Device-starved time by cause, counted where the batches are.

    The vector stores count their batches in flight (dispatched, not yet
    finalized). From the instant the last one lands (1 -> 0) to the next
    dispatch (0 -> 1) the device has no kNN batch to work on; that
    interval goes to one of two counters:

    * `serving.idle_no_request_nanos` — until the first request was
      enqueued in a store's batcher: nobody asked;
    * `serving.idle_pickup_nanos` — from that enqueue to the dispatch: a
      request was waiting and no runner had launched it yet (run lock,
      GIL, batch forming, the host half of the dispatch before launch).

    A request already waiting when the last batch landed makes the whole
    interval pickup. Cost: two clock reads a BATCH, on the edges only;
    `waiting()` reads the clock once an idle stretch (the first request
    after a dispatch began notes its time; the check is unlocked, and a
    lost race files one interval under the neighbouring cause). Process-
    wide like the registry it feeds: one device, one clock. The time
    left over is a batch in flight: host work (`dispatch.prepare`,
    `.h2d`, `.launch`, `.d2h`, `.land`) or the wait for the device
    (`dispatch.sync_wait`)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._active = 0
        self._idle_since = 0        # 0: never yet busy (start-up is no gap)
        self._first_waiting = 0     # 0: nobody since the last dispatch began

    def waiting(self, now_ns: Optional[int] = None) -> None:
        """A request is about to queue for a dispatch."""
        if not self._first_waiting:
            self._first_waiting = now_ns or time.monotonic_ns()

    def begin(self, now_ns: Optional[int] = None) -> None:
        """A batch goes in flight."""
        with self._lock:
            self._active += 1
            first, self._first_waiting = self._first_waiting, 0
            if self._active != 1 or not self._idle_since:
                return
            since, self._idle_since = self._idle_since, 0
            now = now_ns or time.monotonic_ns()
        self._book(since, first, now)

    def end(self, now_ns: Optional[int] = None) -> None:
        """A batch has landed (or its handle was dropped)."""
        with self._lock:
            self._active = max(0, self._active - 1)
            if self._active == 0:
                self._idle_since = now_ns or time.monotonic_ns()

    def flush(self, now_ns: Optional[int] = None) -> None:
        """Book the idle stretch that is still open, up to now: a reader
        of the counters (`_nodes/stats`) then sees them as of its read,
        and a stretch that straddles two reads is split between them."""
        with self._lock:
            if self._active or not self._idle_since:
                return
            now = now_ns or time.monotonic_ns()
            since, self._idle_since = self._idle_since, now
            first = self._first_waiting
        self._book(since, first, now)

    @staticmethod
    def _book(since: int, first: int, now: int) -> None:
        asked = min(max(first, since), now) if first else now
        # resolved per call: a test-time `REGISTRY.reset()` must not
        # detach the clock from the registry
        _metrics.counter(IDLE_NO_REQUEST).inc(asked - since)
        _metrics.counter(IDLE_PICKUP).inc(now - asked)

    def ensure_counters(self) -> None:
        """Create both counters, so that one that never moved reads 0 in
        `_nodes/stats telemetry` and not nothing (called when a node
        starts)."""
        _metrics.counter(IDLE_NO_REQUEST)
        _metrics.counter(IDLE_PICKUP)


IDLE = IdleClock()


class _QueueEntry:
    """One queued request: payload, future, and its schedule metadata."""

    __slots__ = ("request", "fut", "enqueued", "deadline", "seq", "claimed",
                 "ctx", "trace", "span_parent", "token")

    def __init__(self, request, fut: Future, enqueued: float,
                 deadline: Optional[float], seq: int):
        self.request = request
        self.fut = fut
        self.enqueued = enqueued
        self.deadline = deadline   # monotonic instant; None = never expires
        self.seq = seq             # arrival order (EDF tie-break)
        self.claimed = False       # a runner owns it (set under _q_lock)
        # telemetry context, captured from the SUBMITTING thread at
        # enqueue time: the pipelined batcher claims, dispatches, and
        # finalizes this entry on other threads, so thread-locals alone
        # cannot follow the request — the entry carries its own trace
        # (None = unsampled), parent span id, and cancellation token
        # (the live task; a truthy `.cancelled` sheds at EDF admission)
        self.ctx = _tt.capture()
        self.trace, self.span_parent, self.token = self.ctx

    def sort_key(self) -> Tuple[float, int]:
        return (self.deadline if self.deadline is not None else float("inf"),
                self.seq)


def _fresh_sched_stats() -> dict:
    return {"batches": 0, "pipelined_batches": 0, "requests": 0,
            "topups": 0, "deadline_sheds": 0, "cancelled_sheds": 0,
            "overlap_hits": 0}


class CombiningBatcher:
    """Combining-lock request coalescer with continuous-batching
    scheduling.

    submit() enqueues and then either (a) finds its result already set by a
    concurrent runner, or (b) becomes the runner: drains the queue
    earliest-deadline-first, tops the batch up to its dispatch bucket
    boundary, and executes it. While a runner is dispatching, later
    submitters queue up — their requests form the next batch (or top up
    this one). No background thread, no batching timer, zero idle latency.

    Two executor shapes:

    * `execute(requests) -> results` — the classic synchronous path: runs
      under the run lock, exactly one batch in flight at a time.
    * `dispatch_fn(requests) -> handle` + `finalize_fn(handle) -> results`
      — the pipelined path: `dispatch_fn` launches device work and returns
      WITHOUT syncing (un-synced arrays in the handle); the runner then
      releases the run lock and finalizes (device sync + host
      post-processing) outside it, so the next batch's device dispatch
      overlaps this batch's host work. `async_depth` bounds in-flight
      un-finalized batches. `execute` stays the poisoned-batch serial-
      retry path (synthesized from the pair when not given).

    `sched` counts the scheduler's work: batches, top-ups, schedule-time
    deadline sheds, dispatch/finalize overlap hits. Its TIMES are the
    telemetry stages `serving.queue_wait` (a request), `serving.batch_form`,
    `serving.device_dispatch` and `serving.device_sync` (a batch): one
    `telemetry.stage` call each, and kept nowhere else.
    """

    def __init__(self, execute: Optional[Callable[[Sequence], List]],
                 max_batch: int = 256, *,
                 dispatch_fn: Optional[Callable[[Sequence], Any]] = None,
                 finalize_fn: Optional[Callable[[Any], List]] = None,
                 topup: bool = True,
                 target_batch_latency_ms: float = 0.0,
                 async_depth: int = 2):
        from elasticsearch_tpu.ops import dispatch
        if (dispatch_fn is None) != (finalize_fn is None):
            raise ValueError("dispatch_fn and finalize_fn come as a pair")
        self._dispatch_fn = dispatch_fn
        self._finalize_fn = finalize_fn
        if execute is None:
            if dispatch_fn is None:
                raise ValueError("need execute or dispatch_fn/finalize_fn")
            execute = lambda reqs: finalize_fn(dispatch_fn(reqs))  # noqa: E731
        self._execute = execute
        # the batch ceiling snaps to a dispatch query bucket: a saturated
        # drain then hands the executor an exactly-bucket-sized batch (no
        # padding waste at peak), and light-load drains pad up to the
        # nearest bucket inside the executor — either way the compiled
        # shape set stays closed
        self._max_batch = dispatch.bucket_queries(max_batch)
        self._topup_enabled = bool(topup)
        self._target_ms = float(target_batch_latency_ms)
        self._run_lock = threading.Lock()
        self._q_lock = threading.Lock()
        self._q_cond = threading.Condition(self._q_lock)
        self._queue: List[_QueueEntry] = []
        self._seq = 0
        self._inflight = 0           # dispatched, not yet finalized
        self._depth_sem = threading.BoundedSemaphore(max(1, int(async_depth)))
        self._tls = threading.local()
        self.sched = _fresh_sched_stats()

    # ------------------------------------------------------------ queue
    def pending(self) -> int:
        """Requests queued but not yet claimed by a runner."""
        with self._q_lock:
            return len(self._queue)

    def load(self) -> dict:
        """Live scheduler snapshot for load-aware routing — what the
        mesh policy's dp-vs-shard router reads (via the store's
        `_queued_requests`): queued entries, in-flight batches, and the
        cumulative pressure counters (`topups`, `overlap_hits`) that say
        whether this batcher has been running hot. Note the router's
        queue-depth signal uses
        `pending` only — in-flight batches are already counted by the
        store's dispatch gauge."""
        with self._q_lock:
            return {"pending": len(self._queue),
                    "inflight": self._inflight,
                    "topups": self.sched["topups"],
                    "overlap_hits": self.sched["overlap_hits"]}

    def _deadline_for(self, now: float) -> Optional[float]:
        """Absolute deadline for a request enqueued at `now`; None means
        it never expires (base batcher has no admission deadline)."""
        return None

    def _admit(self, depth: int, now: float) -> None:
        """Admission hook, called under the queue lock with the current
        queue depth: subclasses refuse (raise) instead of queueing
        without bound."""

    def _enqueue(self, request, fut: Future,
                 deadline_at: Optional[float] = None) -> _QueueEntry:
        """Queue one request (admission may refuse — `_admit`). Returns
        the queue entry. `deadline_at` is a per-request ABSOLUTE deadline
        (time.monotonic seconds) — a cross-node search propagates the
        request's end-to-end deadline here so the EDF queue sheds the
        sub-request at THIS node's admission layer; it tightens (never
        loosens) the batcher's own admission deadline."""
        now = time.monotonic()
        with self._q_cond:
            self._admit(len(self._queue), now)
            deadline = self._deadline_for(now)
            if deadline_at is not None:
                deadline = deadline_at if deadline is None \
                    else min(deadline, deadline_at)
            entry = _QueueEntry(request, fut, now, deadline, self._seq)
            self._seq += 1
            self._queue.append(entry)
            self._q_cond.notify_all()
        return entry

    def _shed(self, entry: _QueueEntry, now: float) -> None:
        """Schedule-time deadline shed: the request can no longer meet
        its deadline, so it is timed out NOW instead of spending device
        time on an answer nobody reads."""
        self.sched["deadline_sheds"] += 1
        if not entry.fut.done():
            waited = (now - entry.enqueued) * 1000.0
            entry.fut.set_exception(EsRejectedExecutionError(
                f"rejected execution: request spent "
                f"{waited:.0f}ms queued, over the admission deadline"))

    def _shed_cancelled(self, entry: _QueueEntry, now: float) -> None:
        """Cancellation shed: the request's task was cancelled
        (`POST _tasks/_cancel`) while it sat queued — it leaves the EDF
        queue exactly like an expired deadline, before any device time
        is spent on an answer nobody will read."""
        self.sched["cancelled_sheds"] += 1
        _stage_done("serving.queue_wait", entry.enqueued * 1e9, now * 1e9,
                    entry.ctx, status="cancelled")
        if not entry.fut.done():
            entry.fut.set_exception(TaskCancelledError(
                "task cancelled while queued (shed at EDF admission)"))

    def _claim_locked(self, want: int, now: float) -> List[_QueueEntry]:
        """Take up to `want` entries off the queue, earliest deadline
        first, shedding any whose deadline has already passed (or whose
        task was cancelled). Caller holds `_q_lock`."""
        if not self._queue:
            return []
        # deadline-less queues (the base batcher) are already in seq
        # order; skip the sort on the hot path. With a uniform
        # deadline_ms, arrival order IS deadline order, so this sort is
        # a near-no-op there too — it only reorders genuinely mixed
        # deadlines.
        if any(e.deadline is not None for e in self._queue):
            self._queue.sort(key=_QueueEntry.sort_key)
        claimed: List[_QueueEntry] = []
        keep: List[_QueueEntry] = []
        for entry in self._queue:
            if entry.token is not None \
                    and getattr(entry.token, "cancelled", False):
                self._shed_cancelled(entry, now)
                continue
            if entry.deadline is not None and now > entry.deadline:
                self._shed(entry, now)
                continue
            if len(claimed) < want:
                entry.claimed = True
                # enqueue (the submitter's thread) -> claim (the
                # runner's): plain host writes (no syncs, no allocation
                # beyond the span) — safe under _q_lock
                _stage_done("serving.queue_wait", entry.enqueued * 1e9,
                            now * 1e9, entry.ctx)
                claimed.append(entry)
            else:
                keep.append(entry)
        self._queue[:] = keep
        return claimed

    def _drain(self) -> List[_QueueEntry]:
        """Take the next batch off the queue (under the run lock):
        earliest-deadline-first, schedule-time shedding of expired
        entries."""
        with self._q_lock:
            return self._claim_locked(self._max_batch, time.monotonic())

    def _topup(self, batch: List[_QueueEntry]) -> List[_QueueEntry]:
        """In-flight bucket top-up: the drained batch dispatches padded to
        `bucket_queries(len(batch))` rows anyway, so any headroom up to
        that boundary is free — late arrivals claim it (zero recompiles:
        the compiled shape is the bucket, not the batch). With a
        `target_batch_latency_ms` budget the runner briefly waits for
        arrivals, but never past the oldest member's batching budget —
        an idle single query (bucket 1) never waits at all."""
        from elasticsearch_tpu.ops import dispatch
        if not batch:
            return batch
        target = len(batch) + dispatch.bucket_headroom(len(batch),
                                                       self._max_batch)
        if not self._topup_enabled or len(batch) >= target:
            return batch
        oldest = min(e.enqueued for e in batch)
        budget_until = oldest + self._target_ms / 1000.0
        joined = 0
        with self._q_cond:
            while len(batch) < target:
                now = time.monotonic()
                got = self._claim_locked(target - len(batch), now)
                if got:
                    batch.extend(got)
                    joined += len(got)
                    continue
                remaining = budget_until - now
                if remaining <= 0:
                    break
                self._q_cond.wait(min(remaining, 0.0005))
        if joined:
            self.sched["topups"] += joined
        return batch

    # ------------------------------------------------------------ serving
    @staticmethod
    def _check_results(batch: List[_QueueEntry], results: List) -> None:
        if len(results) != len(batch):
            raise RuntimeError(
                f"batch executor returned {len(results)} results "
                f"for {len(batch)} requests")

    def _set_results(self, batch: List[_QueueEntry], results: List) -> None:
        self._check_results(batch, results)
        for entry, res in zip(batch, results):
            entry.fut.set_result(res)

    def _retry_serially(self, batch: List[_QueueEntry], exc: Exception):
        """One poisoned request (bad filter, malformed vector) must not
        fail unrelated searches that happened to coalesce with it: retry
        each request alone so only the offender surfaces its error."""
        if len(batch) == 1:
            if not batch[0].fut.done():
                batch[0].fut.set_exception(exc)
            return
        for entry in batch:
            if entry.fut.done():
                continue
            try:
                entry.fut.set_result(self._execute([entry.request])[0])
            except Exception as one_exc:
                entry.fut.set_exception(one_exc)

    @staticmethod
    def _trace_leader(batch: List[_QueueEntry]) -> Optional[_QueueEntry]:
        """The batch's trace LEADER: the first member with a sampled
        trace. The leader's trace carries the batch-level device spans;
        other traced members (followers) link to them instead of
        double-counting device time that was shared by the whole
        coalesced batch."""
        for entry in batch:
            if entry.trace is not None:
                return entry
        return None

    def _batch_stage(self, batch: List[_QueueEntry], name: str,
                     section: str) -> _stage:
        """One batch-level stage: its span lands on the trace LEADER's
        trace. The `with` block must close, and `_link_followers` run,
        BEFORE any future of the batch resolves — a submitter thread
        woken by set_result may immediately finish its request and ship
        the trace, and the span must already be in it."""
        # no member sampled: the histogram and no span (never the runner
        # thread's own trace)
        leader = self._trace_leader(batch)
        return _stage(name, ctx=leader.ctx if leader is not None
                      else _UNSAMPLED, section=section,
                      coalesced=len(batch))

    def _link_followers(self, batch: List[_QueueEntry], st: _stage) -> None:
        """Every traced follower links to the leader's batch span
        instead of double-counting device time the whole batch shared."""
        if st.span_id is None:
            return
        leader = self._trace_leader(batch)
        for entry in batch:
            if entry.trace is not None and entry is not leader:
                entry.trace.add_link(leader.trace.trace_id, st.span_id,
                                     "coalesced_follower")

    def _trace_since(self, batch: List[_QueueEntry]) -> Optional[int]:
        # dispatch-trace attribution (profile.dispatch): the runner
        # thread executes device work for EVERY request in the batch. If
        # this thread is recording a profile trace, label the batch's
        # events with the coalesced size so the leader's trace doesn't
        # silently claim follower dispatches as its own; followers still
        # report an empty trace (documented — `_nodes/stats
        # indices.dispatch` is the authoritative counter).
        from elasticsearch_tpu.ops import dispatch as _dispatch
        return (_dispatch.DISPATCH.event_count()
                if len(batch) > 1 and _dispatch.DISPATCH.events_enabled()
                else None)

    def _annotate(self, trace_since: Optional[int], n: int) -> None:
        # annotate on EVERY exit: the serial per-request retries of a
        # poisoned batch run on this same runner thread, and their
        # dispatches are just as much coalesced-batch work as the happy
        # path's
        if trace_since is None:
            return
        from elasticsearch_tpu.ops import dispatch as _dispatch
        _dispatch.DISPATCH.annotate_events(trace_since,
                                           coalesced_batch=n)

    def _run_sync(self, batch: List[_QueueEntry]) -> None:
        """Classic synchronous serving of one batch (under the run
        lock). Dispatch + device sync run back to back, so the whole
        stage is one figure: `serving.device_dispatch`."""
        trace_since = self._trace_since(batch)
        err: Optional[Exception] = None
        results = None
        try:
            st = self._batch_stage(batch, "serving.device_dispatch",
                                   "batcher-drain")
            try:
                with st:
                    try:
                        results = self._execute(
                            [e.request for e in batch])
                        self._check_results(batch, results)
                    except Exception as exc:
                        err = exc
                        st.status = "error"
            except BaseException as exc:  # KeyboardInterrupt/SystemExit:
                for entry in batch:       # fail fast, no serial retries
                    if not entry.fut.done():
                        entry.fut.set_exception(exc)
                raise
            self._link_followers(batch, st)
            if err is None:
                self._set_results(batch, results)
            else:
                self._retry_serially(batch, err)
        finally:
            self._annotate(trace_since, len(batch))

    def _begin_pipelined(self, batch: List[_QueueEntry]):
        """Dispatch stage (under the run lock): launch the batch's device
        work WITHOUT syncing (`serving.device_dispatch`). Returns the
        finalize context."""
        trace_since = self._trace_since(batch)
        self._depth_sem.acquire()   # bounds in-flight un-finalized batches
        with self._q_lock:
            if self._inflight > 0:
                # a previous batch is still finalizing on another thread
                # while this dispatch starts: the overlap the pipeline
                # exists to create
                self.sched["overlap_hits"] += 1
            self._inflight += 1
        handle: Any = None
        err: Optional[Exception] = None
        st = self._batch_stage(batch, "serving.device_dispatch",
                               "batcher-drain")
        try:
            with st:
                try:
                    handle = self._dispatch_fn([e.request for e in batch])
                except Exception as exc:
                    err = exc
                    st.status = "error"
        except BaseException as exc:
            for entry in batch:
                if not entry.fut.done():
                    entry.fut.set_exception(exc)
            self._end_pipelined()
            self._annotate(trace_since, len(batch))
            raise
        self._link_followers(batch, st)
        return batch, handle, err, trace_since

    def _end_pipelined(self) -> None:
        with self._q_lock:
            self._inflight -= 1
        self._depth_sem.release()

    def _finish_pipelined(self, batch: List[_QueueEntry], handle,
                          err: Optional[Exception],
                          trace_since: Optional[int]) -> None:
        """Finalize stage (OUTSIDE the run lock): device sync + host
        post-processing (`serving.device_sync`). Runs concurrently with
        the next batch's dispatch stage."""
        released = False
        results = None
        try:
            if err is None:
                st = self._batch_stage(batch, "serving.device_sync",
                                       "batcher-finalize")
                try:
                    with st:
                        try:
                            results = self._finalize_fn(handle)
                            self._check_results(batch, results)
                        except Exception as exc:
                            err = exc
                            st.status = "error"
                except BaseException as exc:
                    for entry in batch:
                        if not entry.fut.done():
                            entry.fut.set_exception(exc)
                    raise
                self._link_followers(batch, st)
            if err is None:
                self._set_results(batch, results)
            else:
                # serial retries re-enter the FULL sync executor
                # (dispatch + finalize) — take the scheduler lock so
                # they serialize with other dispatch stages exactly like
                # a sync batch (executor plan caches/stats assume
                # dispatch stages never run concurrently). Release this
                # batch's depth slot FIRST: a runner can block on the
                # slot while holding the run lock, so retrying while
                # still holding it would deadlock at async_depth=1.
                self._end_pipelined()
                released = True
                with self._run_lock:
                    self._retry_serially(batch, err)
        finally:
            if not released:
                self._end_pipelined()
            self._annotate(trace_since, len(batch))

    def batch_meta(self) -> dict:
        """Schedule metadata of the batch THIS thread is currently
        executing (set just before the executor runs): coalesced size and
        the longest queue wait among its members. Executors fold it into
        per-request observability (profile.hybrid queue_wait). CONSUMED
        on read — a poisoned batch's serial retries re-enter the
        executor on this same thread and must not re-count the dead
        batch's schedule metadata. Empty off a runner thread."""
        meta = getattr(self._tls, "meta", None)
        self._tls.meta = None
        return dict(meta or {})

    def _run_once(self, entry: Optional[_QueueEntry] = None) -> None:
        """One scheduler turn: drain + top up + serve a batch (if any).
        With `entry`, returns immediately once that entry is claimed or
        done instead of competing to run someone else's batch."""
        pending = None
        with self._run_lock:
            if entry is not None and (entry.fut.done() or entry.claimed):
                return
            # run lock taken -> the batch is fixed (the top-up's bounded
            # wait for late arrivals included); one a scheduler turn
            with _stage("serving.batch_form"):
                batch = self._drain()
                if batch:
                    batch = self._topup(batch)
            if not batch:
                return
            self.sched["batches"] += 1
            self.sched["requests"] += len(batch)
            now = time.monotonic()
            self._tls.meta = {
                "coalesced": len(batch),
                "queue_wait_max_nanos": int(max(
                    (now - e.enqueued) for e in batch) * 1e9)}
            # the dispatch and finalize stages name their sections
            # (`»batcher-drain`, `»batcher-finalize`) on the borrowed
            # runner thread, so `_nodes/hot_threads` attributes a busy
            # stack to the batcher instead of to whichever client thread
            # happened to become the runner
            if self._dispatch_fn is not None:
                self.sched["pipelined_batches"] += 1
                pending = self._begin_pipelined(batch)
            else:
                self._run_sync(batch)
        if pending is not None:
            self._finish_pipelined(*pending)

    def submit(self, request, deadline_at: Optional[float] = None):
        fut: Future = Future()
        entry = self._enqueue(request, fut, deadline_at=deadline_at)
        while not fut.done():
            if entry.claimed:
                # a runner owns this request; its finalize (possibly on
                # another thread) will set the future
                break
            # block until the current runner releases the dispatch lock,
            # then take over if our request still isn't scheduled
            self._run_once(entry)
        return fut.result()


class BoundedBatcher(CombiningBatcher):
    """CombiningBatcher + admission control: the p99-tail fix.

    The r03 record's 1.1–2.5 s p99 tails (15–30× p50) came from exactly
    this queue growing without bound under closed-loop overload — every
    request eventually served, each behind an ever-longer convoy. A
    production serving path sheds instead (the reference's
    EsRejectedExecutionHandler / `ThreadPool.java:129` bounded queues →
    HTTP 429):

    * depth limit — a submit that finds `max_queue_depth` requests already
      waiting is rejected immediately with `EsRejectedExecutionError`
      (HTTP 429 through the existing error mapping); the client retries
      against a queue that can still absorb it.
    * deadline — every request carries `enqueue + deadline_ms` as its
      schedule deadline: the queue orders earliest-deadline-first and the
      scheduler sheds a request the moment it can no longer be served in
      time (at drain AND during top-up claims), rather than spending
      device time on an answer nobody reads.

    `stats` counts shed requests and tracks the high-water queue depth so
    saturation tests can assert the bound actually held.
    """

    def __init__(self, execute: Optional[Callable[[Sequence], List]],
                 max_batch: int = 256, max_queue_depth: int = 256,
                 deadline_ms: Optional[float] = None,
                 warmup: Optional[Callable[[], None]] = None, **kwargs):
        super().__init__(execute, max_batch=max_batch, **kwargs)
        self.max_queue_depth = max_queue_depth
        self.deadline_ms = deadline_ms
        self.stats = {"accepted": 0, "rejected_depth": 0,
                      "shed_deadline": 0, "shed_cancelled": 0,
                      "max_depth_seen": 0}
        if warmup is not None:
            # warmup-at-start: pre-compile the dispatch bucket grid off
            # the critical path, so the queue's first drained batch finds
            # its program compiled instead of stalling behind XLA
            threading.Thread(target=self._run_warmup, args=(warmup,),
                             daemon=True, name="batcher-warmup").start()

    @staticmethod
    def _run_warmup(warmup: Callable[[], None]) -> None:
        try:
            warmup()
        except Exception as exc:
            # a warmup failure must never take down admission — but a
            # silent one is indistinguishable from warmup-disabled while
            # first batches stall behind the compiles warmup exists to
            # absorb, so leave a trace
            import logging
            logging.getLogger("elasticsearch_tpu.serving").warning(
                "hybrid batcher warmup failed (first batches will pay "
                "compiles): %s", exc)

    def _deadline_for(self, now: float) -> Optional[float]:
        if self.deadline_ms is None:
            return None
        return now + self.deadline_ms / 1000.0

    def _shed(self, entry: _QueueEntry, now: float) -> None:
        self.stats["shed_deadline"] += 1
        self.sched["deadline_sheds"] += 1
        if not entry.fut.done():
            waited = (now - entry.enqueued) * 1000.0
            entry.fut.set_exception(EsRejectedExecutionError(
                f"rejected execution: request spent "
                f"{waited:.0f}ms queued, over the "
                f"{self.deadline_ms:.0f}ms admission deadline"))

    def _shed_cancelled(self, entry: _QueueEntry, now: float) -> None:
        self.stats["shed_cancelled"] += 1
        super()._shed_cancelled(entry, now)

    def _admit(self, depth: int, now: float) -> None:
        if depth >= self.max_queue_depth:
            self.stats["rejected_depth"] += 1
            raise EsRejectedExecutionError(
                f"rejected execution: hybrid search queue is full "
                f"[{depth} >= {self.max_queue_depth}] (queue capacity "
                f"{self.max_queue_depth})")
        self.stats["accepted"] += 1
        if depth + 1 > self.stats["max_depth_seen"]:
            self.stats["max_depth_seen"] = depth + 1
