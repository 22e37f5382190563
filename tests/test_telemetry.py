"""End-to-end request telemetry (elasticsearch_tpu/telemetry/).

The contracts ISSUE 14 ships:

* histogram math — fixed log2 buckets must reproduce numpy percentiles
  within one bucket (the `_nodes/stats telemetry` fidelity claim);
* single-node tracing — `?trace=true` / a `profile` body forces a trace
  whose spans cover REST parse, query, fetch, merge; the completed trace
  lands in the per-node ring (`GET _nodes/traces`);
* the async batcher — queue-wait/dispatch/sync spans survive the
  pipelined batcher, coalesced FOLLOWERS link to the leader's batch span
  instead of double-counting device time, and task cancellation sheds
  queued entries at EDF admission exactly like expired deadlines;
* cross-node tracing — the trace context rides the PR-12 deadline
  envelope, remote segments parent under the coordinator's per-leg
  spans, a dead node's leg is an ERROR span (never a leak), and the
  device-path attribution (queue wait / dispatch / device sync /
  hydrate) sums consistently inside the trace — with zero added
  recompiles (checked here) and zero new host syncs (the tpulint
  TPU002/TPU009 gate in test_tpulint.py covers the instrumented
  modules);
* `telemetry.stage` / `stage_done` (ISSUE 26) — the one call form: a
  histogram always, a span with its real start, parent and trace id
  where sampled, nothing else where not; waits across threads; the HTTP
  front's stages adopted by a trace that runs socket to socket; the
  device-starved clock exact on a hand-made sequence; one kNN batch's
  six dispatch stages inside the two outer ones;
* X-Opaque-ID — one header threads through tasks, traces, and slow-log
  breaches;
* REST/stats response shapes — `_tasks`, `_nodes/traces`,
  `_nodes/stats` telemetry + slowlog sections.
"""

import json
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.common.errors import TaskCancelledError
from elasticsearch_tpu.telemetry import TRACER, metrics
from elasticsearch_tpu.telemetry.metrics import (
    Histogram, bucket_index, percentile_from_counts,
)

DIMS = 4


@pytest.fixture(autouse=True)
def _fresh_tracer():
    TRACER.clear()
    prior = TRACER.sample_rate
    yield
    TRACER.configure(sample_rate=prior)
    TRACER.clear()


@pytest.fixture()
def node(tmp_path):
    from elasticsearch_tpu.node import Node
    n = Node(str(tmp_path / "n"),
             settings={"telemetry.tracing.sample_rate": 0.0})
    yield n
    n.close()


@pytest.fixture()
def rest(node):
    from elasticsearch_tpu.rest.actions import register_all
    from elasticsearch_tpu.rest.controller import RestController
    rc = RestController()
    register_all(rc, node)
    return rc


def _dispatch(rc, method, path, query=None, body=None, headers=None):
    raw = json.dumps(body).encode() if body is not None else b""
    return rc.dispatch(method, path, query or {}, raw,
                       "application/json", headers=headers)


def _seed(rc, index="idx", docs=8, vectors=False):
    props = {"a": {"type": "text"}, "n": {"type": "long"}}
    if vectors:
        props["v"] = {"type": "dense_vector", "dims": DIMS}
    st, _ = _dispatch(rc, "PUT", f"/{index}",
                      body={"mappings": {"properties": props}})
    assert st == 200
    rng = np.random.default_rng(5)
    for i in range(docs):
        doc = {"a": f"hello doc{i}", "n": i}
        if vectors:
            doc["v"] = rng.standard_normal(DIMS).tolist()
        st, _ = _dispatch(rc, "PUT", f"/{index}/_doc/{i}",
                          {"refresh": "true"}, doc)
        assert st in (200, 201)


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_one_log2_bucket_of_numpy():
    rng = np.random.default_rng(7)
    samples = np.exp(rng.normal(13.0, 2.0, size=5_000)).astype(np.int64)
    h = Histogram("t")
    for s in samples:
        h.record(int(s))
    for q in (0.50, 0.90, 0.99):
        ours = h.percentile(q)
        ref = float(np.percentile(samples, q * 100))
        assert abs(bucket_index(int(ours)) - bucket_index(int(ref))) <= 1, \
            f"q={q}: histogram {ours} vs numpy {ref}"


def test_histogram_snapshot_and_empty_percentiles():
    h = Histogram("t")
    assert h.percentile(0.99) == 0.0
    h.record(1000)
    snap = h.snapshot(raw=True)
    assert snap["count"] == 1 and snap["sum_nanos"] == 1000
    assert snap["max_nanos"] == 1000
    assert len(snap["counts"]) == metrics.N_BUCKETS
    assert percentile_from_counts(snap["counts"], 0.5) <= 1024


def test_registry_snapshot_shapes():
    reg = metrics.MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(2.5)
    reg.histogram("h").record(10)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"]["count"] == 1


# ---------------------------------------------------------------------------
# single-node tracing through REST
# ---------------------------------------------------------------------------

def test_forced_trace_spans_and_ring(rest, node):
    _seed(rest, docs=4)
    st, resp = _dispatch(rest, "POST", "/idx/_search", {"trace": "true"},
                         {"query": {"match": {"a": "hello"}}},
                         headers={"x-opaque-id": "op-7"})
    assert st == 200 and resp["hits"]["total"]["value"] == 4
    traces = TRACER.traces(node_id=node.node_id)
    assert len(traces) == 1
    tr = traces[0]
    assert tr["action"] == "indices:data/read/search"
    assert tr["opaque_id"] == "op-7"
    assert tr["took_ns"] > 0
    names = [s["name"] for s in tr["spans"]]
    for expected in ("rest.parse", "search.query", "search.fetch",
                     "search.merge", "search.took"):
        assert expected in names, f"{expected} missing from {names}"
    # a span's name is its histogram's: one name a boundary
    hists = metrics.REGISTRY.snapshot()["histograms"]
    for s in tr["spans"][1:]:
        assert s["name"] in hists, s["name"]
    # every span is closed (no leaks) and parents resolve inside the trace
    ids = {s["span_id"] for s in tr["spans"]}
    for s in tr["spans"]:
        assert s["dur_ns"] is not None, f"leaked span {s['name']}"
        assert s["parent_id"] is None or s["parent_id"] in ids


def test_profile_body_forces_trace_and_profile_trace_section(rest, node):
    _seed(rest, docs=4)
    st, resp = _dispatch(rest, "POST", "/idx/_search", {},
                         {"query": {"match_all": {}}, "profile": True})
    assert st == 200
    prof_trace = resp["profile"]["trace"]
    assert prof_trace["trace_id"]
    ring = TRACER.traces(node_id=node.node_id)
    assert ring and ring[0]["trace_id"] == prof_trace["trace_id"]


def test_unsampled_request_leaves_no_trace(rest, node):
    _seed(rest, docs=2)
    st, _ = _dispatch(rest, "POST", "/idx/_search", {},
                      {"query": {"match_all": {}}})
    assert st == 200
    assert TRACER.traces(node_id=node.node_id) == []


def test_sampling_is_deterministic_counter_based():
    TRACER.configure(sample_rate=0.5)
    decisions = [TRACER.should_sample() for _ in range(8)]
    assert decisions == [False, True] * 4


def test_search_took_histogram_records_without_tracing(rest):
    _seed(rest, docs=2)
    before = metrics.REGISTRY.histogram("search.took").count
    st, _ = _dispatch(rest, "POST", "/idx/_search", {},
                      {"query": {"match_all": {}}})
    assert st == 200
    assert metrics.REGISTRY.histogram("search.took").count == before + 1


# ---------------------------------------------------------------------------
# slow log + X-Opaque-ID
# ---------------------------------------------------------------------------

def test_slow_log_carries_opaque_trace_and_phases(rest, node):
    _seed(rest, docs=4)
    st, _ = _dispatch(rest, "PUT", "/idx/_settings",
                      body={"index.search.slowlog.threshold.query.warn":
                            "0ms"})
    assert st == 200
    st, _ = _dispatch(rest, "POST", "/idx/_search", {"trace": "true"},
                      {"query": {"match": {"a": "hello"}}},
                      headers={"x-opaque-id": "slow-1"})
    assert st == 200
    entry = node.search_slow_log.entries[-1]
    assert entry["index"] == "idx" and entry["level"] == "warn"
    assert entry["opaque_id"] == "slow-1"
    assert entry["trace_id"]
    assert entry["phases"]["query_nanos"] > 0
    assert isinstance(entry["top_spans"], list) and entry["top_spans"]
    # the attached trace id resolves in the ring
    ring_ids = {t["trace_id"] for t in TRACER.traces(node_id=node.node_id)}
    assert entry["trace_id"] in ring_ids


def test_nodes_stats_has_telemetry_and_slowlog_sections(rest, node):
    _seed(rest, docs=2)
    _dispatch(rest, "POST", "/idx/_search", {},
              {"query": {"match_all": {}}})
    st, resp = _dispatch(rest, "GET", "/_nodes/stats")
    assert st == 200
    section = resp["nodes"][node.node_id]["telemetry"]
    hist = section["histograms"]["search.took"]
    for key in ("count", "p50_nanos", "p90_nanos", "p99_nanos",
                "p999_nanos"):
        assert key in hist
    assert hist["count"] >= 1
    assert "tracing" in section and "sample_rate" in section["tracing"]
    slowlog = resp["nodes"][node.node_id]["indices"]["slowlog"]
    assert set(slowlog) == {"search", "indexing"}
    assert "count" in slowlog["search"]


def test_nodes_traces_endpoint_shape(rest, node):
    _seed(rest, docs=2)
    _dispatch(rest, "POST", "/idx/_search", {"trace": "true"},
              {"query": {"match_all": {}}})
    st, resp = _dispatch(rest, "GET", "/_nodes/traces", {"size": "10"})
    assert st == 200
    section = resp["nodes"][node.node_id]
    assert section["traces"], "ring empty after a forced trace"
    tr = section["traces"][0]
    assert {"trace_id", "node", "action", "spans"} <= set(tr)


def test_hybrid_slow_log_breach_carries_phases_without_profile(rest, node):
    _seed(rest, docs=6, vectors=True)
    st, _ = _dispatch(rest, "PUT", "/idx/_settings",
                      body={"index.search.slowlog.threshold.query.warn":
                            "0ms"})
    assert st == 200
    rng = np.random.default_rng(11)
    st, resp = _dispatch(
        rest, "POST", "/idx/_search", {},
        {"rank": {"rrf": {}},
         "query": {"match": {"a": "hello"}},
         "knn": {"field": "v",
                 "query_vector": rng.standard_normal(DIMS).tolist(),
                 "k": 3, "num_candidates": 3},
         "size": 3})
    assert st == 200
    # the private phases key never reaches the client...
    assert "_took_phases" not in resp
    # ...but the breach entry carries the device-path breakdown even
    # though the request never asked for profile
    entry = node.search_slow_log.entries[-1]
    assert entry["index"] == "idx"
    for key in ("plan_nanos", "device_dispatch_nanos",
                "device_sync_nanos", "hydrate_nanos"):
        assert key in entry["phases"], entry["phases"]


# ---------------------------------------------------------------------------
# tasks API
# ---------------------------------------------------------------------------

def test_tasks_api_lists_inflight_with_opaque_trace_and_current_span(
        rest, node):
    with telemetry.rest_request(node, "indices:data/read/search",
                                opaque_id="task-op", force_trace=True):
        st, resp = _dispatch(rest, "GET", "/_tasks")
        assert st == 200
        tasks = resp["nodes"][node.node_id]["tasks"]
        mine = [t for t in tasks.values()
                if t.get("headers", {}).get("X-Opaque-Id") == "task-op"]
        assert mine, f"in-flight task not listed: {tasks}"
        task = mine[0]
        assert task["action"] == "indices:data/read/search"
        assert task["running_time_in_nanos"] >= 0
        assert task["trace_id"]
        assert task["current_span"] == "indices:data/read/search"
    # unregistered after the request finishes
    st, resp = _dispatch(rest, "GET", "/_tasks")
    tasks = resp["nodes"][node.node_id]["tasks"]
    assert not [t for t in tasks.values()
                if t.get("headers", {}).get("X-Opaque-Id") == "task-op"]


def test_rest_cancel_all_sets_cancelled_flag(rest, node):
    task = node.tasks.register("indices:data/read/search", trace=None)
    try:
        st, resp = _dispatch(rest, "POST", "/_tasks/_cancel",
                             {"actions": "indices:data/read/*"})
        assert st == 200
        assert task.cancelled is True
        listed = resp["nodes"][node.node_id]["tasks"][task.task_id]
        assert listed["cancelled"] is True
    finally:
        node.tasks.unregister(task)


# ---------------------------------------------------------------------------
# the async batcher: spans, follower links, cancellation
# ---------------------------------------------------------------------------

def _drain_barrier_batcher(started, release):
    """A batcher whose executor blocks until `release` is set — queued
    entries pile up behind the in-flight batch."""
    from elasticsearch_tpu.serving.batcher import CombiningBatcher

    def execute(reqs):
        started.set()
        assert release.wait(10)
        return list(reqs)

    return CombiningBatcher(execute, max_batch=8, topup=False)


def test_cancellation_sheds_queued_entries_at_admission():
    started, release = threading.Event(), threading.Event()
    batcher = _drain_barrier_batcher(started, release)

    class Token:
        cancelled = False

    token = Token()
    results = {}

    def blocker():
        results["lead"] = batcher.submit("lead")

    lead = threading.Thread(target=blocker)
    lead.start()
    assert started.wait(10)

    def queued():
        with telemetry.use(task=token):
            try:
                results["q"] = batcher.submit("q")
            except TaskCancelledError as e:
                results["q_err"] = e

    qt = threading.Thread(target=queued)
    qt.start()
    # wait until the entry is actually queued, then cancel it
    deadline = time.monotonic() + 10
    while batcher.pending() == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert batcher.pending() == 1
    token.cancelled = True
    release.set()
    lead.join(10)
    qt.join(10)
    assert isinstance(results.get("q_err"), TaskCancelledError)
    assert batcher.sched["cancelled_sheds"] == 1
    assert results["lead"] == "lead"


def test_coalesced_follower_links_to_leader_batch_span():
    started, release = threading.Event(), threading.Event()
    batcher = _drain_barrier_batcher(started, release)
    leader_tr = TRACER.start("search", node_id="n", forced=True)
    follower_tr = TRACER.start("search", node_id="n", forced=True)
    out = {}

    def first():
        with telemetry.use(trace=leader_tr):
            out["a"] = batcher.submit("a")

    t1 = threading.Thread(target=first)
    t1.start()
    assert started.wait(10)

    def second():
        with telemetry.use(trace=follower_tr):
            out["b"] = batcher.submit("b")

    t2 = threading.Thread(target=second)
    t2.start()
    deadline = time.monotonic() + 10
    while batcher.pending() == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    release.set()
    t1.join(10)
    t2.join(10)
    assert out == {"a": "a", "b": "b"}
    TRACER.finish(leader_tr)
    TRACER.finish(follower_tr)
    # exactly one of the two traces carries the second batch's execute
    # span; the other links to it (never double-counts device time)
    all_spans = {sp.span_id: (tr, sp)
                 for tr in (leader_tr, follower_tr)
                 for sp in tr.spans}
    linked = [link for tr in (leader_tr, follower_tr)
              for link in tr.links if link["reason"] == "coalesced_follower"]
    if linked:   # both coalesced into one batch
        link = linked[0]
        assert link["span_id"] in all_spans
        owner, span = all_spans[link["span_id"]]
        assert span.attrs.get("coalesced", 0) >= 2
        assert owner.trace_id == link["trace_id"]
    else:        # scheduling served them as two singleton batches
        for tr in (leader_tr, follower_tr):
            assert any(sp.name == "serving.device_dispatch"
                       for sp in tr.spans)
    # queue waits are always per-request, never shared
    assert any(sp.name == "serving.queue_wait"
               for sp in follower_tr.spans)


# ---------------------------------------------------------------------------
# cross-node tracing on the 3-node simulator (fault harness active)
# ---------------------------------------------------------------------------

def _cluster(tmp_path, **kw):
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from test_fanout import FaultyCluster, _build
    c = FaultyCluster(tmp_path, n_nodes=3)
    _build(c, docs=12, shards=3, vectors=True)
    return c


def _traced_search(c, body):
    coord = c.nodes["n0"]
    tr = TRACER.start("indices:data/read/search", node_id="n0",
                      forced=True, opaque_id="xn-1")
    box = {}
    coord.client_search("docs", body,
                        on_done=lambda r: box.update(r=r),
                        telemetry_ctx=(tr, tr.root.span_id, None))
    assert c.run_until(lambda: "r" in box)
    TRACER.finish(tr)
    return tr, box["r"]


def test_cross_node_trace_parents_device_attribution_no_recompiles(
        tmp_path):
    from elasticsearch_tpu.ops import dispatch
    c = _cluster(tmp_path)
    try:
        rng = np.random.default_rng(3)
        body = {"knn": {"field": "v",
                        "query_vector": rng.standard_normal(DIMS).tolist(),
                        "k": 3, "num_candidates": 6},
                "size": 3}
        # warm pass: compiles happen here, not in the traced request
        _traced_search(c, dict(body))
        TRACER.clear()
        compiles_before = dispatch.DISPATCH.compile_count()
        tr, resp = _traced_search(c, dict(body))
        assert resp["_shards"]["failed"] == 0
        # acceptance: ZERO added recompiles from tracing the request
        assert dispatch.DISPATCH.compile_count() == compiles_before
        spans = tr.span_dicts()
        by_id = {s["span_id"]: s for s in spans}
        names = [s["name"] for s in spans]
        # coordinator spans
        assert "phase.query" in names and "phase.fetch" in names
        # per-leg spans for all three shards, remote segments under them
        legs = [s for s in spans if s["name"] == "fanout.leg"
                and s["attrs"]["phase"] == "query"]
        assert len(legs) == 3
        remote_roots = [s for s in spans
                        if s["name"].startswith("shard.query[")]
        assert len(remote_roots) == 3
        leg_ids = {s["span_id"] for s in legs}
        for rr in remote_roots:
            assert rr["parent_id"] in leg_ids, \
                "remote segment must parent under its coordinator leg"
        # device-path attribution spans from the remote batcher
        assert "serving.queue_wait" in names
        assert "serving.device_dispatch" in names
        assert "shard.hydrate" in names
        # every span closed; parents resolve; attribution is consistent:
        # each child's duration fits inside the request window
        root_dur = tr.took_ns
        for s in spans:
            assert s["dur_ns"] is not None, f"leaked span {s['name']}"
            assert s["parent_id"] is None or s["parent_id"] in by_id
            assert s["dur_ns"] <= root_dur * 2 + 50_000_000
        # per-leg attribution sums to (within slack) the leg's own span
        for rr in remote_roots:
            children = [s for s in spans if s["parent_id"] == rr["span_id"]]
            assert children, "remote segment carries no attribution"
            assert sum(s["dur_ns"] for s in children) <= \
                rr["dur_ns"] + 50_000_000
    finally:
        c.stop()


def test_cross_node_dead_node_leg_is_error_span_not_a_leak(tmp_path):
    c = _cluster(tmp_path)
    try:
        # warm once so the kill window only covers the traced request
        _traced_search(c, {"query": {"match_all": {}}, "size": 3})
        victim = [nid for nid in c.nodes if nid != "n0"][0]
        c.faults.kill_node(victim)
        tr, resp = _traced_search(
            c, {"query": {"match_all": {}}, "size": 3,
                "timeout": "2s"})
        assert resp["_shards"]["failed"] >= 1
        spans = tr.span_dicts()
        bad = [s for s in spans if s["name"] == "fanout.leg"
               and s["attrs"]["node"] == victim]
        assert bad, "dead node's leg span missing"
        assert bad[0]["dur_ns"] is not None, "dead node's leg span leaked"
        assert bad[0]["status"] != "ok"
        # the phase still completed and every span closed
        assert all(s["dur_ns"] is not None for s in spans)
    finally:
        c.stop()


def test_remote_segments_land_in_their_own_nodes_ring(tmp_path):
    c = _cluster(tmp_path)
    try:
        tr, _resp = _traced_search(
            c, {"query": {"match_all": {}}, "size": 3})
        data_nodes = [nid for nid in c.nodes if nid != "n0"]
        remote = [t for nid in data_nodes
                  for t in TRACER.traces(node_id=nid)]
        assert remote, "data nodes recorded no segments"
        assert all(t["trace_id"] == tr.trace_id for t in remote
                   if t["opaque_id"] == "xn-1")
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# telemetry.stage / stage_done: the one call form (ISSUE 26)
# ---------------------------------------------------------------------------

def _hist(name):
    h = metrics.REGISTRY.histogram(name)
    return h.count, h.sum_ns


def _responses():
    """(written by a pool worker, written by the loop)."""
    c = metrics.REGISTRY.snapshot()["counters"]
    return c["http.responses.worker"], c["http.responses.loop"]


def test_stage_feeds_histogram_and_sampled_span_with_real_start():
    tr = TRACER.start("act", node_id="n", forced=True)
    before = {n: _hist(n) for n in ("t26.outer", "t26.inner")}
    t_in = time.monotonic_ns()
    with telemetry.use(trace=tr):
        with telemetry.stage("t26.outer", batch=3) as outer:
            time.sleep(0.002)
            with telemetry.stage("t26.inner") as inner:
                time.sleep(0.001)
    t_out = time.monotonic_ns()
    TRACER.finish(tr)
    # (a) the histogram of the same name, once each, the stage's duration
    for name, st in (("t26.outer", outer), ("t26.inner", inner)):
        count, total = _hist(name)
        assert count == before[name][0] + 1
        assert total - before[name][1] == st.nanos
    # (b) the span: real start, end, parent, the request's trace id
    spans = {sp["name"]: sp for sp in tr.span_dicts()}
    o, i = spans["t26.outer"], spans["t26.inner"]
    assert o["span_id"] == outer.span_id and i["span_id"] == inner.span_id
    assert o["parent_id"] == tr.root.span_id
    assert i["parent_id"] == o["span_id"], "nested stage hangs under it"
    assert t_in <= o["start_ns"] == outer.start_ns <= i["start_ns"]
    assert i["start_ns"] + i["dur_ns"] <= o["start_ns"] + o["dur_ns"] <= t_out
    assert o["dur_ns"] == outer.nanos >= 3_000_000
    assert o["attrs"] == {"batch": 3}
    assert TRACER.traces()[0]["trace_id"] == tr.trace_id
    # the thread's context is back where it was
    assert telemetry.current_trace() is None


def test_stage_unsampled_feeds_the_histogram_and_nothing_else():
    before = _hist("t26.unsampled")
    snap = TRACER.snapshot()
    with telemetry.stage("t26.unsampled") as st:
        pass
    assert _hist("t26.unsampled")[0] == before[0] + 1
    assert st.span_id is None
    assert TRACER.snapshot() == snap and TRACER.traces() == []
    # an explicit unsampled context never falls back to the thread's trace
    tr = TRACER.start("act", node_id="n", forced=True)
    with telemetry.use(trace=tr):
        with telemetry.stage("t26.unsampled", ctx=(None, None, None)) as st:
            pass
        assert telemetry.stage_done("t26.unsampled", 1, 2,
                                    (None, None, None)) is None
    assert st.span_id is None and len(tr.spans) == 1


def test_stage_error_and_status_are_filed():
    tr = TRACER.start("act", node_id="n", forced=True)
    with telemetry.use(trace=tr):
        with pytest.raises(ValueError):
            with telemetry.stage("t26.raises"):
                raise ValueError("boom")
        with telemetry.stage("t26.soft") as st:
            st.status = "timeout"
    by = {sp.name: sp for sp in tr.spans}
    assert by["t26.raises"].status == "error"
    assert by["t26.raises"].dur_ns is not None
    assert by["t26.soft"].status == "timeout"
    assert telemetry.current_span_id() is None


def test_stage_section_tags_the_thread_for_hot_threads():
    t = threading.current_thread()
    name = t.name
    with telemetry.stage("t26.section", section="batcher-drain"):
        assert t.name == name + "\u00bbbatcher-drain"
    assert t.name == name


def test_stage_done_files_a_wait_that_ends_on_another_thread():
    tr = TRACER.start("act", node_id="n", forced=True)
    box = {}
    with telemetry.use(trace=tr):
        ctx = telemetry.capture()        # on the submitting thread
        start_ns = time.monotonic_ns()
    before = _hist("t26.wait")

    def other():
        time.sleep(0.002)
        end_ns = time.monotonic_ns()
        box["end"] = end_ns
        box["thread_ctx"] = telemetry.current_trace()
        box["id"] = telemetry.stage_done("t26.wait", start_ns, end_ns, ctx,
                                         queue="q")

    th = threading.Thread(target=other)
    th.start()
    th.join(10)
    assert box["thread_ctx"] is None     # the tuple carried it, no thread-local
    count, total = _hist("t26.wait")
    assert count == before[0] + 1
    assert total - before[1] == box["end"] - start_ns
    sp = [sp for sp in tr.spans if sp.name == "t26.wait"][0]
    assert sp.span_id == box["id"]
    assert sp.start_ns == start_ns, "the real start, not now - duration"
    assert sp.dur_ns == box["end"] - start_ns
    assert sp.parent_id == tr.root.span_id
    assert sp.attrs == {"queue": "q"}


def test_stage_done_takes_a_span_id_handed_out_beforehand():
    tr = TRACER.start("act", node_id="n", forced=True)
    early = telemetry.new_span_id()
    got = telemetry.stage_done("t26.leg", 10, 30,
                               (tr, tr.root.span_id, None),
                               status="timed_out", span_id=early)
    assert got == early
    sp = tr.spans[-1]
    assert (sp.span_id, sp.status, sp.start_ns, sp.dur_ns) == \
        (early, "timed_out", 10, 20)


def test_front_files_its_marks_as_five_stages_socket_to_socket(node):
    """What the HTTP server does around a handler, by hand: clock marks
    on the request's front, the handler's `rest_request` adopting the
    front when it samples the request, and `finish` filing the five
    stages (and the trace, socket to socket) once the response is out."""
    names = ("http.keepalive_gap", "http.read", "http.pool_wait",
             "rest.handle", "http.loop_wake", "http.respond")
    before = {n: _hist(n) for n in names}
    t0 = time.monotonic_ns()
    front = telemetry.Front(t0 - 40, t0)
    front.read_ns = t0 + 3
    front.submit_ns = t0 + 5
    with front:                          # on the pool's worker
        p0 = time.monotonic_ns()
        with telemetry.rest_request(node, "indices:data/read/search",
                                    force_trace=True,
                                    parsed=(p0, p0 + 7)) as tr:
            assert tr is not None and front.trace is tr
            telemetry.stage_done("search.took", p0 + 8, p0 + 20)
        # the handler is done, the response is not: still open
        assert TRACER.traces() == []
    assert telemetry.trace_mod._CTX.front is None
    front.wake_ns = front.return_ns + 11
    end = front.wake_ns + 13
    front.finish(end)
    durations = {"http.keepalive_gap": 40, "http.read": 3,
                 "http.pool_wait": front.handle_ns - (t0 + 5),
                 "rest.handle": front.return_ns - front.handle_ns,
                 "http.loop_wake": 11, "http.respond": 13}
    for n in names:
        count, total = _hist(n)
        assert count == before[n][0] + 1, n
        assert total - before[n][1] == durations[n], n
    ring = TRACER.traces(node_id=node.node_id)
    assert len(ring) == 1 and ring[0]["trace_id"] == tr.trace_id
    spans = {sp["name"]: sp for sp in ring[0]["spans"]}
    root = spans["indices:data/read/search"]
    assert root["start_ns"] == t0, "the trace starts with the request line"
    assert root["start_ns"] + root["dur_ns"] == end, "socket to socket"
    assert "http.keepalive_gap" not in spans   # it precedes the request
    for name in names[1:]:
        assert spans[name]["parent_id"] == root["span_id"], name
        assert spans[name]["dur_ns"] == durations[name]
    handle = spans["rest.handle"]
    assert spans["rest.parse"]["parent_id"] == handle["span_id"]
    assert spans["search.took"]["parent_id"] == handle["span_id"]
    assert spans["http.pool_wait"]["start_ns"] == t0 + 5


def test_front_unsampled_or_refused_request_records_histograms_only(node):
    names = ("http.read", "http.pool_wait", "rest.handle",
             "http.loop_wake", "http.respond")
    before = {n: _hist(n)[0] for n in names}
    t0 = time.monotonic_ns()
    front = telemetry.Front(t0 - 1, t0)
    front.read_ns = front.submit_ns = t0 + 1
    with front:
        with telemetry.rest_request(node, "indices:data/read/search") as tr:
            assert tr is None
    front.wake_ns = front.return_ns + 1
    front.finish(front.wake_ns + 1)
    assert [_hist(n)[0] - before[n] for n in names] == [1, 1, 1, 1, 1]
    # a request the pool refused (429) never had a worker: read and
    # respond only
    refused = telemetry.Front(t0, t0 + 1)
    refused.read_ns = refused.submit_ns = t0 + 2
    refused.finish(t0 + 9)
    assert [_hist(n)[0] - before[n] for n in names] == [2, 1, 1, 1, 2]
    assert TRACER.traces() == []


def test_http_server_traces_a_request_socket_to_socket(rest, node):
    """The real front: one keep-alive connection, an unsampled request
    then a forced one. Every boundary of the front is in the histograms
    for both and in the trace of the second."""
    import asyncio
    import http.client

    from elasticsearch_tpu.rest.http_server import HttpServer

    _seed(rest, docs=4)
    server = HttpServer(rest, host="127.0.0.1", port=0,
                        thread_pool=node.thread_pool)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(15)
    names = ("http.read", "http.pool_wait", "rest.handle",
             "http.loop_wake", "http.respond")
    before = {n: _hist(n)[0] for n in names + ("search.took",)}
    responses = _responses()
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    try:
        body = json.dumps({"query": {"match": {"a": "hello"}}})
        for path in ("/idx/_search", "/idx/_search?trace=true"):
            conn.request("POST", path, body,
                         {"content-type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["hits"]["total"]["value"] == 4
    finally:
        conn.close()
        loop.call_soon_threadsafe(loop.stop)
    deadline = time.monotonic() + 10
    while (not TRACER.traces(node_id=node.node_id)
           or _hist("http.respond")[0] < before["http.respond"] + 2) \
            and time.monotonic() < deadline:
        time.sleep(0.01)       # finished on the worker, after the response
    for n in names + ("search.took",):
        assert _hist(n)[0] == before[n] + 2, n
    # plain TCP, answers under a kilobyte: both written by their workers
    assert _responses() == (responses[0] + 2, responses[1])
    ring = TRACER.traces(node_id=node.node_id)
    assert len(ring) == 1, "only the forced request is traced"
    spans = {sp["name"]: sp for sp in ring[0]["spans"]}
    root = spans["indices:data/read/search"]
    for n in names:
        assert spans[n]["parent_id"] == root["span_id"], n
    handle = spans["rest.handle"]
    for n in ("rest.parse", "search.took", "search.query"):
        assert spans[n]["parent_id"] == handle["span_id"], n
    # in the order a request lives through them, on one clock
    order = [spans[n]["start_ns"] for n in
             ("http.read", "http.pool_wait", "rest.handle", "rest.parse",
              "search.took", "http.loop_wake", "http.respond")]
    assert order == sorted(order) and order[0] >= root["start_ns"]
    assert ring[0]["took_ns"] >= sum(
        spans[n]["dur_ns"] for n in names) * 0.999
    # the last two stages, filed once a request by the thread that
    # responded: each starts where the one before it ended, and the trace
    # ends with the bytes handed to the socket
    wake, respond = spans["http.loop_wake"], spans["http.respond"]
    assert wake["dur_ns"] >= 0 and respond["dur_ns"] > 0
    assert wake["start_ns"] == handle["start_ns"] + handle["dur_ns"]
    assert respond["start_ns"] == wake["start_ns"] + wake["dur_ns"]
    assert root["start_ns"] + root["dur_ns"] == \
        respond["start_ns"] + respond["dur_ns"], "socket to socket"


def test_idle_clock_books_each_gap_to_its_cause_exactly():
    from elasticsearch_tpu.serving.batcher import (
        IDLE_NO_REQUEST, IDLE_PICKUP, IdleClock)

    def read():
        c = metrics.REGISTRY.snapshot()["counters"]
        return c[IDLE_NO_REQUEST], c[IDLE_PICKUP]

    clock = IdleClock()
    clock.ensure_counters()
    nr0, pu0 = read()

    def booked():
        nr, pu = read()
        return nr - nr0, pu - pu0

    # start-up is no gap: nothing was ever in flight
    clock.waiting(50)
    clock.begin(100)
    assert booked() == (0, 0)
    # a request that queues while a batch is in flight, picked up after
    # the batch has landed: the whole gap is pickup
    clock.waiting(150)
    clock.end(200)
    clock.begin(260)
    assert booked() == (0, 60)
    # nobody asks for a while, then a request waits 30 for its runner
    clock.end(300)
    clock.waiting(400)
    clock.waiting(410)              # only the first counts
    clock.begin(430)
    assert booked() == (100, 90)
    # overlapped batches: no edge until the last one lands
    clock.begin(440)
    clock.end(450)
    assert booked() == (100, 90)
    clock.end(500)
    # a dispatch nobody announced (a caller that brought its own batch)
    clock.begin(520)
    assert booked() == (120, 90)
    # a reader flushes the open gap up to its read and the rest follows
    clock.end(600)
    clock.flush(650)
    assert booked() == (170, 90)
    clock.waiting(660)
    clock.flush(680)
    assert booked() == (180, 110)
    clock.begin(700)
    assert booked() == (180, 130)
    clock.flush(800)                # a batch is in flight: nothing to book
    assert booked() == (180, 130)


def test_knn_batch_six_stages_split_the_two_outer_ones(rest, node):
    """One kNN search through REST on the CPU: the four histograms the
    benchmark reads are recorded once a request / once a batch, and
    prepare + h2d + launch (sync_wait + d2h + land) lie inside
    serving.device_dispatch (serving.device_sync), within the overhead
    of the calls around them."""
    # one refresh, so one generation: the plain single-device route (a
    # field of several generations fans out a dispatch each)
    st, _ = _dispatch(rest, "PUT", "/idx", body={"mappings": {"properties": {
        "v": {"type": "dense_vector", "dims": DIMS}}}})
    assert st == 200
    rng = np.random.default_rng(2)
    for i in range(8):
        st, _ = _dispatch(rest, "PUT", f"/idx/_doc/{i}", {},
                          {"v": rng.standard_normal(DIMS).tolist()})
        assert st in (200, 201)
    assert _dispatch(rest, "POST", "/idx/_refresh")[0] == 200

    def body():
        return {"knn": {"field": "v", "k": 3, "num_candidates": 8,
                        "query_vector": rng.standard_normal(DIMS).tolist()},
                "size": 3}

    st, _ = _dispatch(rest, "POST", "/idx/_search", {}, body())
    assert st == 200               # warm: the compile is not a stage's
    dispatch3 = ("dispatch.prepare", "dispatch.h2d", "dispatch.launch")
    sync3 = ("dispatch.sync_wait", "dispatch.d2h", "dispatch.land")
    outer = ("search.took", "serving.queue_wait", "serving.device_dispatch",
             "serving.device_sync", "serving.batch_form")
    before = {n: _hist(n) for n in dispatch3 + sync3 + outer}
    st, resp = _dispatch(rest, "POST", "/idx/_search", {"trace": "true"},
                         body())
    assert st == 200 and len(resp["hits"]["hits"]) == 3
    d = {n: (_hist(n)[0] - before[n][0], _hist(n)[1] - before[n][1])
         for n in before}
    for n in before:
        assert d[n][0] == 1, f"{n} recorded {d[n][0]} times"
    slack = 2_000_000              # the calls between the stages, on a CPU
    inner = sum(d[n][1] for n in dispatch3)
    assert inner <= d["serving.device_dispatch"][1] <= inner + slack
    inner = sum(d[n][1] for n in sync3)
    assert inner <= d["serving.device_sync"][1] <= inner + slack
    # and as spans: the six hang under the two, on the request's trace
    spans = {sp["name"]: sp
             for sp in TRACER.traces(node_id=node.node_id)[0]["spans"]}
    for group, parent in ((dispatch3, "serving.device_dispatch"),
                          (sync3, "serving.device_sync")):
        for n in group:
            assert spans[n]["parent_id"] == spans[parent]["span_id"], n
    assert spans["serving.queue_wait"]["start_ns"] <= \
        spans["serving.device_dispatch"]["start_ns"]
