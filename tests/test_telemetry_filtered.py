"""The stages and counters of filtered kNN (ISSUE 33), in
`telemetry.stage`'s one call form, always on:

    knn.filter_resolve        once a filtered search (the filter query to
                              sorted rows), never for an unfiltered one
    dispatch.mask_build       once a BATCH that carries a filter (a
                              scatter of each filter's rows, ISSUE 34),
                              inside `dispatch.prepare` on the
                              single-device route; never for a batch of
                              unfiltered requests
    knn.filtered_searches     requests that reached the store with filter
                              rows, `knn.filter_matched_rows` the sum of
                              their lengths, `dispatch.mask_bytes` the
                              bytes of mask handed to `device_put`
    dispatch.mask_scattered   of those requests, the ones whose rows were
                              written through the row map's locator;
                              `dispatch.mask_searched` the ones that took
                              the `np.isin` fallback (none here)

and the unfiltered route's stage counts are what they were.
"""

import numpy as np
import pytest

from elasticsearch_tpu.telemetry import TRACER, metrics

DIMS, ROWS = 8, 300
STAGES = ("knn.filter_resolve", "dispatch.mask_build", "dispatch.prepare",
          "dispatch.h2d", "dispatch.launch", "dispatch.sync_wait",
          "dispatch.d2h", "dispatch.land", "serving.device_dispatch",
          "serving.device_sync", "search.took")
COUNTERS = ("knn.filtered_searches", "knn.filter_matched_rows",
            "dispatch.mask_bytes", "dispatch.mask_scattered",
            "dispatch.mask_searched")


def _read():
    hist = {n: (metrics.histogram(n).count, metrics.histogram(n).sum_ns)
            for n in STAGES}
    return hist, {n: metrics.counter(n).value for n in COUNTERS}


def _delta(before):
    hist, count = _read()
    return ({n: hist[n][0] - before[0][n][0] for n in STAGES},
            {n: hist[n][1] - before[0][n][1] for n in STAGES},
            {n: count[n] - before[1][n] for n in COUNTERS})


@pytest.fixture()
def node(tmp_path):
    from elasticsearch_tpu.node import Node
    n = Node(str(tmp_path / "n"),
             settings={"telemetry.tracing.sample_rate": 0.0})
    n.create_index_with_templates("idx", settings={}, mappings={
        "properties": {"v": {"type": "dense_vector", "dims": DIMS,
                             "similarity": "l2_norm"},
                       "tags": {"type": "keyword"}}})
    rng = np.random.default_rng(33)
    ops = []
    for i in range(ROWS):
        ops.append({"index": {"_index": "idx", "_id": str(i)}})
        # "even" on every second row, "third" on every third: an array
        ops.append({"v": rng.integers(0, 256, DIMS).tolist(),
                    "tags": ["all"] + ["even"] * (i % 2 == 0)
                    + ["third"] * (i % 3 == 0)})
    n.bulk(ops)
    n.indices.get("idx").refresh()       # one refresh: one generation
    yield n
    n.close()


def _body(rng, tags=None):
    knn = {"field": "v", "k": 3, "num_candidates": 10,
           "query_vector": rng.integers(0, 256, DIMS).tolist()}
    if tags:
        knn["filter"] = {"bool": {"filter": [{"term": {"tags": t}}
                                             for t in tags]}}
    return {"size": 3, "_source": False, "knn": knn}


def test_a_filtered_search_records_each_stage_once(node):
    rng = np.random.default_rng(1)
    node.search("idx", _body(rng, ["even", "third"]))    # warm: compiles
    node.search("idx", _body(rng))
    before = _read()
    resp = node.search("idx", _body(rng, ["even", "third"]))
    assert len(resp["hits"]["hits"]) == 3
    assert all(int(h["_id"]) % 6 == 0 for h in resp["hits"]["hits"])
    counts, nanos, counters = _delta(before)
    for name in STAGES:
        assert counts[name] == 1, f"{name} recorded {counts[name]} times"
    # the mask is built inside dispatch.prepare, and uploaded in h2d
    assert 0 < nanos["dispatch.mask_build"] <= nanos["dispatch.prepare"]
    assert nanos["knn.filter_resolve"] <= nanos["search.took"]
    assert counters["knn.filtered_searches"] == 1
    assert counters["knn.filter_matched_rows"] == ROWS // 6
    store = node.indices.get("idx").shards[0].vector_store
    n_pad = store.field("v").corpus.matrix.shape[0]
    assert counters["dispatch.mask_bytes"] == 1 * n_pad    # a batch of one
    assert counters["dispatch.mask_scattered"] == 1
    assert counters["dispatch.mask_searched"] == 0


def test_an_unfiltered_search_records_neither(node):
    rng = np.random.default_rng(2)
    node.search("idx", _body(rng))                       # warm
    before = _read()
    for _ in range(3):
        assert len(node.search("idx", _body(rng))["hits"]["hits"]) == 3
    counts, _nanos, counters = _delta(before)
    assert counts["knn.filter_resolve"] == 0
    assert counts["dispatch.mask_build"] == 0
    assert all(v == 0 for v in counters.values()), counters
    # and the unfiltered route's own stages are what they were: once a
    # batch each
    for name in STAGES[2:]:
        assert counts[name] == 3, f"{name} recorded {counts[name]} times"


def test_the_counters_add_up_on_a_batch_of_mixed_requests(node):
    """One coalesced batch of four requests, two of them filtered, handed
    to the store as the batcher hands it: ONE mask build, the two
    filters' rows counted, the padded mask's bytes."""
    store = node.indices.get("idx").shards[0].vector_store
    rng = np.random.default_rng(3)
    evens = np.arange(0, ROWS, 2, dtype=np.int64)
    thirds = np.arange(0, ROWS, 3, dtype=np.int64)

    def reqs():
        qs = rng.integers(0, 256, (4, DIMS)).astype(np.float32)
        return [(qs[0], evens), (qs[1], None), (qs[2], thirds),
                (qs[3], None)]

    store.search_many("v", reqs(), 3)                    # warm
    before = _read()
    out = store.search_many("v", reqs(), 3)
    assert [len(rows) for rows, _ in out] == [3, 3, 3, 3]
    assert all(r % 2 == 0 for r in out[0][0])
    assert all(r % 3 == 0 for r in out[2][0])
    counts, _nanos, counters = _delta(before)
    assert counts["dispatch.mask_build"] == 1
    assert counts["dispatch.prepare"] == 1
    assert counts["knn.filter_resolve"] == 0    # the store got rows, no query
    assert counters["knn.filtered_searches"] == 2
    assert counters["knn.filter_matched_rows"] == len(evens) + len(thirds)
    from elasticsearch_tpu.ops import dispatch
    n_pad = store.field("v").corpus.matrix.shape[0]
    assert counters["dispatch.mask_bytes"] == \
        dispatch.bucket_queries(4) * n_pad
    assert counters["dispatch.mask_scattered"] == 2
    assert counters["dispatch.mask_searched"] == 0


def test_the_generational_fan_out_builds_its_masks_in_one_stage(node):
    """A second refresh makes a second generation: a filtered search then
    fans out a dispatch a generation, and still records ONE
    `dispatch.mask_build`; the uploaded bytes are both legs' masks."""
    rng = np.random.default_rng(4)
    node.bulk([{"index": {"_index": "idx", "_id": str(ROWS)}},
               {"v": rng.integers(0, 256, DIMS).tolist(),
                "tags": ["all", "even", "third"]}])
    node.indices.get("idx").refresh()
    node.search("idx", _body(rng, ["even"]))             # warm
    store = node.indices.get("idx").shards[0].vector_store
    assert store.last_knn_phases.get("engine") == "tpu_generational"
    before = _read()
    resp = node.search("idx", _body(rng, ["even"]))
    assert len(resp["hits"]["hits"]) == 3
    counts, _nanos, counters = _delta(before)
    assert counts["dispatch.mask_build"] == 1
    assert counts["knn.filter_resolve"] == 1
    assert counters["knn.filtered_searches"] == 1
    assert counters["knn.filter_matched_rows"] == ROWS // 2 + 1
    gens = store.field("v").gens.snapshot().generations
    assert len(gens) == 2
    assert counters["dispatch.mask_bytes"] == sum(g.n_pad for g in gens)
    # once a request, not once a generation
    assert [g.locator.form for g in gens] == ["contiguous", "contiguous"]
    assert counters["dispatch.mask_scattered"] == 1
    assert counters["dispatch.mask_searched"] == 0


def test_a_traced_filtered_search_hangs_the_new_spans_in_its_trace(node):
    """Through REST with `?trace=true`: the two stages are spans of the
    request's trace, the mask's under `dispatch.prepare`."""
    import json

    from elasticsearch_tpu.rest.actions import register_all
    from elasticsearch_tpu.rest.controller import RestController
    rest = RestController()
    register_all(rest, node)
    rng = np.random.default_rng(5)

    def search(query):
        raw = json.dumps(_body(rng, ["third"])).encode()
        status, resp = rest.dispatch("POST", "/idx/_search", query, raw,
                                     "application/json")
        assert status == 200 and len(resp["hits"]["hits"]) == 3

    search({})                                           # warm
    TRACER.clear()
    search({"trace": "true"})
    spans = {sp["name"]: sp
             for sp in TRACER.traces(node_id=node.node_id)[0]["spans"]}
    TRACER.clear()
    assert "knn.filter_resolve" in spans
    assert spans["dispatch.mask_build"]["parent_id"] == \
        spans["dispatch.prepare"]["span_id"]
