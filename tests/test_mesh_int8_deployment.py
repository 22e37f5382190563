"""The deployment `cohere-768-int8-mesh4` at a test's size, on 4 of the
virtual CPU devices: an int8 field sharded over a mesh that the operator
switched on (`search.mesh.enabled: true`).

(a) What the mesh serves is what the plain int8 reference of the benchmark
    (`benchmark/kinds/knn_int8_reference.py`, numpy alone) computes: the
    same ids as its exact int8 scan, scores inside the configuration's
    limit; and the reference cut into the shards' row ranges and merged is
    the uncut reference.
(b) The store keeps NO whole single-device copy of such a field: it is
    built on the first request that leaves the mesh (k deeper than a
    shard), counted, and that request is answered correctly. Without the
    explicit setting (auto) the copy is resident as before.
"""

import json
import os
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.data import Corpus  # noqa: E402
from benchmark.kinds import knn_int8_reference as reference  # noqa: E402
from elasticsearch_tpu import telemetry  # noqa: E402
from elasticsearch_tpu.ops import knn as knn_ops  # noqa: E402

pytestmark = pytest.mark.multidevice

ROWS, DIMS, K, SHARDS = 2048, 128, 10, 4
with open(os.path.join(REPO, "benchmark", "configs",
                       "cohere-768-int8-mesh4.json")) as _f:
    CONFIG = json.load(_f)


@pytest.fixture
def mesh4():
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    policy.configure(enabled=True, num_shards=SHARDS, min_rows=1)
    if policy.serving_mesh() is None:
        policy.reset(full=True)
        pytest.skip("needs 4 jax devices (forced-host-device-count)")
    yield policy
    policy.reset(full=True)


def _rows(seed):
    config = dict(CONFIG, dims=DIMS)
    corpus = Corpus(seed, config)
    docs = corpus.block_docs
    return corpus.rows([(b, docs) for b in range(ROWS // docs)])


def _node(rows):
    from elasticsearch_tpu.node import Node
    node = Node(tempfile.mkdtemp())
    node.create_index_with_templates(
        "m", settings={}, mappings={"properties": {"v": {
            "type": "dense_vector", "dims": DIMS, "similarity": "cosine",
            "index_options": {"type": "int8_flat"}}}})
    ops = []
    for i, vec in enumerate(rows.vectors.tolist()):
        ops.append({"index": {"_index": "m", "_id": str(i)}})
        ops.append({"v": vec})
    node.bulk(ops)
    node.indices.get("m").refresh()
    return node


def _search(node, vec, k):
    resp = node.search("m", {"size": k, "_source": False, "knn": {
        "field": "v", "query_vector": vec, "k": k, "num_candidates": k}})
    hits = resp["hits"]["hits"]
    return ([int(h["_id"]) for h in hits], [h["_score"] for h in hits])


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 28])
def test_the_mesh_serves_what_the_int8_reference_computes(mesh4, seed):
    rows = _rows(seed)
    queries, _ = rows.queries(0, 24)
    stated = reference.Int8Rows(rows)
    want_ids, want_cos = stated.scan(queries, K)
    node = _node(rows)
    try:
        for i, q in enumerate(queries.tolist()):
            ids, scores = _search(node, q, K)
            assert ids == want_ids[i].tolist()
            np.testing.assert_allclose(
                scores, reference.to_scores(want_cos[i]), rtol=0,
                atol=CONFIG["limits"]["score_rms_err"]["limit"])
        router = mesh4.stats()["router"]
        assert router["mesh"] == len(queries)
        assert router["single_device"] == 0
    finally:
        node.close()

    # the shards' own parts, merged, are the uncut reference: each shard
    # scans its contiguous quarter of the rows, the candidates are merged
    # by score with ties to the lower row (the lower shard)
    chunk = -(-ROWS // SHARDS)
    qn = reference.unit_queries(queries)
    levels, scale = reference.quantise(rows.unit, 127)
    parts_ids, parts_cos = [], []
    for s in range(SHARDS):
        lo, hi = s * chunk, min((s + 1) * chunk, ROWS)
        cos = (qn @ levels[lo:hi].T) * scale[None, lo:hi]
        top = np.argsort(-cos, axis=1, kind="stable")[:, :K]
        parts_ids.append(top + lo)
        parts_cos.append(np.take_along_axis(cos, top, axis=1))
    all_ids = np.concatenate(parts_ids, axis=1)
    all_cos = np.concatenate(parts_cos, axis=1)
    order = np.argsort(-all_cos, axis=1, kind="stable")[:, :K]
    assert np.array_equal(np.take_along_axis(all_ids, order, axis=1),
                          want_ids)
    assert np.array_equal(np.take_along_axis(all_cos, order, axis=1),
                          want_cos)


def test_no_whole_copy_until_a_request_leaves_the_mesh(mesh4):
    telemetry.REGISTRY.reset()
    rows = _rows(11)
    node = _node(rows)
    try:
        store = node.indices.get("m").shards[0].vector_store
        fc = store.field("v")
        assert fc.mesh_state is not None
        assert isinstance(fc.corpus, knn_ops.DeferredCorpus)
        assert not knn_ops.is_resident(fc.corpus)
        # it reads as its shape meanwhile, and holds no device bytes
        assert fc.corpus.matrix.shape == (ROWS, DIMS)
        assert str(fc.corpus.matrix.dtype) == "int8"
        assert store.segment_stats()["bytes"] == 0
        counters = telemetry.metrics.snapshot()["counters"]
        assert counters["mesh.single_device_fallbacks"] == 0

        queries, _ = rows.queries(0, 4)
        _search(node, queries[0].tolist(), K)
        assert not fc.corpus.built                  # the mesh answered
        counters = telemetry.metrics.snapshot()["counters"]
        assert counters["mesh.dispatches"] == 1
        # [S, Q, k] scores and ids: 4 shards x 1 query x k 10 x 8 bytes
        assert counters["mesh.collective_bytes"] == SHARDS * 1 * K * 8
        hist = telemetry.metrics.snapshot()["histograms"]
        assert hist["mesh.guard_wait"]["count"] == 1

        # k deeper than a shard's rows cannot merge losslessly: one device
        deep = fc.mesh_state.layout.rows_per_shard + 8
        ids, scores = _search(node, queries[1].tolist(), deep)
        assert fc.corpus.built
        assert store.segment_stats()["bytes"] > ROWS * DIMS
        counters = telemetry.metrics.snapshot()["counters"]
        assert counters["mesh.single_device_fallbacks"] == 1
        assert mesh4.stats()["router"]["reasons"] == {
            "knn_k_deeper_than_shard": 1}
        want_ids, want_cos = reference.Int8Rows(rows).scan(queries[1:2],
                                                           deep)
        assert ids == want_ids[0].tolist()
        np.testing.assert_allclose(
            scores, reference.to_scores(want_cos[0]), rtol=0,
            atol=CONFIG["limits"]["score_rms_err"]["limit"])
        # a second one builds nothing more
        _search(node, queries[2].tolist(), deep)
        counters = telemetry.metrics.snapshot()["counters"]
        assert counters["mesh.single_device_fallbacks"] == 1
    finally:
        node.close()


def test_the_copy_is_built_when_the_mesh_is_switched_off_later(mesh4):
    telemetry.REGISTRY.reset()
    rows = _rows(13)
    node = _node(rows)
    try:
        fc = node.indices.get("m").shards[0].vector_store.field("v")
        assert not knn_ops.is_resident(fc.corpus)
        queries, _ = rows.queries(0, 2)
        on_mesh = _search(node, queries[0].tolist(), K)
        mesh4.configure(enabled=False)
        off_mesh = _search(node, queries[0].tolist(), K)
        assert fc.corpus.built
        assert off_mesh[0] == on_mesh[0]
        np.testing.assert_allclose(off_mesh[1], on_mesh[1], rtol=0,
                                   atol=1e-6)
        assert telemetry.metrics.snapshot()["counters"][
            "mesh.single_device_fallbacks"] == 1
    finally:
        node.close()


def test_auto_mode_keeps_the_resident_copy():
    """Unset `search.mesh.enabled` (a mesh where devices allow): the
    single-device copy is uploaded at refresh as before, so the routes of
    deployments that never asked for the mesh are as they were."""
    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    policy.configure(num_shards=SHARDS, min_rows=1)
    try:
        assert policy.stats()["single_device_copy"] == "resident"
        node = _node(_rows(17))
        try:
            fc = node.indices.get("m").shards[0].vector_store.field("v")
            assert not isinstance(fc.corpus, knn_ops.DeferredCorpus)
            assert knn_ops.is_resident(fc.corpus)
        finally:
            node.close()
        policy.configure(enabled=True)
        assert policy.stats()["single_device_copy"] == "on_first_use"
    finally:
        policy.reset(full=True)


def test_the_mesh_grid_warms_every_rung_with_the_serving_precision(mesh4):
    from elasticsearch_tpu.ops import dispatch
    from elasticsearch_tpu.parallel.sharded_knn import ShardedFieldState
    from elasticsearch_tpu.vectors import store as vstore
    rng = np.random.default_rng(3)
    state = ShardedFieldState(
        rng.standard_normal((512, 32)).astype(np.float32),
        mesh4.serving_mesh(), "cosine", "int8")
    entries = state.warmup_entries(32, precision=vstore.SERVING_PRECISION)
    buckets = sorted({e[1][0].shape[0] for e in entries})
    assert buckets == [1, 8, 16, 32, 64] == list(
        dispatch.query_buckets_upto(64))
    assert {e[2]["precision"] for e in entries} == {"bf16"}
    assert {e[2]["k"] for e in entries} == {10, 100}
    assert dispatch.query_buckets_upto(1) == (1,)
    assert dispatch.query_buckets_upto(20) == (1, 8, 16, 32)
