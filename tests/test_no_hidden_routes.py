"""Nothing on the serving path may answer from another route behind the
caller's back: a device error fails the shard the response reports, a
cost-model input that cannot be had raises, a mesh that was asked for
and cannot be built raises, and one engine scores vectors: the device."""

import ast
import inspect
import pathlib
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import SearchPhaseExecutionError
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops

PACKAGE = pathlib.Path(knn_ops.__file__).resolve().parents[1]


@pytest.fixture()
def node():
    from elasticsearch_tpu.node import Node
    n = Node(tempfile.mkdtemp())
    mappings = {"properties": {
        "v": {"type": "dense_vector", "dims": 8, "similarity": "cosine"},
        "tag": {"type": "keyword"}, "n": {"type": "long"}}}
    rng = np.random.default_rng(0)
    for name in ("a", "b"):
        n.create_index_with_templates(name, mappings=mappings)
        for i in range(40):
            n.index_doc(name, str(i), {
                "v": rng.standard_normal(8).tolist(),
                "tag": "x" if i % 2 else "y", "n": i})
        n.indices.get(name).refresh()
    yield n, rng
    n.close()


def _knn_body(rng):
    return {"size": 3, "request_cache": False,
            "knn": {"field": "v", "k": 3, "num_candidates": 10,
                    "query_vector": rng.standard_normal(8).tolist()}}


def _break_device_knn(monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel (injected)")
    monkeypatch.setattr(knn_ops, "knn_search_auto", boom)


def test_knn_kernel_error_is_a_failed_shard_not_a_slower_answer(
        node, monkeypatch):
    n, rng = node
    body = _knn_body(rng)
    assert n.search("a", body)["_shards"]["failed"] == 0
    _break_device_knn(monkeypatch)
    with pytest.raises(SearchPhaseExecutionError) as ei:
        n.search("a", body)
    err = ei.value.to_wrapped_dict()
    assert err["reason"] == "all shards failed"
    (failed,) = err["failed_shards"]
    assert failed["index"] == "a" and failed["shard"] == 0
    assert "Mosaic failed to compile" in failed["reason"]["reason"]


def test_failed_index_is_reported_beside_the_other_indexs_hits(
        node, monkeypatch):
    """Two indices, one broken field store: the response carries the
    healthy index's hits and names the failed shard."""
    n, rng = node
    body = _knn_body(rng)
    store_a = n.indices.get("a").shards[0].vector_store

    def boom(*a, **kw):
        raise RuntimeError("device dispatch died (injected)")
    monkeypatch.setattr(store_a, "search", boom)
    resp = n.search("a,b", body)
    assert resp["_shards"]["failed"] == 1
    assert resp["_shards"]["failures"][0]["index"] == "a"
    assert "device dispatch died" in \
        resp["_shards"]["failures"][0]["reason"]["reason"]
    assert resp["hits"]["hits"]
    assert {h["_index"] for h in resp["hits"]["hits"]} == {"b"}


def test_device_agg_error_fails_the_shard_and_is_counted(node, monkeypatch):
    n, _rng = node
    n.settings["search.aggs.cost_router"] = "false"
    body = {"size": 0, "request_cache": False,
            "aggs": {"t": {"terms": {"field": "tag"}}}}
    ok = n.search("a", body)
    assert {b["key"]: b["doc_count"]
            for b in ok["aggregations"]["t"]["buckets"]} \
        == {"x": 20, "y": 20}
    engine = n._agg_engine(n.indices.get("a"))
    assert engine.stats["device_nodes"] >= 1

    def boom(*a, **kw):
        raise RuntimeError("agg dispatch died (injected)")
    monkeypatch.setattr(engine, "_run_device_node", boom)
    with pytest.raises(SearchPhaseExecutionError) as ei:
        n.search("a", body)
    assert "agg dispatch died" in \
        ei.value.shard_failures[0]["reason"]["reason"]
    assert engine.stats["fallback_reasons"]["device_error"]["count"] == 1
    # ... and the host walker did NOT quietly answer it
    assert engine.stats["host_nodes"] == 0


def test_one_engine_scores_vectors(node):
    """There is no second kNN engine on the host: no mirror beside a
    synced bf16 field, no module, no native symbol, no option."""
    import importlib

    from elasticsearch_tpu import native
    from elasticsearch_tpu.segments.generation import Generation
    from elasticsearch_tpu.vectors.store import FieldCorpus, VectorStoreShard
    n, _rng = node
    store = n.indices.get("a").shards[0].vector_store
    fc = store.field("v")
    assert fc.encoding == "bf16" and fc.corpus is not None
    assert isinstance(fc, FieldCorpus) and not hasattr(fc, "host")
    (base,) = fc.gens.snapshot().generations
    assert isinstance(base, Generation) and not hasattr(base, "host")
    assert "host" not in FieldCorpus.__slots__ + Generation.__slots__
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("elasticsearch_tpu.vectors.host_corpus")
    native.require()
    assert b"es_knn_" not in pathlib.Path(native._SO_PATH).read_bytes()
    assert not hasattr(native, "knn_i8p_topk")
    assert "host_mirror_max_bytes" not in inspect.signature(
        VectorStoreShard.__init__).parameters


def test_route_is_the_fields_not_the_loads(node):
    """Which program answers, and what it answers, depend on the field
    and the request, not on how many requests ride along: one query
    alone and as row 0 of a batch of 16 takes the same kind of handle
    and lands the same ids and scores."""
    n, rng = node
    store = n.indices.get("a").shards[0].vector_store
    fc = store.field("v")
    queries = rng.standard_normal((16, 8)).astype(np.float32)
    landed = []
    for reqs in ([(queries[0], None)], [(q, None) for q in queries]):
        handle = store._dispatch_many(fc, 10, "bf16", reqs, field="v")
        landed.append((handle[0], store.finalize_many(handle)[0]))
    (kind_1, (rows_1, scores_1)), (kind_16, (rows_16, scores_16)) = landed
    assert kind_1 == kind_16 == "pending"
    np.testing.assert_array_equal(rows_1, rows_16)
    np.testing.assert_array_max_ulp(scores_1, scores_16, maxulp=1)


def test_ops_does_not_import_serving():
    """`ops/` is the layer under `serving/`: it imports nothing of it."""
    for path in sorted((PACKAGE / "ops").glob("*.py")):
        for stmt in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(stmt, ast.ImportFrom):
                names = [stmt.module or ""] + [
                    f"{stmt.module}.{a.name}" for a in stmt.names]
            elif isinstance(stmt, ast.Import):
                names = [a.name for a in stmt.names]
            assert not [m for m in names
                        if m.startswith("elasticsearch_tpu.serving")], \
                f"{path.name}:{stmt.lineno} imports serving/"


def test_overhead_probe_failure_raises(monkeypatch):
    monkeypatch.setattr(dispatch, "_overhead_ms", None)

    def boom(*a, **kw):
        raise RuntimeError("no backend (injected)")
    monkeypatch.setattr(dispatch.DISPATCH, "call", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        dispatch.device_overhead_ms()
    assert dispatch._overhead_ms is None     # nothing latched


def test_mesh_enabled_but_unbuildable_raises(monkeypatch):
    import jax

    from elasticsearch_tpu.parallel import policy
    policy.reset(full=True)
    try:
        one = jax.devices()[:1]
        monkeypatch.setattr(jax, "devices", lambda *a: one)
        policy.configure(enabled=True, num_shards=4)
        with pytest.raises(RuntimeError,
                           match="search.mesh.enabled is set"):
            policy.serving_mesh()
        with pytest.raises(RuntimeError,
                           match="search.mesh.enabled is set"):
            policy.mesh_for_shards(4)
        # auto mode on one device: no mesh, no error
        policy.reset(full=True)
        assert policy.serving_mesh() is None
    finally:
        policy.reset(full=True)


def test_nodes_stats_reports_what_ran(node):
    n, rng = node
    n.search("a", _knn_body(rng))
    dev = n.local_node_stats()["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert isinstance(dev["device_kind"], str) and dev["device_kind"]
    assert len(dev["memory"]) == dev["count"]
    assert set(dev["cost_model"]) == {"device_overhead_ms"}
    knn = n.local_node_stats()["indices"]["knn"]
    # the benchmark's kinds and chip_smoke.py still read this key
    assert knn["host_mirror_searches"] == 0
