"""Nothing on the serving path may answer from another route behind the
caller's back: a device error fails the shard the response reports, a
cost-model input that cannot be had raises, a mesh that was asked for
and cannot be built raises."""

import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import SearchPhaseExecutionError
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.serving import batcher


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


def test_peak_table_knows_v5e_and_refuses_an_unknown_tpu(monkeypatch):
    import jax

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    def peak_for(dev):
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        return batcher.device_peak_ops()

    assert peak_for(Dev("tpu", "TPU v5 lite")) == 197.0e12
    assert batcher.DEVICE_PEAKS["TPU v5 lite"] == (197.0e12, 393.0e12,
                                                   819.0e9)
    with pytest.raises(RuntimeError, match="no published peak"):
        peak_for(Dev("tpu", "TPU v9 imaginary"))


def test_cpu_backend_never_prefers_the_host_mirror():
    """With the CPU as JAX's backend there is no device to price: the
    route is the device one by that fact, at any size and batch."""
    assert batcher.device_peak_ops() is None
    for batch, rows in ((1, 128), (1, 4096), (64, 1 << 20)):
        assert batcher.CostModel.prefer_host(batch, rows, 128) is False


def test_overhead_probe_failure_raises(monkeypatch):
    from elasticsearch_tpu.ops import dispatch
    monkeypatch.setattr(batcher, "_overhead_ms", None)

    def boom(*a, **kw):
        raise RuntimeError("no backend (injected)")
    monkeypatch.setattr(dispatch, "call", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        batcher.device_overhead_ms()
    assert batcher._overhead_ms is None     # nothing latched


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
    assert dev["cost_model"]["device_peak_ops"] is None   # CPU backend
    knn = n.local_node_stats()["indices"]["knn"]
    assert "host_mirror_searches" in knn
