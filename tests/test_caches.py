"""Layered read-path caching tier (PR 16).

Three rungs, three contracts:

1. PARITY — a cache-served response is byte-identical (modulo `took`)
   to the same body executed with the cache disabled, on the hybrid,
   kNN, and agg paths alike.
2. ZERO STALE — ingest/delete churn + refresh always invalidates: the
   key carries the reader CONTENT fingerprint, so no served response
   ever reflects a superseded snapshot.
3. CLOSED GRID — the semantic cache's probe kernel lives on the shared
   dispatch bucket ladder: a steady-state probe workload recompiles
   nothing.
"""

import json
import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.search.caches import (
    LruCache, NodeCaches, RequestCache, reader_fingerprint,
    request_cache_key, value_fingerprint,
)


# ---------------------------------------------------------------------------
# unit: byte accounting, opt-in policy, key helper
# ---------------------------------------------------------------------------

class TestLruBytes:
    def test_memory_size_tracks_entries(self):
        c = LruCache(max_entries=8)
        assert c.stats()["memory_size_in_bytes"] == 0
        c.put("a", np.zeros(1024, dtype=np.float32))
        assert c.bytes >= 4096
        c.put("b", {"hits": [1, 2, 3], "s": "x" * 100})
        b2 = c.bytes
        assert b2 > 4096
        assert c.stats()["memory_size_in_bytes"] == b2

    def test_eviction_releases_bytes(self):
        c = LruCache(max_entries=2)
        c.put("a", np.zeros(256, dtype=np.float32))
        c.put("b", np.zeros(256, dtype=np.float32))
        full = c.bytes
        c.put("c", np.zeros(256, dtype=np.float32))  # evicts "a"
        assert c.stats()["evictions"] == 1
        assert c.bytes == full  # one out, one in, same size
        c.clear()
        assert c.bytes == 0

    def test_overwrite_replaces_bytes(self):
        c = LruCache(max_entries=4)
        c.put("a", np.zeros(1024, dtype=np.float32))
        c.put("a", np.zeros(16, dtype=np.float32))
        assert c.bytes < 1024


class TestOptInPolicy:
    def test_skipped_uncacheable_counts(self):
        rc = RequestCache(8)
        # opted in but non-deterministic: counted, refused
        body = {"size": 0, "request_cache": True,
                "query": {"range": {"d": {"gte": "now-1d"}}}}
        assert not rc.cacheable_tracked(body)
        assert rc.skipped_uncacheable == 1
        assert rc.stats()["skipped_uncacheable"] == 1
        # no opt-in flag: not counted (the default policy just declines)
        assert not rc.cacheable_tracked({"size": 10})
        assert rc.skipped_uncacheable == 1

    def test_device_cacheable_policy(self):
        rc = RequestCache(8)
        knn = {"size": 5, "knn": {"field": "v", "query_vector": [0.0],
                                  "k": 5}}
        assert rc.device_cacheable(knn)
        assert not rc.device_cacheable({**knn, "request_cache": False})
        assert not rc.device_cacheable({"size": 5})  # not knn-bearing
        bad = {**knn, "request_cache": True,
               "query": {"range": {"d": {"gte": "now-1h"}}}}
        assert not rc.device_cacheable(bad)
        assert rc.skipped_uncacheable == 1


class TestRequestCacheKey:
    def test_strips_cache_control_keys(self):
        fp = (("s0", 10, 10),)
        body = {"size": 0, "aggs": {"a": {"avg": {"field": "n"}}}}
        k1 = request_cache_key("plan", body, fingerprint=fp)
        k2 = request_cache_key(
            "plan", {**body, "request_cache": True, "profile": False},
            fingerprint=fp)
        assert k1 == k2

    def test_fingerprint_distinguishes(self):
        body = {"size": 0, "aggs": {"a": {"avg": {"field": "n"}}}}
        k1 = request_cache_key("plan", body,
                               fingerprint=(("s0", 10, 10),))
        k2 = request_cache_key("plan", body,
                               fingerprint=(("s0", 10, 9),))
        assert k1 != k2

    def test_vector_values_hash_as_f32(self):
        qv = [0.1, 0.2, 0.3]
        b1 = {"knn": {"field": "v", "query_vector": qv, "k": 5}}
        b2 = {"knn": {"field": "v",
                      "query_vector": np.asarray(qv, dtype=np.float32)
                      .tolist(), "k": 5}}
        assert value_fingerprint(b1) == value_fingerprint(b2)
        b3 = {"knn": {"field": "v", "query_vector": [0.1, 0.2, 0.4],
                      "k": 5}}
        assert value_fingerprint(b1) != value_fingerprint(b3)


# ---------------------------------------------------------------------------
# node-level parity + churn
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def node():
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass
    from elasticsearch_tpu.node import Node
    rng = np.random.default_rng(23)
    n = Node(tempfile.mkdtemp())
    # aggs cost-router OFF: its probe legs add wall-clock between the
    # warm/cached/off searches, which lets the background merge's
    # install (a different f32 reduce order) land INSIDE a parity
    # triple instead of between rounds
    n.settings["search.aggs.cost_router"] = "false"
    mappings = {"properties": {
        "body": {"type": "text"},
        "n": {"type": "long"},
        "v": {"type": "dense_vector", "dims": 8,
              "similarity": "cosine"}}}
    # "c": request-cache parity index (semantic cache OFF — its exact-
    # f32 re-rank is a deliberate, opt-in ordering refinement and would
    # muddy the byte-parity contract under test here)
    n.create_index_with_templates("c", mappings=mappings)
    # "sc": semantic cache ON, for the closed-grid test
    n.create_index_with_templates("sc", settings={
        "index.knn.semantic_cache.enabled": True,
        "index.knn.semantic_cache.size": 16,
        "index.knn.semantic_cache.threshold": 0.99,
    }, mappings=mappings)
    ops = []
    for i in range(120):
        doc = {"body": " ".join(rng.choice(list("abcdef"), 4)),
               "n": i, "v": rng.standard_normal(8).tolist()}
        ops.append({"index": {"_index": "c", "_id": str(i)}})
        ops.append(doc)
        ops.append({"index": {"_index": "sc", "_id": str(i)}})
        ops.append(doc)
    n.bulk(ops)
    n.indices.get("c").refresh()
    n.indices.get("sc").refresh()
    yield n, rng
    n.close()


def _parity(node, body):
    """Same body, cache-enabled twice vs cache-disabled; all three
    responses must agree byte-for-byte modulo took."""
    warm = node.search("c", dict(body))
    cached = node.search("c", dict(body))
    off = node.search("c", {**body, "request_cache": False})
    for r in (warm, cached, off):
        r.pop("took", None)
    assert json.dumps(warm, sort_keys=True) \
        == json.dumps(cached, sort_keys=True)
    assert json.dumps(cached, sort_keys=True) \
        == json.dumps(off, sort_keys=True)
    return cached


class TestNodeParityAndChurn:
    def test_agg_parity_and_hit(self, node):
        n, _ = node
        before = n.caches.request.hits
        body = {"size": 0, "aggs": {"s": {"sum": {"field": "n"}}}}
        _parity(n, body)
        assert n.caches.request.hits > before

    def test_knn_parity_and_hit(self, node):
        n, rng = node
        body = {"size": 5, "request_cache": True,
                "knn": {"field": "v",
                        "query_vector": rng.standard_normal(8).tolist(),
                        "k": 5, "num_candidates": 20}}
        before = n.caches.device_request.hits
        _parity(n, body)
        assert n.caches.device_request.hits > before

    def test_zero_stale_across_churn(self, node):
        n, rng = node
        agg = {"size": 0, "aggs": {"s": {"sum": {"field": "n"}}}}
        knn = {"size": 3,
               "knn": {"field": "v",
                       "query_vector": rng.standard_normal(8).tolist(),
                       "k": 3, "num_candidates": 20}}
        for round_no in range(3):
            a = _parity(n, agg)
            k = _parity(n, knn)
            # churn: one ingest + one delete, then refresh
            doc_id = f"churn{round_no}"
            n.index_doc("c", doc_id, {
                "body": "zz", "n": 100000 + round_no,
                "v": rng.standard_normal(8).tolist()})
            victim = k["hits"]["hits"][0]["_id"]
            n.delete_doc("c", victim)
            n.indices.get("c").refresh()
            # the cached agg/knn MUST reflect the churn (fingerprint
            # moved): sum changed, deleted doc gone
            a2 = _parity(n, agg)
            k2 = _parity(n, knn)
            assert a2["aggregations"]["s"]["value"] \
                != a["aggregations"]["s"]["value"]
            assert victim not in [h["_id"] for h in k2["hits"]["hits"]]

    def test_hybrid_parity_and_hit(self, node):
        n, rng = node
        body = {"rank": {"rrf": {"rank_constant": 60,
                                 "rank_window_size": 40}},
                "query": {"match": {"body": "a b"}},
                "knn": {"field": "v",
                        "query_vector": rng.standard_normal(8).tolist(),
                        "k": 40, "num_candidates": 40},
                "size": 10}
        warm = n.search("c", dict(body))
        before = n.local_node_stats()["indices"]["hybrid"][
            "request_cache_hits"]
        cached = n.search("c", dict(body))
        assert n.local_node_stats()["indices"]["hybrid"][
            "request_cache_hits"] == before + 1
        off = n.search("c", {**body, "request_cache": False})
        for r in (warm, cached, off):
            r.pop("took", None)
        assert json.dumps(warm, sort_keys=True) \
            == json.dumps(cached, sort_keys=True)
        assert json.dumps(cached, sort_keys=True) \
            == json.dumps(off, sort_keys=True)

    def test_profile_annotation_and_bypass(self, node):
        n, _ = node
        body = {"size": 0, "profile": True,
                "aggs": {"s": {"sum": {"field": "n"}}}}
        r1 = n.search("c", dict(body))
        shard_prof = r1["profile"]["shards"][0]
        assert shard_prof["cache"]["rung"] == "shard_request"
        r2 = n.search("c", dict(body))
        assert r2["profile"]["shards"][0]["cache"]["hit"] is True

    def test_stats_report_real_bytes(self, node):
        n, _ = node
        n.search("c", {"size": 0,
                       "aggs": {"s": {"sum": {"field": "n"}}}})
        st = n.local_node_stats()["indices"]
        rc = st["request_cache"]
        assert rc["memory_size_in_bytes"] > 0
        assert rc["hit_count"] + rc["miss_count"] > 0
        assert "skipped_uncacheable" in rc
        assert rc["host"]["memory_size_in_bytes"] >= 0
        assert rc["device"]["memory_size_in_bytes"] >= 0


# ---------------------------------------------------------------------------
# semantic cache: guard + closed grid
# ---------------------------------------------------------------------------

class _FakeSource:
    def __init__(self, arr):
        self.arr = np.asarray(arr, dtype=np.float32)
        self.dims = self.arr.shape[1]

    def gather(self, pos):
        return self.arr[np.asarray(pos, dtype=np.int64)]


class _FakeFc:
    def __init__(self, docs):
        self.source = _FakeSource(docs)
        self.row_map = np.arange(len(docs), dtype=np.int64)
        self.dims = docs.shape[1]
        self.gens = None


def _fill(cache, fc, q, k):
    """Insert one exact top-k window for q (computed in f32)."""
    from elasticsearch_tpu.quant.rescore import exact_scores
    scores = exact_scores(q[None, :], fc.source.arr[None], sim.COSINE)[0]
    top = np.argsort(-scores, kind="stable")[:k]
    cache.insert_many(
        [(q, None)], [(top.astype(np.int64), scores[top])],
        fc, k, "bf16", None)


class TestSemanticGuard:
    DIMS = 8

    def _mk(self, threshold=0.99, seed=5, n_docs=64):
        from elasticsearch_tpu.vectors.semantic_cache import SemanticCache
        rng = np.random.default_rng(seed)
        docs = rng.standard_normal((n_docs, self.DIMS)).astype(np.float32)
        fc = _FakeFc(docs)
        cache = SemanticCache(16, threshold, self.DIMS, sim.COSINE,
                              version=("t",))
        return cache, fc, rng

    def _drift(self, q, target_sim, rng):
        """A query at a controlled cosine distance from q."""
        qn = q / np.linalg.norm(q)
        r = rng.standard_normal(self.DIMS).astype(np.float32)
        r -= (r @ qn) * qn
        r /= np.linalg.norm(r)
        out = target_sim * qn + np.sqrt(1 - target_sim ** 2) * r
        return out.astype(np.float32)

    def test_identical_resend_serves_exact_topk(self):
        cache, fc, rng = self._mk()
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        served, stats = cache.probe([(q, None)], 5, "bf16", None)
        assert stats == {"probed": 1, "hits": 1, "rejects": 0,
                         "nanos": stats["nanos"]}
        rows, scores = served[0]
        from elasticsearch_tpu.quant.rescore import exact_scores
        exact = exact_scores(q[None, :], fc.source.arr[None],
                             sim.COSINE)[0]
        expect = np.argsort(-exact, kind="stable")[:5]
        assert np.array_equal(rows, expect)
        assert np.allclose(scores, exact[expect])

    def test_rescore_guard_rejects_unprovable_drift(self):
        """A near-duplicate ABOVE the probe threshold still rejects when
        the rescored k-th score cannot dominate the window floor plus
        the drift bound: with window == k the rescored k-th IS the
        floor, so any real drift margin fails the dominance check."""
        cache, fc, rng = self._mk(threshold=0.99)
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        q_near = self._drift(q, 0.995, rng)  # above threshold
        served, stats = cache.probe([(q_near, None)], 5, "bf16", None)
        assert served == {}
        assert stats["rejects"] == 1 and stats["hits"] == 0

    def test_below_threshold_is_a_plain_miss(self):
        cache, fc, rng = self._mk(threshold=0.99)
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        q_far = self._drift(q, 0.5, rng)
        served, stats = cache.probe([(q_far, None)], 5, "bf16", None)
        assert served == {} and stats["rejects"] == 0

    def test_filtered_queries_bypass(self):
        cache, fc, rng = self._mk()
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        served, stats = cache.probe(
            [(q, np.array([1, 2, 3], dtype=np.int64))], 5, "bf16", None)
        assert served == {} and stats["probed"] == 0

    def test_k_mismatch_never_serves(self):
        cache, fc, rng = self._mk()
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        served, stats = cache.probe([(q, None)], 10, "bf16", None)
        assert served == {} and stats["rejects"] == 1

    def test_complete_window_serves_any_near_dup(self):
        """k >= corpus: the window IS the corpus, nothing exists outside
        it, so any above-threshold neighbor serves (exact re-rank)."""
        cache, fc, rng = self._mk(threshold=0.99, n_docs=4)
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=8)  # k > n_docs -> complete
        q_near = self._drift(q, 0.995, rng)
        served, stats = cache.probe([(q_near, None)], 8, "bf16", None)
        assert stats["hits"] == 1
        rows, scores = served[0]
        from elasticsearch_tpu.quant.rescore import exact_scores
        exact = exact_scores(q_near[None, :], fc.source.arr[None],
                             sim.COSINE)[0]
        expect = np.argsort(-exact, kind="stable")[:8]
        assert np.array_equal(rows, expect)

    def test_memory_size(self):
        cache, fc, rng = self._mk()
        empty = cache.memory_size_in_bytes()
        q = rng.standard_normal(self.DIMS).astype(np.float32)
        _fill(cache, fc, q, k=5)
        assert cache.memory_size_in_bytes() > empty
        assert cache.entry_count() == 1


class TestSemanticClosedGrid:
    def test_second_pass_compiles_nothing(self, node):
        """Steady-state semcache probing stays on the compiled grid: after
        one warmup pass (ring upload + probe + miss dispatch), a second
        pass of probes — hits, rejects, and misses alike — records ZERO
        new compiles."""
        n, rng = node
        base = rng.standard_normal(8).astype(np.float32)

        def drive(qs):
            for q in qs:
                n.search("sc", {
                    "size": 3, "request_cache": False,
                    "knn": {"field": "v", "query_vector": q.tolist(),
                            "k": 3, "num_candidates": 20}})

        warm = [base, base + 1e-6, rng.standard_normal(8)]
        drive([q.astype(np.float32) for q in warm])
        st = n.local_node_stats()["indices"]["knn"]
        assert st["semantic_probes"] > 0
        before = dispatch.DISPATCH.compile_count()
        drive([base, (base + 1e-6).astype(np.float32),
               rng.standard_normal(8).astype(np.float32)])
        after = dispatch.DISPATCH.compile_count()
        assert after == before, (
            f"semcache steady state recompiled {after - before} "
            f"programs; stats={dispatch.stats(per_bucket=True)}")
