"""Mesh-sharded serving (parallel/policy.py + sharded_knn/sharded_ivf).

Two contracts gate SPMD promotion from bench demo to default serving
mode, both pinned here on the 8 virtual CPU devices conftest forces
(same XLA partitioner as a real mesh — program structure, not ICI):

1. PARITY — sharded execution is result-identical to single-device:
   exact kNN and IVF top-k byte-parity at the kernel layer, and full
   `rank.rrf` / knn response parity through the REST controller.

2. CLOSED GRID — steady-state sharded serving compiles nothing: the
   second pass over the sharded grid runs under strict dispatch with a
   zero `compiles` delta for the kNN, IVF, and hybrid legs.
"""

import json
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.parallel import mesh as mesh_lib
from elasticsearch_tpu.parallel.sharded_knn import (
    ShardedFieldState,
    distributed_knn_search,
)

pytestmark = pytest.mark.multidevice


def _single_device_knn(vectors, queries, k, metric="cosine",
                       precision="f32", filter_mask=None):
    corpus = knn_ops.build_corpus(vectors, metric=metric, dtype="f32")
    s, i = knn_ops.knn_search(jnp.asarray(queries), corpus, k,
                              metric=metric, precision=precision,
                              filter_mask=filter_mask)
    return np.asarray(s), np.asarray(i)


def _mesh_knn(state, queries, k, metric="cosine", precision="f32",
              mask=None):
    q = jax.device_put(jnp.asarray(queries), state.query_sharding())
    if mask is not None:
        mask = jax.device_put(jnp.asarray(mask),
                              state.mask_sharding(mask.ndim))
    s, g = distributed_knn_search(q, state.corpus, k, state.mesh,
                                  metric=metric, filter_mask=mask,
                                  precision=precision)
    return np.asarray(s), state.map_ids(np.asarray(g))


# ------------------------------------------------------------ kernels


class TestShardedKnnParity:
    def test_byte_parity_vs_single_device(self, mesh_serving):
        rng = np.random.default_rng(0)
        vectors = rng.standard_normal((1000, 64)).astype(np.float32)
        queries = rng.standard_normal((8, 64)).astype(np.float32)
        state = ShardedFieldState(vectors, mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        s_mesh, rows_mesh = _mesh_knn(state, queries, 10)
        s_one, rows_one = _single_device_knn(vectors, queries, 10)
        assert np.array_equal(rows_mesh, rows_one)
        # byte-identical, not approx: same matmul precision, the sharded
        # merge only reorders candidates that were scored identically
        assert s_mesh.tobytes() == s_one.tobytes()

    def test_ragged_shard_padding_never_leaks(self, mesh_serving):
        """The padded-row escape (ISSUE 5): 37 rows over 8 shards leaves
        every shard ragged; k=16 exceeds each shard's num_valid, so
        un-masked padding rows would enter the merge as aliased ids."""
        rng = np.random.default_rng(1)
        vectors = rng.standard_normal((37, 16)).astype(np.float32)
        queries = rng.standard_normal((8, 16)).astype(np.float32)
        state = ShardedFieldState(vectors, mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        s_mesh, rows_mesh = _mesh_knn(state, queries, 16)
        # padding must surface as (-inf, -1), never as an aliased row
        valid = s_mesh > -np.inf
        assert (rows_mesh[valid] >= 0).all()
        assert (rows_mesh[valid] < 37).all()
        assert (rows_mesh[~valid] == -1).all()
        s_one, rows_one = _single_device_knn(vectors, queries, 16)
        assert np.array_equal(rows_mesh[valid],
                              rows_one[np.asarray(s_one) > -1e37])
        assert s_mesh[valid].tobytes() == \
            s_one[np.asarray(s_one) > -1e37].tobytes()

    def test_per_query_filter_parity(self, mesh_serving):
        rng = np.random.default_rng(2)
        n = 600
        vectors = rng.standard_normal((n, 32)).astype(np.float32)
        queries = rng.standard_normal((8, 32)).astype(np.float32)
        state = ShardedFieldState(vectors, mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        allowed = rng.random((8, n)) < 0.3
        mask = np.stack([state.filter_mask(a) for a in allowed])
        s_mesh, rows_mesh = _mesh_knn(state, queries, 10, mask=mask)
        corpus = knn_ops.build_corpus(vectors, metric="cosine",
                                      dtype="f32")
        pad_n = corpus.matrix.shape[0]
        allowed_pad = np.zeros((8, pad_n), dtype=bool)
        allowed_pad[:, :n] = allowed
        s_one, rows_one = _single_device_knn(
            vectors, queries, 10, filter_mask=jnp.asarray(allowed_pad))
        v = s_one > -1e37
        assert np.array_equal(rows_mesh[v], rows_one[v])
        assert s_mesh[v].tobytes() == s_one[v].tobytes()
        # filtered-out slots surface as (-inf, -1) on the mesh
        assert (rows_mesh[~v] == -1).all()

    def test_incremental_append_parity(self, mesh_serving):
        """Refresh appends land in per-shard headroom via `mesh.append`
        (delta-only upload) and must serve identically to a corpus built
        whole."""
        rng = np.random.default_rng(3)
        vectors = rng.standard_normal((2000, 32)).astype(np.float32)
        queries = rng.standard_normal((8, 32)).astype(np.float32)
        state = ShardedFieldState(vectors[:1500],
                                  mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        assert state.can_append(500)
        old = state
        state = state.append(vectors[1500:])
        assert state.n_rows == 2000
        assert int(state.shard_counts.sum()) == 2000
        # copy-on-write: the pre-append snapshot an in-flight search
        # captured must be untouched and still serve from live buffers
        assert old.n_rows == 1500
        assert int(old.shard_counts.sum()) == 1500
        s_old, rows_old = _mesh_knn(old, queries, 10)
        s_ref, rows_ref = _single_device_knn(vectors[:1500], queries, 10)
        assert np.array_equal(rows_old, rows_ref)
        assert s_old.tobytes() == s_ref.tobytes()
        s_mesh, rows_mesh = _mesh_knn(state, queries, 10)
        s_one, rows_one = _single_device_knn(vectors, queries, 10)
        # appended rows land in whichever shard had headroom, so the
        # merge may order equal-score candidates differently — compare
        # as ranked sets
        assert np.array_equal(np.sort(rows_mesh, axis=1),
                              np.sort(rows_one, axis=1))
        assert np.sort(s_mesh, axis=1).tobytes() == \
            np.sort(s_one, axis=1).tobytes()

    def test_append_beyond_headroom_raises(self, mesh_serving):
        rng = np.random.default_rng(4)
        vectors = rng.standard_normal((256, 8)).astype(np.float32)
        state = ShardedFieldState(vectors, mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        too_many = state.headroom() + 1
        assert not state.can_append(too_many)
        with pytest.raises(ValueError, match="headroom"):
            state.append(rng.standard_normal((too_many, 8))
                         .astype(np.float32))

    def test_warmup_precompiles_sharded_grid(self, mesh_serving):
        """`warmup_entries` AOT specs (shape + NamedSharding) must key to
        the SAME executables live sharded traffic dispatches."""
        rng = np.random.default_rng(5)
        vectors = rng.standard_normal((512, 16)).astype(np.float32)
        state = ShardedFieldState(vectors, mesh_serving.serving_mesh(),
                                  "cosine", "f32")
        dispatch.DISPATCH.warmup(state.warmup_entries(16),
                                 background=False)
        before = dispatch.stats(per_bucket=False)
        queries = rng.standard_normal((8, 16)).astype(np.float32)
        # the grid is warmed in the form the store serves: one packed
        # board, the queries placed from host numpy in one call
        distributed_knn_search(
            jax.device_put(queries, state.query_sharding()), state.corpus,
            10, state.mesh, precision="bf16", board=True)
        after = dispatch.stats(per_bucket=False)
        assert after["compiles"] == before["compiles"]
        assert after["hits"] > before["hits"]


class TestShardedIvfParity:
    def test_byte_parity_vs_single_device(self, mesh_serving):
        from elasticsearch_tpu.ann.ivf_index import build_ivf_index
        from elasticsearch_tpu.ann.router import IVFRouter

        rng = np.random.default_rng(6)
        vectors = rng.standard_normal((2000, 32)).astype(np.float32)
        queries = rng.standard_normal((8, 32)).astype(np.float32)
        index = build_ivf_index(vectors, metric="cosine", nlist=16,
                                dtype="f32")
        router = IVFRouter(index, nprobe=4)
        s_one, rows_one, ph_one = router.search(queries, 10, nprobe=4)
        s_mesh, rows_mesh, ph_mesh = router.search(
            queries, 10, nprobe=4, mesh=mesh_serving.serving_mesh())
        assert ph_mesh["engine"] == "tpu_ivf_mesh"
        assert ph_mesh["mesh_shards"] == 8
        assert np.array_equal(rows_mesh, rows_one)
        assert s_mesh.tobytes() == s_one.tobytes()

    def test_quantized_int8_parity(self, mesh_serving):
        from elasticsearch_tpu.ann.ivf_index import build_ivf_index
        from elasticsearch_tpu.ann.router import IVFRouter

        rng = np.random.default_rng(7)
        vectors = rng.standard_normal((1500, 16)).astype(np.float32)
        queries = rng.standard_normal((8, 16)).astype(np.float32)
        index = build_ivf_index(vectors, metric="cosine", nlist=16,
                                dtype="int8")
        router = IVFRouter(index, nprobe=4)
        s_one, rows_one, _ = router.search(queries, 10, nprobe=4)
        s_mesh, rows_mesh, _ = router.search(
            queries, 10, nprobe=4, mesh=mesh_serving.serving_mesh())
        assert np.array_equal(rows_mesh, rows_one)
        assert s_mesh.tobytes() == s_one.tobytes()


class TestShardedBm25Int8:
    def test_int8_impacts_mesh_parity(self, mesh_serving):
        """int8 tile scales are rank-1 [T]: the sharded kernel must
        accept them (regression: a rank-2 in_spec made every mesh-routed
        BM25 dispatch on an int8-impact index raise in shard_map) and
        score byte-identically to the single-device int8 board."""
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.index.mapping import MapperService
        from elasticsearch_tpu.ops.bm25 import LexicalShard

        ms = MapperService({"properties": {"body": {"type": "text"}}})
        eng = Engine(tempfile.mkdtemp(), ms)
        rng = np.random.default_rng(13)
        vocab = [f"tok{i}" for i in range(40)]
        for i in range(300):
            words = " ".join(rng.choice(vocab, size=rng.integers(2, 10)))
            eng.index(str(i), {"body": words})
        eng.refresh()
        reader = eng.acquire_searcher()
        lex = LexicalShard(dtype="int8")
        queries = [(["tok1", "tok2"], 1.0), (["tok5"], 2.0),
                   (["tok7", "tok8", "tok9"], 1.0)]

        mesh_res = lex.search_batch(reader, "body", queries, 10,
                                    route="device")
        assert mesh_serving.stats()["router"]["mesh"] >= 1, \
            "int8 lexical dispatch did not route to the mesh"
        mesh_serving.configure(enabled=False)
        one_res = lex.search_batch(reader, "body", queries, 10,
                                   route="device")
        for (m_rows, m_scores), (o_rows, o_scores) in zip(mesh_res,
                                                          one_res):
            assert np.array_equal(m_rows, o_rows)
            assert m_scores.tobytes() == o_scores.tobytes()


# ----------------------------------------------------- store + REST


def _make_node(tmp, settings=None, n=900, dims=16, seed=11):
    from elasticsearch_tpu.node import Node

    rng = np.random.default_rng(seed)
    node = Node(tmp)
    node.create_index_with_templates("m", settings=settings or {},
                                     mappings={"properties": {
                                         "body": {"type": "text"},
                                         "tag": {"type": "keyword"},
                                         "v": {"type": "dense_vector",
                                               "dims": dims}}})
    ops = []
    for i in range(n):
        ops.append({"index": {"_index": "m", "_id": str(i)}})
        ops.append({"body": " ".join(rng.choice(list("abcdefgh"), 5)),
                    "tag": "even" if i % 2 == 0 else "odd",
                    "v": rng.standard_normal(dims).tolist()})
    node.bulk(ops)
    node.indices.get("m").refresh()
    return node, rng


def _strip_took(resp):
    resp = dict(resp)
    resp.pop("took", None)
    return json.dumps(resp, sort_keys=True)


class TestRestParity:
    def test_knn_and_rrf_response_parity_and_strict_second_pass(
            self, mesh_serving):
        """One node, three serving legs (exact kNN, IVF via a second
        index, fused rank.rrf), each compared mesh-vs-single-device
        through the REST-facing search entry, then re-run under strict
        dispatch asserting the sharded grid is closed (zero compiles)."""
        node, rng = _make_node(tempfile.mkdtemp())
        try:
            qv = rng.standard_normal(16).tolist()
            knn_body = {"knn": {"field": "v", "query_vector": qv,
                                "k": 10, "num_candidates": 50},
                        "size": 10}
            rrf_body = {
                "rank": {"rrf": {"rank_constant": 60,
                                 "rank_window_size": 20}},
                "query": {"match": {"body": "a b"}},
                "knn": {"field": "v", "query_vector": qv, "k": 10,
                        "num_candidates": 50},
                "size": 10}

            mesh_resp_knn = node.search("m", dict(knn_body))
            mesh_resp_rrf = node.search("m", json.loads(
                json.dumps(rrf_body)))
            stats = mesh_serving.stats()
            assert stats["available"] and stats["num_shards"] == 8
            assert stats["router"]["mesh"] >= 1
            assert "knn" in stats["legs"]
            knn_stats = node.indices.get("m").shards[0] \
                .vector_store.knn_stats
            assert knn_stats["mesh_searches"] >= 1

            # the same requests with the mesh router OFF: byte-identical
            # responses prove sharded execution changed nothing
            mesh_serving.configure(enabled=False)
            one_resp_knn = node.search("m", dict(knn_body))
            one_resp_rrf = node.search("m", json.loads(
                json.dumps(rrf_body)))
            assert _strip_took(mesh_resp_knn) == _strip_took(one_resp_knn)
            assert _strip_took(mesh_resp_rrf) == _strip_took(one_resp_rrf)

            # strict second pass: mesh back on, identical requests must
            # reuse every sharded executable (closed-grid acceptance)
            mesh_serving.configure(enabled=True, num_shards=8,
                                   min_rows=1)
            node.search("m", dict(knn_body))  # re-warm post-toggle
            node.search("m", json.loads(json.dumps(rrf_body)))
            before = dispatch.stats(per_bucket=False)
            old_strict = dispatch.DISPATCH.strict
            dispatch.DISPATCH.strict = True
            try:
                again_knn = node.search("m", dict(knn_body))
                again_rrf = node.search("m", json.loads(
                    json.dumps(rrf_body)))
            finally:
                dispatch.DISPATCH.strict = old_strict
            after = dispatch.stats(per_bucket=False)
            assert after["compiles"] == before["compiles"]
            assert after["out_of_grid_compiles"] == \
                before["out_of_grid_compiles"]
            assert _strip_took(again_knn) == _strip_took(mesh_resp_knn)
            assert _strip_took(again_rrf) == _strip_took(mesh_resp_rrf)
        finally:
            node.close()

    def test_ivf_engine_rides_mesh_through_store(self, mesh_serving):
        node, rng = _make_node(
            tempfile.mkdtemp(),
            settings={"index.knn.engine": "tpu_ivf",
                      "index.knn.nlist": 16, "index.knn.nprobe": 4},
            n=2000, seed=12)
        try:
            qv = rng.standard_normal(16).tolist()
            body = {"knn": {"field": "v", "query_vector": qv, "k": 10,
                            "num_candidates": 64}, "size": 10}
            mesh_resp = node.search("m", dict(body))
            store = node.indices.get("m").shards[0].vector_store
            assert store.last_knn_phases["engine"] == "tpu_ivf_mesh"
            assert store.knn_stats["mesh_searches"] >= 1
            mesh_serving.configure(enabled=False)
            one_resp = node.search("m", dict(body))
            assert store.last_knn_phases["engine"] == "tpu_ivf"
            assert _strip_took(mesh_resp) == _strip_took(one_resp)
        finally:
            node.close()

    def test_profile_and_nodes_stats_mesh_sections(self, mesh_serving):
        node, rng = _make_node(tempfile.mkdtemp(), seed=13)
        try:
            qv = rng.standard_normal(16).tolist()
            resp = node.search("m", {
                "knn": {"field": "v", "query_vector": qv, "k": 5,
                        "num_candidates": 20},
                "size": 5, "profile": True})
            shard_prof = resp["profile"]["shards"][0]
            assert shard_prof["mesh"]["shards"] == 8
            assert shard_prof["mesh"]["collective_bytes"] > 0
            assert shard_prof["mesh"]["breakdown"]["local_nanos"] > 0

            resp = node.search("m", {
                "rank": {"rrf": {"rank_window_size": 10}},
                "query": {"match": {"body": "a"}},
                "knn": {"field": "v", "query_vector": qv, "k": 5,
                        "num_candidates": 20},
                "size": 5, "profile": True})
            hyb = resp["profile"]["hybrid"]
            assert hyb["mesh"]["shards"] == 8
            assert hyb["mesh"]["router"]["mesh"] >= 1
            assert "knn" in hyb["mesh"]["legs"]

            section = node._mesh_stats_section()
            assert section["available"] is True
            assert section["num_shards"] == 8
            assert section["router"]["mesh"] >= 2
            for leg, entry in section["legs"].items():
                assert entry["dispatches"] >= 1
                assert entry["collective_bytes"] > 0
        finally:
            node.close()

    def test_small_corpus_stays_single_device(self, mesh_serving):
        """The cost router's row floor: corpora under min_rows never pay
        the second resident copy or the all-gather merge."""
        mesh_serving.configure(enabled=True, num_shards=8,
                               min_rows=100_000)
        node, rng = _make_node(tempfile.mkdtemp(), n=200, seed=14)
        try:
            qv = rng.standard_normal(16).tolist()
            node.search("m", {"knn": {"field": "v", "query_vector": qv,
                                      "k": 5, "num_candidates": 20},
                              "size": 5})
            store = node.indices.get("m").shards[0].vector_store
            assert store.field("v").mesh_state is None
            assert store.knn_stats["mesh_searches"] == 0
            stats = mesh_serving.stats()
            assert stats["router"]["single_device"] >= 1
            reasons = stats["router"]["reasons"]
            assert reasons.get("corpus_below_min_rows", 0) \
                + reasons.get("no_sharded_corpus", 0) >= 1
        finally:
            node.close()

    def test_partial_configure_preserves_other_keys(self, mesh_serving):
        """`search.mesh.*` settings are process-wide: a node that sets
        ONE key must not clobber the others an earlier in-process node
        configured (the dispatcher warmup policy's rule)."""
        mesh_serving.configure(min_rows=1024)
        mesh_serving.configure(enabled=True)
        assert mesh_serving.min_rows() == 1024
        assert mesh_serving.stats()["num_shards"] == 8
        mesh_serving.configure(min_rows=None)   # explicit None = default
        assert mesh_serving.min_rows() == mesh_serving.DEFAULT_MIN_ROWS

    def test_dp_setting_partial_configure(self, mesh_serving):
        """`search.mesh.dp` follows `policy.configure`'s partial-update
        semantics: setting dp alone must not clobber the other keys, and
        explicit None resets it."""
        mesh_serving.configure(min_rows=2048)
        mesh_serving.configure(dp=2, num_shards=4)
        assert mesh_serving.min_rows() == 2048
        st = mesh_serving.stats()
        assert st["dp"] == 2 and st["num_shards"] == 4
        assert st["devices"] == {"total": 8, "shard_axis": 4,
                                 "dp_axis": 2}
        mesh_serving.configure(dp=None)   # explicit None = auto (dp=1)
        assert mesh_serving.stats()["dp"] == 1
        assert mesh_serving.min_rows() == 2048

    def test_knn_k_deeper_than_shard_reclassifies_router_stats(
            self, mesh_serving):
        """A mesh-accepted kNN dispatch that the k-deeper-than-shard
        guard then forces single-device must move its router decision
        over (the BM25 window guard's contract): `_nodes/stats
        indices.mesh` reflects where the dispatch actually ran."""
        node, rng = _make_node(tempfile.mkdtemp(), n=900, seed=15)
        try:
            store = node.indices.get("m").shards[0].vector_store
            fc = store.field("v")
            assert fc.mesh_state is not None
            deep_k = fc.mesh_state.layout.rows_per_shard + 1
            qv = rng.standard_normal(16).tolist()
            node.search("m", {"knn": {"field": "v", "query_vector": qv,
                                      "k": deep_k,
                                      "num_candidates": deep_k},
                              "size": 1})
            st = mesh_serving.stats()
            assert st["router"]["reasons"].get(
                "knn_k_deeper_than_shard", 0) >= 1
            assert st["router"]["mesh"] == 0
            assert store.knn_stats.get("mesh_searches", 0) == 0
        finally:
            node.close()


# ------------------------------------------------- dp > 1 (replicated)


def _oracle(vectors, queries, k):
    s, i = _single_device_knn(vectors, queries, k)
    return np.asarray(s), np.asarray(i)


class TestDpReplicatedServing:
    """The (dp=2, shard=4) replicated grid: byte parity on every route,
    concurrency on disjoint groups, replica-consistent merge
    graduation, and the strict-mode zero-recompile dp grid."""

    def test_dp_byte_parity_on_ragged_shards(self, mesh_serving_dp):
        """37 rows over 4 ragged shards, replicated across 2 dp groups:
        the full-mesh split route and BOTH group routes must be
        byte-identical to single-device (padding surfaces as (-inf, -1),
        never an aliased id, on every replica)."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib

        rng = np.random.default_rng(21)
        vectors = rng.standard_normal((37, 16)).astype(np.float32)
        queries = rng.standard_normal((8, 16)).astype(np.float32)
        mesh = mesh_serving_dp.serving_mesh()
        state = ShardedFieldState(vectors, mesh, "cosine", "f32")
        s_ref, i_ref = _oracle(vectors, queries, 16)
        v = s_ref > -1e37
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)
        for route in (mesh,) + tuple(mesh_serving_dp.dp_groups()):
            q = jax.device_put(jnp.asarray(queries),
                               mesh_lib.query_sharding(route))
            s, g = distributed_knn_search(q, state.corpus_for(route), 16,
                                          route, metric="cosine",
                                          precision="f32")
            rows = state.map_ids(np.asarray(g))
            s = np.asarray(s)
            valid = s > -np.inf
            assert (rows[valid] >= 0).all() and (rows[valid] < 37).all()
            assert (rows[~valid] == -1).all()
            assert np.array_equal(rows[valid], i_ref[v])
            assert s[valid].tobytes() == s_ref[v].tobytes()

    def test_router_split_decisions_and_stats(self, mesh_serving_dp):
        """queue depth × corpus size drives the dp-vs-shard split, and
        `stats()` reports routes, reasons, and the round-robin group
        spread — the satellite's `_nodes/stats indices.mesh` contract."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib

        pol = mesh_serving_dp
        # batch below dp -> group; queued -> group; idle large -> full
        m1 = pol.decide("knn", 5000, batch=1)
        m2 = pol.decide("knn", 5000, batch=8, queue_depth=2)
        m3 = pol.decide("knn", 5000, batch=8, queue_depth=0)
        assert mesh_lib.dp_size(m1) == 1
        assert mesh_lib.dp_size(m2) == 1
        assert mesh_lib.dp_size(m3) == 2
        # round-robin: consecutive group picks alternate groups
        assert m1 is not m2
        st = pol.stats()
        assert st["dp"] == 2
        assert st["devices"]["dp_axis"] == 2
        dp_st = st["router"]["dp"]
        assert dp_st["routes"] == {"shard": 1, "dp": 2}
        assert dp_st["reasons"]["batch_below_dp"] == 1
        assert dp_st["reasons"]["queue_pressure"] == 1
        assert dp_st["reasons"]["idle_large_corpus"] == 1
        assert set(dp_st["group_dispatches"]) == {"0", "1"}
        # the node stats section passes the dp fields through
        from elasticsearch_tpu.node import Node
        assert Node._mesh_stats_section()["dp"] == 2

    def test_concurrent_batches_on_disjoint_dp_groups(
            self, mesh_serving_dp):
        """Concurrent dispatches under queue pressure round-robin onto
        disjoint device groups and every one returns the single-device
        answer — the scheduling-concurrency contract the dp bench row
        measures."""
        import threading

        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)

        rng = np.random.default_rng(22)
        vectors = rng.standard_normal((800, 16)).astype(np.float32)
        mesh = mesh_serving_dp.serving_mesh()
        state = ShardedFieldState(vectors, mesh, "cosine", "f32")
        batches = [rng.standard_normal((8, 16)).astype(np.float32)
                   for _ in range(6)]
        oracles = [_oracle(vectors, qs, 10) for qs in batches]
        routes = [mesh_serving_dp.decide("knn", 800, batch=8,
                                         queue_depth=len(batches))
                  for _ in batches]
        assert all(mesh_lib.dp_size(r) == 1 for r in routes)
        assert len({id(r) for r in routes}) == 2  # both groups used
        results = [None] * len(batches)

        def run(idx):
            q = jax.device_put(jnp.asarray(batches[idx]),
                               mesh_lib.query_sharding(routes[idx]))
            s, g = distributed_knn_search(
                q, state.corpus_for(routes[idx]), 10, routes[idx],
                metric="cosine", precision="f32")
            results[idx] = (np.asarray(s), state.map_ids(np.asarray(g)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(batches))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (s, rows), (s_ref, i_ref) in zip(results, oracles):
            assert np.array_equal(rows, i_ref)
            assert s.tobytes() == s_ref.tobytes()
        spread = mesh_serving_dp.stats()["router"]["dp"][
            "group_dispatches"]
        assert len(spread) == 2  # dispatches landed on both groups

    def test_replica_consistent_merge_graduation(self, mesh_serving_dp):
        """Generational merge graduation under dp > 1: a search
        dispatched BEFORE the install keeps one coherent (old) snapshot;
        after the install every dp replica serves the merged corpus
        byte-identically — a merge can never leave two groups on
        different corpus versions."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)
        node, rng = _make_node(tempfile.mkdtemp(), n=600, seed=23)
        try:
            store = node.indices.get("m").shards[0].vector_store
            old_ms = store.field("v").mesh_state
            assert old_ms is not None
            assert old_ms.mesh is mesh_serving_dp.serving_mesh()
            old_vectors = None  # oracle comes from the engine below

            # ingest a delta and refresh: seals an L0 generation; the
            # base's sharded copy graduates at MERGE time
            ops = []
            for i in range(600, 700):
                ops.append({"index": {"_index": "m", "_id": str(i)}})
                ops.append({"body": "x", "tag": "even",
                            "v": rng.standard_normal(16).tolist()})
            node.bulk(ops)
            node.indices.get("m").refresh()
            gc = store._gens["v"]
            snap_before = gc.snapshot()       # dispatch-before-install
            assert len(snap_before.generations) >= 2
            assert gc.force_merge()           # graduates the new base
            snap_after = gc.snapshot()
            base = snap_after.generations[0]
            assert base.n_rows == 700
            new_ms = base.mesh_state
            assert new_ms is not None and new_ms.n_rows == 700

            queries = rng.standard_normal((8, 16)).astype(np.float32)
            # oracle on the store's own serving dtype (bf16), so replica
            # boards are byte-comparable to it
            ref_corpus = knn_ops.build_corpus(
                np.asarray(base.host_vectors, dtype=np.float32),
                metric="cosine", dtype="bf16")
            s_ref, i_ref = knn_ops.knn_search(
                jnp.asarray(queries), ref_corpus, 10, metric="cosine",
                precision="bf16")
            s_ref, i_ref = np.asarray(s_ref), np.asarray(i_ref)
            boards = []
            for route in ((new_ms.mesh,)
                          + tuple(mesh_serving_dp.dp_groups())):
                q = jax.device_put(jnp.asarray(queries),
                                   mesh_lib.query_sharding(route))
                s, g = distributed_knn_search(
                    q, new_ms.corpus_for(route), 10, route,
                    metric="cosine", precision="bf16")
                boards.append((np.asarray(s),
                               new_ms.map_ids(np.asarray(g))))
            # every replica view byte-identical to each other AND to the
            # single-device oracle over the merged host vectors
            for s, rows in boards:
                assert np.array_equal(rows, i_ref)
                assert s.tobytes() == s_ref.tobytes()

            # the pre-install snapshot still serves its own coherent
            # version: the old base's sharded copy reads valid buffers
            # (copy-on-write install) and answers for the OLD corpus
            old_base = snap_before.generations[0]
            assert old_base.mesh_state is old_ms
            group0 = mesh_serving_dp.dp_groups()[0]
            q = jax.device_put(jnp.asarray(queries),
                               mesh_lib.query_sharding(group0))
            s_old, g_old = distributed_knn_search(
                q, old_ms.corpus_for(group0), 10, group0,
                metric="cosine", precision="bf16")
            old_ref_corpus = knn_ops.build_corpus(
                np.asarray(old_base.host_vectors, dtype=np.float32),
                metric="cosine", dtype="bf16")
            s_old_ref, i_old_ref = knn_ops.knn_search(
                jnp.asarray(queries), old_ref_corpus, 10,
                metric="cosine", precision="bf16")
            assert np.array_equal(old_ms.map_ids(np.asarray(g_old)),
                                  np.asarray(i_old_ref))
            assert np.asarray(s_old).tobytes() == \
                np.asarray(s_old_ref).tobytes()
        finally:
            node.close()

    def test_strict_zero_recompile_second_pass_over_dp_grid(
            self, mesh_serving_dp):
        """Warmup covers the full-mesh buckets AND every dp-group
        submesh; a strict-mode second pass over the whole dp grid (both
        routes, interactive buckets) must compile nothing."""
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import (
            distributed_knn_search)

        rng = np.random.default_rng(24)
        vectors = rng.standard_normal((512, 16)).astype(np.float32)
        mesh = mesh_serving_dp.serving_mesh()
        state = ShardedFieldState(vectors, mesh, "cosine", "f32")
        dispatch.DISPATCH.warmup(state.warmup_entries(16),
                                 background=False)
        before = dispatch.stats(per_bucket=False)
        old_strict = dispatch.DISPATCH.strict
        dispatch.DISPATCH.strict = True
        try:
            for route in (mesh,) + tuple(mesh_serving_dp.dp_groups()):
                for b in (8, 16):
                    qs = rng.standard_normal((b, 16)).astype(np.float32)
                    q = jax.device_put(
                        jnp.asarray(qs),
                        __import__("elasticsearch_tpu.parallel.mesh",
                                   fromlist=["query_sharding"])
                        .query_sharding(route))
                    distributed_knn_search(q, state.corpus_for(route),
                                           10, route, metric="cosine",
                                           precision="bf16", board=True)
        finally:
            dispatch.DISPATCH.strict = old_strict
        after = dispatch.stats(per_bucket=False)
        assert after["compiles"] == before["compiles"]
        assert after["out_of_grid_compiles"] == \
            before["out_of_grid_compiles"]
        assert after["hits"] > before["hits"]

    def test_dp_serving_through_store_parity(self, mesh_serving_dp):
        """End-to-end through Node.search on the (dp=2, shard=4) mesh:
        responses byte-identical to the mesh-off single-device path, and
        the mesh router actually routed (the store feeds batch + live
        queue depth into the dp split)."""
        node, rng = _make_node(tempfile.mkdtemp(), n=800, seed=25)
        try:
            qv = rng.standard_normal(16).tolist()
            body = {"knn": {"field": "v", "query_vector": qv, "k": 10,
                            "num_candidates": 50}, "size": 10}
            dp_resp = node.search("m", dict(body))
            st = mesh_serving_dp.stats()
            assert st["router"]["mesh"] >= 1
            assert st["dp"] == 2
            store = node.indices.get("m").shards[0].vector_store
            assert store.knn_stats["mesh_searches"] >= 1
            assert store.last_knn_phases["engine"] == "tpu_mesh"
            assert store.last_knn_phases["mesh_dp"] == 2
            mesh_serving_dp.configure(enabled=False)
            one_resp = node.search("m", dict(body))
            assert _strip_took(dp_resp) == _strip_took(one_resp)
        finally:
            node.close()
