"""Late-interaction (`rank_vectors`) retrieval: fused gather+MaxSim
rescore over columnar token blocks (ops/pallas_maxsim.py +
vectors/late_interaction.py).

Contract tiers, following tests/test_pallas_parity.py:

* kernel vs reference twin: identical candidate ORDERING on separated
  scores, scores allclose to a few ULPs of bf16 — the interpret-mode
  grid loop can steer XLA CPU to a different accumulation order for
  the same per-pair dot, an artifact, not a semantics difference
  (f32 tolerance is tighter than the quantized rungs').
* end-to-end: device top-k recall@10 >= 0.95 vs the exact host walker
  (`late_interaction` query) on a clustered corpus at int8 AND int4,
  under the default oversample window.
"""

import tempfile

import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops.pallas_maxsim import (maxsim_reference,
                                                 maxsim_rescore)
from elasticsearch_tpu.quant import tokens as quant_tokens
from elasticsearch_tpu.search.queries import SearchContext, parse_query
from elasticsearch_tpu.vectors.late_interaction import (
    MAX_QUERY_TOKENS, LateInteractionField, LateInteractionShard)


def _clustered(rng, n_docs, dims, max_tokens, n_topics=12, noise=0.25):
    """Docs whose tokens scatter around a shared topic vector: the
    pooled-centroid coarse phase is informative (as it is for real
    ColBERT-style embeddings), so recall measures the full pipeline."""
    topics = rng.standard_normal((n_topics, dims)).astype(np.float32)
    docs = []
    for i in range(n_docs):
        t = topics[i % n_topics]
        nt = int(rng.integers(2, max_tokens + 1))
        docs.append((t + noise * rng.standard_normal((nt, dims)))
                    .astype(np.float32))
    return topics, docs


# --------------------------------------------------------------- kernel


class TestKernelParity:
    def _board(self, rng, encoding, n=24, cap=8, dims=32, nq=8, wc=16,
               tq=8):
        docs = [rng.standard_normal((int(rng.integers(1, cap + 1)),
                                     dims)).astype(np.float32)
                for _ in range(n)]
        w = quant_tokens.packed_width(encoding, dims)
        n_pad = 32
        dtype = np.uint8 if encoding == "int4" else None
        toks = None
        scales = np.zeros((n_pad, cap), dtype=np.float32)
        for i, d in enumerate(docs):
            prepped = quant_tokens.prep_tokens(d, "cosine")
            data, sc = quant_tokens.encode_tokens(prepped, encoding, dims)
            if toks is None:
                toks = np.zeros((n_pad, cap, w), dtype=data.dtype)
            toks[i, :len(d)] = data
            scales[i, :len(d)] = sc
        ids = rng.integers(0, n, size=(nq, wc)).astype(np.int32)
        q = np.zeros((nq, tq, quant_tokens.pad_dim(dims)),
                     dtype=np.float32)
        for qi in range(nq):
            nt = int(rng.integers(1, tq + 1))
            q[qi, :nt, :dims] = quant_tokens.prep_tokens(
                rng.standard_normal((nt, dims)).astype(np.float32),
                "cosine")
        # the kernel's resident scales layout: [n_pad, 1, cap]
        return ids, q, toks, scales[:, None, :]

    def test_f32_matches_reference_tightly(self):
        rng = np.random.default_rng(3)
        ids, q, toks, scales = self._board(rng, "f32")
        got = np.asarray(maxsim_rescore(ids, q, toks, scales))
        ref = np.asarray(maxsim_reference(ids, q, toks, scales))
        # bf16 operands: a few ULPs of drift from contraction order is
        # the ceiling; anything larger is a real math difference
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("encoding", ["bf16", "int8", "int4"])
    def test_quantized_ordering_and_scores(self, encoding):
        rng = np.random.default_rng(4)
        ids, q, toks, scales = self._board(rng, encoding)
        got = np.asarray(maxsim_rescore(ids, q, toks, scales))
        ref = np.asarray(maxsim_reference(ids, q, toks, scales))
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
        # candidate ordering per query must agree where scores are
        # separated beyond the contraction's ULP drift
        for qi in range(got.shape[0]):
            go, ro = np.argsort(-got[qi]), np.argsort(-ref[qi])
            gv, rv = got[qi][go], ref[qi][ro]
            sep = np.abs(np.diff(rv)) > 1e-2
            stable = np.concatenate([[True], sep]) \
                & np.concatenate([sep, [True]])
            assert np.array_equal(go[stable[:len(go)]],
                                  ro[stable[:len(ro)]])

    def test_zero_scale_padding_scores_neg_inf(self):
        rng = np.random.default_rng(5)
        ids, q, toks, scales = self._board(rng, "f32")
        ids[:, -1] = 31                      # all-zero padding row
        got = np.asarray(maxsim_rescore(ids, q, toks, scales))
        assert np.all(got[:, -1] <= -1e38)


# ---------------------------------------------------------------- field


def _engine(rng, n_docs=200, dims=16, encoding="int8", oversample=4,
            max_tokens=6, n_topics=12, noise=0.25):
    ms = MapperService({"properties": {
        "colv": {"type": "rank_vectors", "dims": dims,
                 "encoding": encoding, "oversample": oversample}}})
    eng = Engine(tempfile.mkdtemp(), ms)
    _topics, docs = _clustered(rng, n_docs, dims, max_tokens,
                               n_topics=n_topics, noise=noise)
    for i, d in enumerate(docs):
        eng.index(str(i), {"colv": d.tolist()})
    eng.refresh()
    return ms, eng, docs


def _oracle_topk(reader, ms, qtok, k):
    ctx = SearchContext(reader, ms)
    ds = parse_query({"late_interaction": {
        "field": "colv", "query_tokens": qtok.tolist()}}).execute(ctx)
    order = np.lexsort((ds.rows, -ds.scores))[:k]
    return ds.rows[order], ds.scores[order]


class TestRecall:
    @pytest.mark.parametrize("encoding", ["int8", "int4"])
    def test_recall_at_10_vs_exact_host_oracle(self, encoding):
        """ColBERT-shaped geometry: 64-dim tokens, ~8 docs per topic so
        a top-10 crosses cluster boundaries (separations above the int4
        step; within-cluster near-ties below it are legitimately
        unordered at 4 bits and are what oversample covers)."""
        rng = np.random.default_rng(7)
        ms, eng, docs = _engine(rng, dims=64, encoding=encoding,
                                oversample=8, n_topics=24, noise=0.8)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        hits = total = 0
        for t in range(12):
            base = docs[t * 7 % len(docs)][:4]
            qtok = base + 0.1 * rng.standard_normal(
                base.shape).astype(np.float32)
            (rows, _), = shard.search_batch(reader, mapper,
                                            [(qtok, 1.0)], 10)
            oracle_rows, _ = _oracle_topk(reader, ms, qtok, 10)
            hits += len(set(rows.tolist()) & set(oracle_rows.tolist()))
            total += 10
        recall = hits / total
        assert recall >= 0.95, f"{encoding} recall@10 {recall:.3f}"

    def test_full_window_matches_oracle_ordering(self):
        """oversample wide enough to cover the corpus: the coarse prune
        is a no-op, so device ordering equals the oracle's modulo int8
        quantization on near-ties."""
        rng = np.random.default_rng(9)
        ms, eng, docs = _engine(rng, n_docs=100, oversample=32)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        qtok = docs[5][:3]
        (rows, scores), = shard.search_batch(reader, mapper,
                                             [(qtok, 1.0)], 10)
        oracle_rows, oracle_scores = _oracle_topk(reader, ms, qtok, 10)
        assert len(set(rows.tolist()) & set(oracle_rows.tolist())) >= 9
        np.testing.assert_allclose(
            scores[:5], oracle_scores[:5], rtol=5e-2)

    def test_boost_scales_scores(self):
        rng = np.random.default_rng(10)
        ms, eng, docs = _engine(rng, n_docs=60)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        qtok = docs[3][:2]
        (r1, s1), = shard.search_batch(reader, mapper, [(qtok, 1.0)], 5)
        (r2, s2), = shard.search_batch(reader, mapper, [(qtok, 2.5)], 5)
        assert np.array_equal(r1, r2)
        np.testing.assert_allclose(s2, s1 * np.float32(2.5), rtol=1e-6)


class TestLifecycle:
    def test_append_delete_rebuild(self):
        rng = np.random.default_rng(11)
        ms = MapperService({"properties": {
            "colv": {"type": "rank_vectors", "dims": 8,
                     "oversample": 32}}})
        eng = Engine(tempfile.mkdtemp(), ms)
        for i in range(40):
            eng.index(str(i), {
                "colv": rng.standard_normal((3, 8)).tolist()})
        eng.refresh()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        reader = eng.acquire_searcher()
        qtok = rng.standard_normal((2, 8)).astype(np.float32)
        shard.search_batch(reader, mapper, [(qtok, 1.0)], 5)
        assert shard.stats["rebuilds"] == 1
        shard.search_batch(reader, mapper, [(qtok, 1.0)], 5)
        assert shard.stats["rebuilds"] == 1       # same reader

        for i in range(40, 60):
            eng.index(str(i), {
                "colv": rng.standard_normal((4, 8)).tolist()})
        eng.refresh()
        reader2 = eng.acquire_searcher()
        (rows, _), = shard.search_batch(reader2, mapper, [(qtok, 1.0)], 60)
        assert shard.stats["rebuilds"] == 2
        oracle_rows, _ = _oracle_topk(reader2, ms, qtok, 60)
        assert set(rows.tolist()) == set(oracle_rows.tolist())

        eng.delete("3")
        eng.refresh()
        reader3 = eng.acquire_searcher()
        (rows, _), = shard.search_batch(reader3, mapper, [(qtok, 1.0)], 60)
        assert shard.stats["rebuilds"] == 3
        assert not any(reader3.get_id(int(r)) == "3" for r in rows)

    def test_docs_without_field_are_absent(self):
        rng = np.random.default_rng(12)
        ms = MapperService({"properties": {
            "colv": {"type": "rank_vectors", "dims": 8,
                     "oversample": 32}}})
        eng = Engine(tempfile.mkdtemp(), ms)
        eng.index("a", {"colv": rng.standard_normal((2, 8)).tolist()})
        eng.index("b", {})
        eng.index("c", {"colv": rng.standard_normal((3, 8)).tolist()})
        eng.refresh()
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        lf = shard.field(reader, ms.get("colv"))
        assert lf.n_docs == 2
        (rows, _), = shard.search_batch(
            reader, ms.get("colv"),
            [(rng.standard_normal((2, 8)).astype(np.float32), 1.0)], 10)
        assert {reader.get_id(int(r)) for r in rows} == {"a", "c"}

    def test_padding_rows_reserved_and_never_surface(self):
        rng = np.random.default_rng(13)
        ms, eng, docs = _engine(rng, n_docs=33, oversample=32)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        lf = shard.field(reader, ms.get("colv"))
        assert lf.n_pad > lf.n_docs              # >= 1 all-zero row
        assert np.all(lf.tile_scales[lf.n_docs:] == 0.0)
        (rows, scores), = shard.search_batch(
            reader, ms.get("colv"), [(docs[0][:2], 1.0)], 33)
        assert len(rows) <= 33 and np.all(np.isfinite(scores))
        assert rows.max() < 33


class TestDispatchGrid:
    def test_strict_zero_recompile_second_pass(self):
        rng = np.random.default_rng(14)
        ms, eng, docs = _engine(rng, n_docs=120)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        queries = [(docs[i][:3], 1.0) for i in range(3)]
        shard.search_batch(reader, mapper, queries, 10)      # warm
        before = dispatch.DISPATCH.compile_count()
        strict_before = dispatch.DISPATCH.strict
        dispatch.DISPATCH.strict = True
        try:
            got = shard.search_batch(reader, mapper, queries, 10)
        finally:
            dispatch.DISPATCH.strict = strict_before
        assert got is not None
        assert dispatch.DISPATCH.compile_count() == before

    def test_warmup_entries_precompile_grid(self):
        rng = np.random.default_rng(15)
        ms, eng, docs = _engine(rng, n_docs=90)
        reader = eng.acquire_searcher()
        shard = LateInteractionShard()
        mapper = ms.get("colv")
        entries = shard.warmup_entries(reader, mapper)
        assert entries
        dispatch.DISPATCH.warmup(entries, background=False)
        before = dispatch.DISPATCH.compile_count()
        shard.search_batch(reader, mapper, [(docs[0][:3], 1.0)], 10)
        assert dispatch.DISPATCH.compile_count() == before


class TestNodePath:
    def test_three_leg_hybrid_and_fallback_count(self):
        from elasticsearch_tpu.node import Node
        rng = np.random.default_rng(16)
        n = Node(tempfile.mkdtemp())
        n.create_index_with_templates("li", mappings={"properties": {
            "body": {"type": "text"},
            "feats": {"type": "rank_features"},
            "colv": {"type": "rank_vectors", "dims": 16}}})
        _topics, docs = _clustered(rng, 80, 16, 5)
        ops = []
        for i, d in enumerate(docs):
            ops.append({"index": {"_index": "li", "_id": str(i)}})
            ops.append({"body": " ".join(rng.choice(list("abcd"), 4)),
                        "feats": {f"t{j}": 1.0
                                  for j in rng.integers(0, 20, 3)},
                        "colv": d.tolist()})
        n.bulk(ops)
        n.indices.get("li").refresh()
        try:
            body = {"rank": {"rrf": {}}, "sub_searches": [
                {"query": {"match": {"body": "a b"}}},
                {"query": {"sparse_vector": {
                    "field": "feats",
                    "query_vector": {"t1": 2.0, "t2": 1.0}}}},
                {"query": {"late_interaction": {
                    "field": "colv", "query_tokens": docs[0].tolist(),
                    "k": 10}}}], "size": 10}
            resp = n.search("li", body)
            assert len(resp["hits"]["hits"]) == 10
            ex = n._hybrid[n.indices.get("li").name]
            assert ex.late.stats["searches"] >= 1

            # over-grid query-token count -> counted walker fallback
            wide = rng.standard_normal(
                (MAX_QUERY_TOKENS + 4, 16)).tolist()
            n.search("li", {"rank": {"rrf": {}}, "sub_searches": [
                {"query": {"match": {"body": "a"}}},
                {"query": {"late_interaction": {
                    "field": "colv", "query_tokens": wide}}}],
                "size": 5})
            assert ex.stats["maxsim_grid_fallbacks"] >= 1
            hyb = n.local_node_stats()["indices"]["hybrid"]
            assert hyb["late_interaction"]["searches"] >= 1
            assert hyb["late_interaction"]["grid_fallbacks"] >= 1
            assert "colv" in hyb["late_interaction"]["fields"]
        finally:
            n.close()


class TestMapping:
    def test_rank_vectors_validation(self):
        from elasticsearch_tpu.common.errors import (
            IllegalArgumentError, MapperParsingError)
        with pytest.raises((IllegalArgumentError, MapperParsingError)):
            MapperService({"properties": {
                "c": {"type": "rank_vectors"}}})          # dims required
        with pytest.raises((IllegalArgumentError, MapperParsingError)):
            MapperService({"properties": {
                "c": {"type": "rank_vectors", "dims": 7,
                      "encoding": "int4"}}})              # odd dims
        ms = MapperService({"properties": {
            "c": {"type": "rank_vectors", "dims": 8}}})
        m = ms.get("c")
        assert (m.encoding, m.similarity, m.oversample) \
            == ("int8", "cosine", 4)

    def test_dims_mismatch_rejected_at_index_time(self):
        ms = MapperService({"properties": {
            "c": {"type": "rank_vectors", "dims": 8}}})
        eng = Engine(tempfile.mkdtemp(), ms)
        with pytest.raises(Exception):
            eng.index("x", {"c": [[1.0] * 5]})
