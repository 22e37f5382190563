"""Columnar segment block store (`elasticsearch_tpu/columnar/`).

Pins the PR 13 contract:
* byte parity — store-backed compositions are identical to the three
  retired private extractors (vector rows + row_map, agg value/ordinal
  columns, BM25 CSR) across append / delete / merge-style segment
  rewrite;
* O(delta) refresh — append-only refreshes extract ONLY delta segments,
  for all three consumers, counter-pinned (zero full-corpus
  compositions after first build);
* merge-does-not-pin — no device generation retains a private
  corpus-sized host array after seal or merge; blocks are zero-copy
  onto the engine segments where tombstones allow;
* eviction — dropping a segment releases its blocks (weak-keyed);
* dp-aware HBM budgeting (`parallel/policy.eligible`) — replication
  eligibility accounts dp× device bytes;
* stats/profile wiring — `_nodes/stats indices.columnar` and the
  `columnar` annotations in `profile.knn` / aggs profile.
"""

import gc as _gc
import json

import numpy as np
import pytest

from elasticsearch_tpu import columnar
from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
from elasticsearch_tpu.index.segment import (
    DocValuesColumn, Segment, SegmentView, ShardReader)
from elasticsearch_tpu.vectors.store import (
    VectorStoreShard, device_corpus_nbytes, extract_field_rows)

SEED = 7
DIMS = 12


def _seg(seg_id, base, mat, doc_values=None, deleted=None):
    n = mat.shape[0]
    return Segment(
        seg_id=seg_id, base=base, num_docs=n, postings={},
        field_lengths={}, total_terms={},
        doc_values=doc_values or {},
        vectors={"v": (mat, np.ones(n, dtype=bool))},
        ids=[f"d{base + i}" for i in range(n)], sources=[None] * n,
        seq_nos=np.arange(base, base + n, dtype=np.int64))


def _mapper():
    return DenseVectorFieldMapper(
        "v", {"type": "dense_vector", "dims": DIMS,
              "similarity": "cosine"})


def _oracle_vector_rows(reader, field):
    """The RETIRED extract_field_rows loop, verbatim — the parity
    oracle for the store-backed composition."""
    mats, rows = [], []
    for view in reader.views:
        seg = view.segment
        if field not in seg.vectors:
            continue
        mat, present = seg.vectors[field]
        keep = present & view.live
        locs = np.nonzero(keep)[0]
        if len(locs):
            mats.append(np.asarray(mat[locs], dtype=np.float32))
            rows.append(locs.astype(np.int64) + seg.base)
    if not mats:
        return (np.zeros((0, 0), dtype=np.float32),
                np.zeros(0, dtype=np.int64))
    return np.concatenate(mats, axis=0), np.concatenate(rows)


def _oracle_values_column(view, field, want_objs):
    """The RETIRED ops/aggs._extract_segment_column loop, verbatim."""
    seg = view.segment
    n_live = int(view.live.sum())
    col = seg.doc_values.get(field)
    vals = np.full(n_live, np.nan, dtype=np.float64)
    present = np.zeros(n_live, dtype=bool)
    objs = np.empty(n_live, dtype=object) if want_objs else None
    multi = False
    if col is not None and n_live:
        live_idx = np.nonzero(view.live)[0]
        raw = None
        if want_objs or col.numeric is None:
            raw = np.empty(n_live, dtype=object)
            for i, loc in enumerate(live_idx):
                v = col.values[int(loc)]
                raw[i] = v
                if isinstance(v, list):
                    multi = True
            if want_objs:
                objs = raw
        else:
            multi = any(isinstance(col.values[int(loc)], list)
                        for loc in live_idx)
        if col.numeric is not None:
            vals[:] = col.numeric[live_idx]
            present[:] = col.present[live_idx]
            vals[~present] = np.nan
        else:
            for i in range(n_live):
                v = raw[i]
                if isinstance(v, list):
                    v = v[0] if v else None
                if v is None:
                    continue
                if isinstance(v, bool):
                    vals[i] = 1.0 if v else 0.0
                    present[i] = True
                elif isinstance(v, (int, float)):
                    vals[i] = float(v)
                    present[i] = True
    return vals, present, objs, multi


# ---------------------------------------------------------------------------
# byte parity vs the retired extractors
# ---------------------------------------------------------------------------


class TestVectorParity:
    def _check(self, reader):
        full, rows = extract_field_rows(reader, "v")
        o_full, o_rows = _oracle_vector_rows(reader, "v")
        assert full.tobytes() == o_full.tobytes()
        assert np.array_equal(rows, o_rows)

    def test_append_delete_rewrite_lifecycle(self):
        rng = np.random.default_rng(SEED)
        mats = [rng.standard_normal((n, DIMS)).astype(np.float32)
                for n in (17, 9, 5)]
        s0, s1 = _seg(0, 0, mats[0]), _seg(1, 17, mats[1])
        self._check(ShardReader([SegmentView(s0)]))
        # append
        self._check(ShardReader([SegmentView(s0), SegmentView(s1)]))
        # delete (tombstones in an existing segment)
        self._check(ShardReader([SegmentView(s0, {2, 11}),
                                 SegmentView(s1)]))
        # more appends on top of the tombstoned view
        s2 = _seg(2, 26, mats[2])
        self._check(ShardReader([SegmentView(s0, {2, 11}),
                                 SegmentView(s1), SegmentView(s2)]))
        # engine merge/rewrite: one combined segment, new id, re-based
        merged = _seg(7, 0, np.concatenate(
            [np.delete(mats[0], [2, 11], axis=0), mats[1], mats[2]]))
        self._check(ShardReader([SegmentView(merged)]))

    def test_zero_copy_when_clean(self):
        rng = np.random.default_rng(SEED)
        mat = rng.standard_normal((8, DIMS)).astype(np.float32)
        s = _seg(11, 0, mat)
        view = columnar.STORE.vector_view(ShardReader([SegmentView(s)]),
                                          "v")
        assert len(view.blocks) == 1
        blk = view.blocks[0]
        assert blk.zero_copy
        assert np.shares_memory(blk.matrix, s.vectors["v"][0])
        # the store's added-RAM accounting excludes the shared matrix
        assert blk.nbytes == blk.rows.nbytes

    def test_empty_field_shape_matches_retired_extractor(self):
        s = Segment(seg_id=21, base=0, num_docs=3, postings={},
                    field_lengths={}, total_terms={}, doc_values={},
                    vectors={}, ids=["a", "b", "c"], sources=[None] * 3,
                    seq_nos=np.arange(3, dtype=np.int64))
        full, rows = extract_field_rows(
            ShardReader([SegmentView(s)]), "v")
        assert full.shape == (0, 0) and full.dtype == np.float32
        assert rows.shape == (0,) and rows.dtype == np.int64


class TestAggColumnParity:
    def _dv_seg(self, seg_id, base, values):
        n = len(values)
        mat = np.zeros((n, DIMS), dtype=np.float32)
        return _seg(seg_id, base, mat,
                    doc_values={"f": DocValuesColumn(list(values))})

    @pytest.mark.parametrize("want_objs", [False, True])
    def test_block_matches_retired_loop(self, want_objs):
        segs = [
            self._dv_seg(0, 0, [1, None, 3.5, [7, 8], 2]),
            self._dv_seg(1, 5, ["x", True, None, [True], 4]),
            self._dv_seg(2, 10, [10, 11, 12]),
        ]
        views = [SegmentView(segs[0], {1}), SegmentView(segs[1]),
                 SegmentView(segs[2])]
        for view in views:
            blk, _ = columnar.STORE.values_block(view, "f", want_objs)
            vals, present, objs, multi = _oracle_values_column(
                view, "f", want_objs)
            assert blk.vals.tobytes() == vals.tobytes()
            assert np.array_equal(blk.present, present)
            assert blk.multi_valued == multi
            if want_objs:
                assert list(blk.objs) == list(objs)
            else:
                assert blk.objs is None

    def test_agg_store_column_across_append_and_delete(self):
        from elasticsearch_tpu.ops.aggs import AggFieldStore
        store = AggFieldStore(warmup=False)
        segs = [self._dv_seg(0, 0, [5, 2, None, 9]),
                self._dv_seg(1, 4, [1, 1, 3])]
        r1 = ShardReader([SegmentView(s) for s in segs])
        col1 = store.column(r1, "f", want_ords=True)
        # oracle composition over the same views
        parts = [_oracle_values_column(v, "f", True) for v in r1.views]
        o_vals = np.concatenate([p[0] for p in parts])
        assert col1.vals[:len(o_vals)].tobytes() == o_vals.tobytes()
        assert col1.ords is not None
        # append a segment, delete a row: delta rebuild stays identical
        segs.append(self._dv_seg(2, 7, [4, None, 2]))
        r2 = ShardReader([SegmentView(segs[0], {1}), SegmentView(segs[1]),
                          SegmentView(segs[2])])
        col2 = store.column(r2, "f", want_ords=True)
        parts = [_oracle_values_column(v, "f", True) for v in r2.views]
        o_vals = np.concatenate([p[0] for p in parts])
        o_present = np.concatenate([p[1] for p in parts])
        assert col2.vals[:len(o_vals)].tobytes() == o_vals.tobytes()
        assert np.array_equal(col2.present[:len(o_present)], o_present)
        assert store.columnar_refresh["f"]["mode"] == "delta"


class TestBm25CsrParity:
    def _node(self, tmp):
        from elasticsearch_tpu.node import Node
        node = Node(tmp)
        node.create_index_with_templates(
            "t", mappings={"properties": {"body": {"type": "text"}}})
        words = ["alpha", "beta", "gamma", "delta", "epsilon"]
        ops = []
        for i in range(60):
            ops.append({"index": {"_index": "t", "_id": str(i)}})
            ops.append({"body": " ".join(
                words[j % 5] for j in range(i % 7 + 1))})
        node.bulk(ops)
        node.indices.get("t").refresh()
        return node

    def test_cold_vs_warm_store_identical_csr(self, tmp_path):
        from elasticsearch_tpu.ops.bm25 import LexicalField
        node = self._node(str(tmp_path))
        try:
            reader = node.indices.get("t").shards[0] \
                .engine.acquire_searcher()
            warm = LexicalField("body")
            warm.sync(reader)          # extracts blocks into the store
            cold = LexicalField("body")
            cold.sync(reader)          # pure cache hits
            assert cold.columnar_refresh["mode"] == "cached"
            for attr in ("tile_slots", "tile_impacts", "row_map"):
                assert getattr(cold, attr).tobytes() == \
                    getattr(warm, attr).tobytes()
            assert cold.term_tiles == warm.term_tiles
            assert cold.nnz == warm.nnz
            # delete + append: re-extraction parity against a store
            # rebuilt from scratch on the same reader
            node.delete_doc("t", "3")
            ops = [{"index": {"_index": "t", "_id": "new1"}},
                   {"body": "alpha zeta zeta"}]
            node.bulk(ops)
            node.indices.get("t").refresh()
            reader2 = node.indices.get("t").shards[0] \
                .engine.acquire_searcher()
            warm.sync(reader2)
            fresh = LexicalField("body")
            fresh.sync(reader2)
            for attr in ("tile_slots", "tile_impacts", "row_map"):
                assert getattr(fresh, attr).tobytes() == \
                    getattr(warm, attr).tobytes()
            assert fresh.term_tiles == warm.term_tiles
        finally:
            node.close()


# ---------------------------------------------------------------------------
# O(delta) refresh: counter-pinned across all three consumers
# ---------------------------------------------------------------------------


class TestDeltaRefresh:
    def test_append_only_refresh_extracts_only_delta_segments(self):
        """After first build, append-only refreshes must classify as
        'delta' for every consumer and never add a 'full' composition —
        the acceptance counter for the O(delta) claim."""
        from elasticsearch_tpu.ops.aggs import AggFieldStore
        from elasticsearch_tpu.ops.bm25 import LexicalField
        rng = np.random.default_rng(SEED)
        mapper = _mapper()
        vstore = VectorStoreShard(segments_enabled=True,
                                  segments_background_merge=False)
        astore = AggFieldStore(warmup=False)
        segs = [_seg(0, 0, rng.standard_normal((32, DIMS))
                     .astype(np.float32),
                     doc_values={"f": DocValuesColumn(list(range(32)))})]
        vstore.sync(ShardReader([SegmentView(s) for s in segs]),
                    {"v": mapper})
        astore.column(ShardReader([SegmentView(s) for s in segs]), "f")
        base_stats = columnar.STORE.stats()
        full0 = base_stats["compositions"]["full"]
        extracts0 = base_stats["extracts"]
        n_appends = 3
        for i in range(n_appends):
            base = sum(s.num_docs for s in segs)
            segs.append(_seg(i + 1, base,
                             rng.standard_normal((8, DIMS))
                             .astype(np.float32),
                             doc_values={"f": DocValuesColumn(
                                 list(range(base, base + 8)))}))
            reader = ShardReader([SegmentView(s) for s in segs])
            vstore.sync(reader, {"v": mapper})
            assert vstore.columnar_refresh["v"]["mode"] == "delta"
            assert vstore.columnar_refresh["v"]["extracted"] == 1
            astore.column(reader, "f")
            assert astore.columnar_refresh["f"]["mode"] == "delta"
            assert astore.columnar_refresh["f"]["extracted"] == 1
        st = columnar.STORE.stats()
        # ZERO full-corpus compositions during append-only ingest
        assert st["compositions"]["full"] == full0
        # extraction volume is the delta segments alone (vector + values
        # per new segment)
        assert st["extracts"] - extracts0 == 2 * n_appends

    def test_absent_field_extraction_is_cached_not_recounted(self):
        """A segment without the field caches an absent marker: repeat
        syncs are cache hits, so the extracts ledger can't inflate in
        fully-cached steady state (and the composition reports
        cached, not full)."""
        rng = np.random.default_rng(SEED)
        seg = _seg(55, 0, rng.standard_normal((4, DIMS))
                   .astype(np.float32))
        reader = ShardReader([SegmentView(seg)])
        before = columnar.STORE.stats()["extracts"]
        v1 = columnar.STORE.vector_view(reader, "no_such_field")
        assert v1.n_rows == 0 and v1.refresh["mode"] == "full"
        v2 = columnar.STORE.vector_view(reader, "no_such_field")
        assert v2.refresh["mode"] == "cached"
        assert columnar.STORE.stats()["extracts"] == before + 1

    def test_bm25_append_only_is_delta(self, tmp_path):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.ops.bm25 import LexicalField
        node = Node(str(tmp_path))
        try:
            node.create_index_with_templates(
                "t2", mappings={"properties": {
                    "body": {"type": "text"}}})
            ops = []
            for i in range(20):
                ops.append({"index": {"_index": "t2", "_id": str(i)}})
                ops.append({"body": f"alpha beta tok{i % 4}"})
            node.bulk(ops)
            node.indices.get("t2").refresh()
            shard = node.indices.get("t2").shards[0]
            lf = LexicalField("body")
            lf.sync(shard.engine.acquire_searcher())
            full0 = columnar.STORE.stats()["compositions"]["full"]
            ops = [{"index": {"_index": "t2", "_id": "a1"}},
                   {"body": "alpha gamma"}]
            node.bulk(ops)
            node.indices.get("t2").refresh()
            lf.sync(shard.engine.acquire_searcher())
            assert lf.columnar_refresh["mode"] == "delta"
            assert lf.columnar_refresh["extracted"] == 1
            assert columnar.STORE.stats()["compositions"]["full"] == full0
        finally:
            node.close()


# ---------------------------------------------------------------------------
# merge does not pin
# ---------------------------------------------------------------------------


class TestMergeDoesNotPin:
    def test_no_generation_pins_a_private_host_array(self):
        """Seed + appends + merges: every live generation's host rows
        resolve through shared blocks (private bytes == 0), the base
        blocks are zero-copy onto the engine segments, and the merged
        serving output stays byte-identical to a monolithic store."""
        rng = np.random.default_rng(SEED)
        mapper = _mapper()
        gen_store = VectorStoreShard(segments_enabled=True,
                                     segments_background_merge=False,
                                     segments_tier_size=2,
                                     segments_max_l0=2)
        mono = VectorStoreShard(segments_enabled=False)
        segs = [_seg(0, 0, rng.standard_normal((64, DIMS))
                     .astype(np.float32))]
        for i in range(4):
            base = sum(s.num_docs for s in segs)
            segs.append(_seg(i + 1, base,
                             rng.standard_normal((16, DIMS))
                             .astype(np.float32)))
            gen_store.sync(ShardReader([SegmentView(s) for s in segs]),
                           {"v": mapper})
        gc = gen_store._gens["v"]
        assert gc.run_merges() > 0
        snap = gc.snapshot()
        corpus_bytes = sum(s.num_docs for s in segs) * DIMS * 4
        for g in snap.generations:
            assert g.host_pinned_nbytes() == 0, \
                f"generation {g.gen_id} pins a private host array"
        # a merged generation's source still materializes correct rows
        merged = snap.generations[0]
        gathered = merged.source.gather()
        oracle = np.concatenate(
            [s.vectors["v"][0] for s in segs])[:merged.n_rows]
        assert gathered.tobytes() == oracle[:len(gathered)].tobytes()
        assert gathered.nbytes >= corpus_bytes // 2  # sanity: corpus-sized
        # serving byte parity vs the monolithic oracle
        mono.sync(ShardReader([SegmentView(s) for s in segs]),
                  {"v": mapper})
        for _ in range(3):
            q = rng.standard_normal(DIMS).astype(np.float32)
            a = gen_store.search("v", q, 10)
            b = mono.search("v", q, 10)
            assert np.array_equal(a[0], b[0])
            assert np.array_equal(a[1], b[1])

    def test_sealed_generation_source_reads_through_store(self):
        """An L0 seal's source points at the delta block (shared), not a
        private copy — and gathers the exact sealed rows."""
        rng = np.random.default_rng(SEED)
        mapper = _mapper()
        store = VectorStoreShard(segments_enabled=True,
                                 segments_background_merge=False)
        segs = [_seg(0, 0, rng.standard_normal((32, DIMS))
                     .astype(np.float32))]
        store.sync(ShardReader([SegmentView(s) for s in segs]),
                   {"v": mapper})
        delta = rng.standard_normal((8, DIMS)).astype(np.float32)
        segs.append(_seg(1, 32, delta))
        store.sync(ShardReader([SegmentView(s) for s in segs]),
                   {"v": mapper})
        snap = store._gens["v"].snapshot()
        assert len(snap.generations) == 2
        sealed = snap.generations[-1]
        assert sealed.host_pinned_nbytes() == 0
        assert sealed.source.gather().tobytes() == delta.tobytes()
        # zero-copy all the way down: the sealed source's matrix IS the
        # engine segment's array
        assert any(np.shares_memory(p.matrix, segs[1].vectors["v"][0])
                   for p in sealed.source.parts)


# ---------------------------------------------------------------------------
# eviction
# ---------------------------------------------------------------------------


class TestEviction:
    def test_dropped_segment_releases_blocks(self):
        rng = np.random.default_rng(SEED)
        seg = _seg(99, 0, rng.standard_normal((16, DIMS))
                   .astype(np.float32),
                   doc_values={"f": DocValuesColumn(list(range(16)))})
        reader = ShardReader([SegmentView(seg)])
        columnar.STORE.vector_view(reader, "v")
        columnar.STORE.values_block(reader.views[0], "f", False)
        before = columnar.STORE.stats()
        del reader, seg
        _gc.collect()
        after = columnar.STORE.stats()
        assert after["evictions"] >= before["evictions"] + 2
        assert after["blocks"] <= before["blocks"] - 2


# ---------------------------------------------------------------------------
# dp-aware HBM budgeting (PR 11 leftover c)
# ---------------------------------------------------------------------------


class TestHbmBudget:
    def test_eligibility_accounts_dp_times_device_bytes(self):
        from elasticsearch_tpu.parallel import policy
        import jax
        if len(jax.devices()) < 4:
            pytest.skip("needs a multi-device host")
        policy.reset(full=True)
        try:
            n_rows, dims = 100_000, 128
            bytes_one = device_corpus_nbytes(n_rows, dims, "bf16")
            policy.configure(enabled=True, min_rows=1, dp=2,
                             hbm_budget_bytes=bytes_one * 2)
            assert policy.serving_mesh() is not None
            # dp=2 × bytes_one fits the 2× budget exactly
            assert policy.eligible(n_rows, device_bytes=bytes_one)
            # a corpus whose replicated footprint exceeds it stays
            # single-device, and the rejection is counted
            assert not policy.eligible(n_rows,
                                       device_bytes=bytes_one + 1024)
            st = policy.stats()["hbm"]
            assert st["budget_bytes"] == bytes_one * 2
            assert st["rejections"] == 1
            assert st["last_rejected_bytes"] == (bytes_one + 1024) * 2
            assert st["accepted_bytes_high_water"] == bytes_one * 2
            # no budget configured → bytes are not a gate (legacy shape)
            policy.configure(hbm_budget_bytes=None)
            assert policy.eligible(n_rows, device_bytes=bytes_one * 100)
        finally:
            policy.reset(full=True)

    def test_device_corpus_nbytes_shapes(self):
        assert device_corpus_nbytes(1000, 64, "bf16") == \
            1000 * 64 * 2 + 4000
        assert device_corpus_nbytes(1000, 64, "int8") == \
            1000 * 64 + 4000 + 4000
        assert device_corpus_nbytes(0, 64, "f32") == 0


# ---------------------------------------------------------------------------
# stats + profile wiring
# ---------------------------------------------------------------------------


class TestStatsAndProfile:
    def test_node_stats_columnar_section_shape(self, tmp_path):
        from elasticsearch_tpu.node import Node
        node = Node(str(tmp_path))
        try:
            node.create_index_with_templates(
                "k", mappings={"properties": {
                    "v": {"type": "dense_vector", "dims": DIMS}}})
            rng = np.random.default_rng(SEED)
            ops = []
            for i in range(40):
                ops.append({"index": {"_index": "k", "_id": str(i)}})
                ops.append({"v": rng.standard_normal(DIMS).tolist()})
            node.bulk(ops)
            node.indices.get("k").refresh()
            st = node.local_node_stats()["indices"]["columnar"]
            for key in ("blocks", "bytes", "hits", "extracts",
                        "extract_nanos", "evictions", "compositions",
                        "fields", "zero_copy_blocks"):
                assert key in st
            assert st["extracts"] >= 1
            assert set(st["compositions"]) == {"cached", "delta", "full"}
            assert any(k.startswith("v:vector") for k in st["fields"])
        finally:
            node.close()

    def test_profile_knn_carries_columnar_annotation(self, tmp_path):
        from elasticsearch_tpu.node import Node
        node = Node(str(tmp_path))
        try:
            node.create_index_with_templates(
                "k2", mappings={"properties": {
                    "v": {"type": "dense_vector", "dims": DIMS}}})
            rng = np.random.default_rng(SEED)
            ops = []
            for i in range(30):
                ops.append({"index": {"_index": "k2", "_id": str(i)}})
                ops.append({"v": rng.standard_normal(DIMS).tolist()})
            node.bulk(ops)
            node.indices.get("k2").refresh()
            body = {"knn": {"field": "v",
                            "query_vector":
                                rng.standard_normal(DIMS).tolist(),
                            "k": 5, "num_candidates": 10},
                    "size": 5, "profile": True}
            resp = node.search("k2", body)
            prof = resp["profile"]["shards"][0]["knn"]
            assert "columnar" in prof
            assert prof["columnar"]["mode"] in ("full", "delta", "cached")
            assert prof["columnar"]["blocks"] >= 1
        finally:
            node.close()

    def test_aggs_profile_carries_columnar_annotation(self, tmp_path):
        from elasticsearch_tpu.node import Node
        node = Node(str(tmp_path))
        # the annotation is a device-path artifact (column builds); the
        # measured cost router would route this tiny corpus host
        node.settings["search.aggs.cost_router"] = "false"
        try:
            node.create_index_with_templates(
                "logs", mappings={"properties": {
                    "cat": {"type": "keyword"},
                    "val": {"type": "long"}}})
            ops = []
            for i in range(120):
                ops.append({"index": {"_index": "logs", "_id": str(i)}})
                ops.append({"cat": ["a", "b"][i % 2], "val": i})
            node.bulk(ops)
            node.indices.get("logs").refresh()
            body = {"size": 0, "profile": True,
                    "aggs": {"by": {"terms": {"field": "cat"}}}}
            resp = node.search("logs", json.loads(json.dumps(body)))
            shard = resp["profile"]["shards"][0]
            assert "columnar" in shard
            assert any(info["mode"] in ("full", "delta", "cached")
                       for info in shard["columnar"].values())
        finally:
            node.close()
