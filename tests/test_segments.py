"""Generational device segments (`elasticsearch_tpu/segments/`).

Pins the write-while-search lifecycle:
* byte-parity of generational vs monolithic search (appends, tombstoned
  rows, k deeper than one generation, per-query filters);
* merge-policy tier math (tier-full runs, L0 overflow, tombstone GC);
* copy-on-write safety — a search dispatched against a pre-merge
  snapshot lands correct results after the merge installs;
* the `segments.*` kernel grid stays closed under strict dispatch with a
  zero-recompile second pass;
* the pre-subsystem rebuild stall is counted (monolithic path) and the
  generational path reports zero rebuilds;
* mesh graduation (multidevice): a merge moves the base generation into
  the sharded corpus, result-identical.
"""

import tempfile
from collections import namedtuple

import numpy as np
import pytest

from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
from elasticsearch_tpu.index.segment import Segment, SegmentView, ShardReader
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.segments import TieredMergePolicy
from elasticsearch_tpu.segments.generation import generation_tier
from elasticsearch_tpu.vectors.store import VectorStoreShard

SEED = 42
DIMS = 16


def _seg(seg_id, base, mat, deleted=None):
    n = mat.shape[0]
    return Segment(
        seg_id=seg_id, base=base, num_docs=n, postings={},
        field_lengths={}, total_terms={}, doc_values={},
        vectors={"v": (mat, np.ones(n, dtype=bool))},
        ids=[f"d{base + i}" for i in range(n)], sources=[None] * n,
        seq_nos=np.arange(base, base + n, dtype=np.int64))


def _mapper(similarity="cosine"):
    return DenseVectorFieldMapper(
        "v", {"type": "dense_vector", "dims": DIMS,
              "similarity": similarity})


def _stores(**gen_kwargs):
    """(generational, monolithic) store pair: the monolithic store is
    the byte-parity oracle."""
    gen = VectorStoreShard(segments_enabled=True,
                           segments_background_merge=False, **gen_kwargs)
    mono = VectorStoreShard(segments_enabled=False)
    return gen, mono


def _corpus_segments(rng, sizes):
    segs, base = [], 0
    for i, n in enumerate(sizes):
        mat = rng.standard_normal((n, DIMS)).astype(np.float32)
        segs.append(_seg(i, base, mat))
        base += n
    return segs


def _sync_both(gen, mono, mapper, views):
    reader_a = ShardReader(views)
    gen.sync(reader_a, {"v": mapper})
    # a reader is a point-in-time object; give the second store its own
    mono.sync(ShardReader([SegmentView(v.segment) for v in views]),
              {"v": mapper})


def _assert_parity(gen, mono, rng, ks=(3, 10, 64), n_queries=4,
                   filter_rows=None):
    for _ in range(n_queries):
        q = rng.standard_normal(DIMS).astype(np.float32)
        for k in ks:
            a = gen.search("v", q, k, filter_rows=filter_rows)
            b = mono.search("v", q, k, filter_rows=filter_rows)
            assert np.array_equal(a[0], b[0]), (k, a[0], b[0])
            assert np.array_equal(a[1], b[1]), (k, a[1], b[1])


@pytest.fixture
def strict_dispatch():
    old = dispatch.DISPATCH.strict
    dispatch.DISPATCH.strict = True
    yield dispatch.DISPATCH
    dispatch.DISPATCH.strict = old


# ---------------------------------------------------------------------------
# Merge-policy tier math
# ---------------------------------------------------------------------------

FakeGen = namedtuple("FakeGen", "tier n_rows dead_rows")


def _fg(tier, rows=None, dead=0):
    return FakeGen(tier, rows if rows is not None else 128 << tier, dead)


class TestTieredMergePolicy:
    def test_tier_from_rows_follows_row_bucket_ladder(self):
        assert generation_tier(1) == 0
        assert generation_tier(128) == 0
        assert generation_tier(129) == 1
        assert generation_tier(256) == 1
        assert generation_tier(512) == 2
        assert generation_tier(100_000) == \
            (dispatch.bucket_gen_rows(100_000) // 128).bit_length() - 1

    def test_row_bucket_ladder_is_pow2_then_capped_multiples(self):
        assert dispatch.bucket_gen_rows(1) == 128
        assert dispatch.bucket_gen_rows(129) == 256
        assert dispatch.bucket_gen_rows(1 << 20) == 1 << 20
        assert dispatch.bucket_gen_rows((1 << 20) + 1) == 2 << 20
        assert dispatch.in_gen_row_grid(256)
        assert not dispatch.in_gen_row_grid(384)
        assert dispatch.in_gen_row_grid(3 << 20)

    def test_tier_full_run_merges_first_tier_size(self):
        pol = TieredMergePolicy(tier_size=3, max_l0=8)
        gens = [_fg(4), _fg(0), _fg(0), _fg(0), _fg(0)]
        spec = pol.select(gens)
        assert (spec.start, spec.stop, spec.reason) == (1, 4, "tier_full")

    def test_run_must_be_contiguous_same_tier(self):
        pol = TieredMergePolicy(tier_size=3, max_l0=8)
        gens = [_fg(4), _fg(0), _fg(1), _fg(0), _fg(1), _fg(0)]
        # no contiguous same-tier run of 3 and only 3 L0s (<= max_l0)
        assert pol.select(gens) is None

    def test_l0_overflow_merges_trailing_run(self):
        pol = TieredMergePolicy(tier_size=10, max_l0=3)
        gens = [_fg(4), _fg(0), _fg(0), _fg(0), _fg(0)]
        spec = pol.select(gens)
        assert (spec.start, spec.stop, spec.reason) == (1, 5,
                                                        "l0_overflow")

    def test_tombstone_gc_selects_mostly_dead_generation(self):
        pol = TieredMergePolicy(tier_size=10, max_l0=10,
                                gc_deleted_fraction=0.5)
        gens = [_fg(4, rows=2048, dead=100), _fg(1, rows=200, dead=150)]
        spec = pol.select(gens)
        assert (spec.start, spec.stop, spec.reason) == (1, 2,
                                                        "tombstone_gc")

    def test_steady_state_selects_nothing(self):
        pol = TieredMergePolicy(tier_size=4, max_l0=8)
        assert pol.select([_fg(5), _fg(3), _fg(1), _fg(0)]) is None
        assert pol.select([]) is None

    def test_force_merge_spec(self):
        assert TieredMergePolicy.force([_fg(2), _fg(0)]).reason == "force"
        assert TieredMergePolicy.force([_fg(2)]) is None
        assert TieredMergePolicy.force(
            [_fg(2, rows=512, dead=3)]) is not None


# ---------------------------------------------------------------------------
# Byte parity vs the monolithic path
# ---------------------------------------------------------------------------

class TestGenerationalParity:
    def test_append_refreshes_seal_and_stay_byte_identical(self):
        rng = np.random.default_rng(SEED)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [400, 60, 33, 200])
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
            _assert_parity(gen, mono, rng)
        st = gen.segment_stats()
        assert st["full_rebuilds"] == 0
        assert st["seals"] == 3
        assert st["rebuilds_avoided"] == 3
        assert st["generations"] == 4

    def test_k_deeper_than_one_generation(self):
        """k larger than every L0 (and the base) still merges exactly:
        a small generation contributes ALL its rows as candidates."""
        rng = np.random.default_rng(SEED + 1)
        gen, mono = _stores()
        mapper = _mapper("l2_norm")
        segs = _corpus_segments(rng, [150, 20, 40])
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
        _assert_parity(gen, mono, rng, ks=(25, 100, 210, 500))

    def test_deletes_become_tombstones_not_rebuilds(self):
        rng = np.random.default_rng(SEED + 2)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [300, 80])
        _sync_both(gen, mono, mapper, [SegmentView(s) for s in segs])
        # deletes across both generations
        views = [SegmentView(segs[0], deleted_locals={0, 17, 250}),
                 SegmentView(segs[1], deleted_locals={5})]
        gen.sync(ShardReader(views), {"v": mapper})
        mono.sync(ShardReader(
            [SegmentView(segs[0], deleted_locals={0, 17, 250}),
             SegmentView(segs[1], deleted_locals={5})]), {"v": mapper})
        _assert_parity(gen, mono, rng, ks=(5, 50, 380))
        st = gen.segment_stats()
        assert st["full_rebuilds"] == 0
        assert st["tombstoned_rows"] == 4
        assert st["tombstone_deletes"] == 4
        # deleted engine rows can never surface
        q = rng.standard_normal(DIMS).astype(np.float32)
        rows, _ = gen.search("v", q, 380)
        assert not np.isin([0, 17, 250, 305], rows).any()

    def test_filtered_search_parity_across_generations(self):
        rng = np.random.default_rng(SEED + 3)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [256, 64])
        _sync_both(gen, mono, mapper, [SegmentView(s) for s in segs])
        fr = np.sort(rng.choice(320, 90, replace=False)).astype(np.int64)
        _assert_parity(gen, mono, rng, ks=(10, 64), filter_rows=fr)

    def test_merges_consolidate_and_preserve_results(self):
        rng = np.random.default_rng(SEED + 4)
        gen, mono = _stores(segments_tier_size=3)
        mapper = _mapper()
        segs = _corpus_segments(rng, [300] + [50] * 5)
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
        gc = gen._gens["v"]
        before = gen.segment_stats()["generations"]
        assert gc.run_merges() >= 1
        after = gen.segment_stats()
        assert after["generations"] < before
        assert after["merges"] >= 1
        assert after["merge_nanos"] > 0
        _assert_parity(gen, mono, rng)
        # force-merge back to one clean generation
        assert gc.force_merge()
        assert gen.segment_stats()["generations"] == 1
        _assert_parity(gen, mono, rng)

    def test_background_merge_thread_drains(self):
        rng = np.random.default_rng(SEED + 5)
        gen = VectorStoreShard(segments_enabled=True,
                               segments_tier_size=3,
                               segments_merge_budget_ms=5.0)
        mapper = _mapper()
        segs = _corpus_segments(rng, [300] + [40] * 5)
        for i in range(1, len(segs) + 1):
            gen.sync(ShardReader([SegmentView(s) for s in segs[:i]]),
                     {"v": mapper})
        gc = gen._gens["v"]
        gc.drain()
        st = gen.segment_stats()
        assert st["merges"] >= 1
        assert gc.merge_pending() is False

    def test_segment_rewrite_falls_back_to_one_rebuild(self):
        """An engine-level segment rewrite (rows re-based) cannot be
        expressed as a delta — it rebuilds, once, with its reason."""
        rng = np.random.default_rng(SEED + 6)
        gen, _ = _stores()
        mapper = _mapper()
        mat = rng.standard_normal((200, DIMS)).astype(np.float32)
        gen.sync(ShardReader([SegmentView(_seg(0, 0, mat))]),
                 {"v": mapper})
        # same vectors, rewritten into one segment at a different base
        gen.sync(ShardReader([SegmentView(_seg(7, 64, mat))]),
                 {"v": mapper})
        st = gen.segment_stats()
        assert st["full_rebuilds"] == 1
        assert st["rebuild_reasons"] == {"segment_rewrite": 1}

    def test_monolithic_path_counts_the_rebuild_stall(self):
        """satellite: with segments disabled, every delta refresh is a
        full-corpus rebuild — now counted + reasoned so the bench can
        hold the pre-subsystem cost against the generational row."""
        rng = np.random.default_rng(SEED + 7)
        mono = VectorStoreShard(segments_enabled=False)
        mapper = _mapper()
        segs = _corpus_segments(rng, [200, 40])
        mono.sync(ShardReader([SegmentView(segs[0])]), {"v": mapper})
        mono.sync(ShardReader([SegmentView(s) for s in segs]),
                  {"v": mapper})
        mono.sync(ShardReader(
            [SegmentView(segs[0], deleted_locals={3}),
             SegmentView(segs[1])]), {"v": mapper})
        st = mono.segment_stats()
        assert st["full_rebuilds"] == 2
        assert st["rebuild_reasons"] == {"append_headroom": 1,
                                         "deletes": 1}
        assert st["rebuilds_avoided"] == 0


# ---------------------------------------------------------------------------
# Copy-on-write + strict grid
# ---------------------------------------------------------------------------

class TestCopyOnWriteAndGrid:
    def test_search_dispatched_mid_merge_reads_old_generation_set(self):
        """A snapshot taken before a merge stays fully servable after
        the merge installs: the install is copy-on-write, nothing the
        old set references is mutated or donated."""
        rng = np.random.default_rng(SEED + 8)
        gen, mono = _stores(segments_tier_size=3)
        mapper = _mapper()
        segs = _corpus_segments(rng, [300] + [50] * 4)
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
        gc = gen._gens["v"]
        snap = gc.snapshot()
        q = rng.standard_normal(DIMS).astype(np.float32)
        expected = mono.search("v", q, 10)
        # "dispatch" against the pre-merge snapshot, then merge, then
        # land — exactly the pipelined path's ordering
        handle = gen._dispatch_generational(
            snap, gen.field("v"), 10, "bf16", [(q, None)], None)
        assert gc.run_merges() >= 1
        assert gc.snapshot().generations != snap.generations
        (rows, scores), = gen.finalize_many(handle)
        assert np.array_equal(rows, expected[0])
        assert np.array_equal(scores, expected[1])
        # and the old snapshot still dispatches fresh searches correctly
        handle2 = gen._dispatch_generational(
            snap, gen.field("v"), 10, "bf16", [(q, None)], None)
        (rows2, scores2), = gen.finalize_many(handle2)
        assert np.array_equal(rows2, expected[0])

    def test_tombstone_install_is_copy_on_write(self):
        rng = np.random.default_rng(SEED + 9)
        gen, _ = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [200, 40])
        _sync_both(gen, VectorStoreShard(segments_enabled=False),
                   mapper, [SegmentView(s) for s in segs])
        gc = gen._gens["v"]
        snap = gc.snapshot()
        old_tombstones = [g.tombstones for g in snap.generations]
        gen.sync(ShardReader([SegmentView(segs[0], deleted_locals={1}),
                              SegmentView(segs[1])]), {"v": mapper})
        # the old snapshot's generations were replaced, never mutated
        for t in old_tombstones:
            assert not t.any()
        assert gc.snapshot().dead_rows == 1

    def test_segments_grid_strict_zero_recompile_second_pass(
            self, strict_dispatch):
        """The `segments.*` kernel grid is CLOSED: first pass compiles
        in-grid under strict mode, an identical second pass runs
        entirely from the executable cache."""
        rng = np.random.default_rng(SEED + 10)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [500, 37, 150])
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
        q = rng.standard_normal(DIMS).astype(np.float32)
        fr = np.arange(0, 600, 3, dtype=np.int64)
        first = gen.search("v", q, 10)
        first_f = gen.search("v", q, 10, filter_rows=fr)
        c0 = dispatch.DISPATCH.compile_count()
        again = gen.search("v", q, 10)
        again_f = gen.search("v", q, 10, filter_rows=fr)
        assert dispatch.DISPATCH.compile_count() == c0, \
            "segments second pass recompiled"
        assert np.array_equal(first[0], again[0])
        assert np.array_equal(first_f[0], again_f[0])
        buckets = dispatch.DISPATCH.stats()["buckets"]
        assert any(k.startswith("segments.knn") for k in buckets)

    def test_sealed_generation_warmup_entries_precompile(self):
        rng = np.random.default_rng(SEED + 11)
        gen, _ = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [200, 40])
        gen.sync(ShardReader([SegmentView(s) for s in segs[:1]]),
                 {"v": mapper})
        gen.sync(ShardReader([SegmentView(s) for s in segs]),
                 {"v": mapper})
        l0 = gen._gens["v"].snapshot().generations[1]
        entries = l0.warmup_entries(DIMS, "cosine")
        assert entries and all(e[0] == "segments.knn" for e in entries)
        dispatch.DISPATCH.warmup(entries, background=False)
        c0 = dispatch.DISPATCH.compile_count()
        dispatch.DISPATCH.warmup(entries, background=False)
        assert dispatch.DISPATCH.compile_count() == c0


# ---------------------------------------------------------------------------
# Node-level wiring: profile + stats + settings
# ---------------------------------------------------------------------------

class TestNodeWiring:
    def test_profile_and_stats_sections(self):
        from elasticsearch_tpu.node import Node
        node = Node(tempfile.mkdtemp())
        try:
            node.create_index_with_templates(
                "t", mappings={"properties": {
                    "v": {"type": "dense_vector", "dims": 8}}})
            rng = np.random.default_rng(5)
            for batch in range(3):
                for i in range(30):
                    node.index_doc("t", f"{batch}_{i}",
                                   {"v": rng.standard_normal(8).tolist()})
                node.indices.get("t").refresh()
            node.delete_doc("t", "0_0")
            node.indices.get("t").refresh()
            body = {"knn": {"field": "v",
                            "query_vector":
                                rng.standard_normal(8).tolist(),
                            "k": 5, "num_candidates": 5},
                    "size": 5, "profile": True}
            resp = node.search("t", body)
            knn_prof = resp["profile"]["shards"][0]["knn"]
            assert knn_prof["engine"] == "tpu_generational"
            assert knn_prof["generations"] >= 2
            assert knn_prof["tombstoned_rows"] == 1
            seg = node.local_node_stats()["indices"]["segments"]["device"]
            assert seg["full_rebuilds"] == 0
            assert seg["rebuilds_avoided"] >= 2
            assert seg["seals"] >= 2
            assert seg["generations"] >= 2
            assert seg["tiers"]
        finally:
            node.close()

    def test_segments_settings_validation(self):
        from elasticsearch_tpu.common.errors import IllegalArgumentError
        from elasticsearch_tpu.indices.service import (
            validate_segments_settings)
        out = validate_segments_settings({
            "index.segments.enabled": "false",
            "index.segments.tier_size": "6",
            "index.segments.max_l0": 4,
            "index.segments.merge_budget_ms": "25"})
        assert out == {"segments_enabled": False,
                       "segments_tier_size": 6,
                       "segments_max_l0": 4,
                       "segments_merge_budget_ms": 25.0}
        with pytest.raises(IllegalArgumentError):
            validate_segments_settings({"index.segments.tier_size": 1})
        with pytest.raises(IllegalArgumentError):
            validate_segments_settings(
                {"index.segments.merge_budget_ms": "0"})

    def test_segments_disabled_setting_serves_monolithic(self):
        from elasticsearch_tpu.node import Node
        node = Node(tempfile.mkdtemp())
        try:
            node.create_index_with_templates(
                "t", settings={"index.segments.enabled": False},
                mappings={"properties": {
                    "v": {"type": "dense_vector", "dims": 8}}})
            shard = node.indices.get("t").shards[0]
            assert shard.vector_store.segments_enabled is False
        finally:
            node.close()


# ---------------------------------------------------------------------------
# Mesh graduation (SPMD) — rides the standalone strict recompile gate
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
class TestMeshGraduation:
    def test_merge_graduates_base_into_sharded_corpus(
            self, mesh_serving):
        """L0 generations stay single-device; a merge graduates the new
        base into the sharded serving corpus, result-identical, and the
        post-graduation grid holds a strict zero-recompile second
        pass."""
        rng = np.random.default_rng(SEED + 12)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [1600, 100])
        for i in range(1, len(segs) + 1):
            _sync_both(gen, mono, mapper,
                       [SegmentView(s) for s in segs[:i]])
        # fan-out: base rides the mesh leg, the L0 stays single-device
        _assert_parity(gen, mono, rng, ks=(10,))
        assert gen.knn_stats["mesh_searches"] >= 1
        gc = gen._gens["v"]
        assert gc.force_merge()
        base = gc.snapshot().generations[0]
        assert base.mesh_state is not None, \
            "merge did not graduate into the sharded corpus"
        assert base.mesh_state.n_rows == 1700
        _assert_parity(gen, mono, rng, ks=(10, 64))
        # strict zero-recompile second pass over the graduated grid
        q = rng.standard_normal(DIMS).astype(np.float32)
        gen.search("v", q, 10)
        old_strict = dispatch.DISPATCH.strict
        dispatch.DISPATCH.strict = True
        try:
            c0 = dispatch.DISPATCH.compile_count()
            gen.search("v", q, 10)
            assert dispatch.DISPATCH.compile_count() == c0
        finally:
            dispatch.DISPATCH.strict = old_strict

    def test_tombstoned_mesh_base_masks_in_spmd(self, mesh_serving):
        rng = np.random.default_rng(SEED + 13)
        gen, mono = _stores()
        mapper = _mapper()
        segs = _corpus_segments(rng, [1600])
        _sync_both(gen, mono, mapper, [SegmentView(segs[0])])
        dead = set(range(12))
        gen.sync(ShardReader([SegmentView(segs[0],
                                          deleted_locals=dead)]),
                 {"v": mapper})
        mono.sync(ShardReader([SegmentView(segs[0],
                                           deleted_locals=dead)]),
                  {"v": mapper})
        _assert_parity(gen, mono, rng, ks=(10, 100))
        q = rng.standard_normal(DIMS).astype(np.float32)
        rows, _ = gen.search("v", q, 100)
        assert not np.isin(sorted(dead), rows).any()
        assert gen.segment_stats()["full_rebuilds"] == 0
