"""Vector codec subsystem (`elasticsearch_tpu/quant/`).

Pins the quantization-ladder contracts:
* encode host-vs-device parity — every codec's np and jnp twins produce
  BYTE-identical packed data (scales allclose: float reduction order),
  and the host decode twin round-trips within the rung's error bound;
* recall gates per rung on the 768-d clustered bench shape — int4 and
  binary(Hamming) + exact rescore both hold recall@10 >= 0.95 vs exact
  f32 at their default oversamples;
* the store-level two-phase path (`index_options` int4_flat /
  binary_flat / int4_ivf): recall, rescore counters, profile phases,
  and the `rescore_oversample` small fix;
* dtype changes run on the MERGE thread: an int8→int4 mapping update
  never full-rebuilds on the serving path (`dtype_change` rebuilds stay
  0), searches stay byte-stable during the re-encode, and the budgeted
  merger installs the re-encoded generations;
* per-segment ENCODED blocks cache in the columnar store like f32 rows
  (delta composition on append);
* mesh byte parity for packed corpora (multidevice).
"""

import numpy as np
import pytest

from elasticsearch_tpu.common.errors import MapperParsingError
from elasticsearch_tpu.index.mapping import DenseVectorFieldMapper
from elasticsearch_tpu.index.segment import Segment, SegmentView, ShardReader
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.quant import codec as quant_codec
from elasticsearch_tpu.quant import rescore as quant_rescore
from elasticsearch_tpu.vectors.store import VectorStoreShard

SEED = 11


# ---------------------------------------------------------------------------
# codec registry: host/device twins, round-trips, accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["int8", "int4", "binary"])
def test_encode_np_and_jnp_twins_byte_parity(name):
    """The np and jnp encoders implement ONE recipe: packed bytes are
    identical; scales agree to reduction-order float noise."""
    import jax.numpy as jnp
    rng = np.random.default_rng(SEED)
    mat = rng.standard_normal((128, 64)).astype(np.float32) * 3.0
    codec = quant_codec.get(name)
    enc = codec.encode_np(mat)
    data_j, scales_j = codec.encode_jnp(jnp.asarray(mat))
    np.testing.assert_array_equal(enc.data, np.asarray(data_j))
    np.testing.assert_allclose(enc.scales, np.asarray(scales_j), rtol=1e-6)


@pytest.mark.parametrize("name,rel", [("int8", 1 / 254), ("int4", 1 / 14)])
def test_scalar_decode_roundtrip_bound(name, rel):
    """Symmetric max-abs scaling bounds per-element error by half a
    quantization step of the row's max magnitude."""
    rng = np.random.default_rng(SEED + 1)
    mat = rng.standard_normal((64, 32)).astype(np.float32)
    codec = quant_codec.get(name)
    enc = codec.encode_np(mat)
    recon = codec.decode_np(enc.data, enc.scales)
    bound = np.abs(mat).max(axis=1)[:, None] * rel + 1e-6
    assert (np.abs(recon - mat) <= bound).all()


def test_binary_decode_is_sign_times_mean_abs():
    rng = np.random.default_rng(SEED + 2)
    mat = rng.standard_normal((16, 64)).astype(np.float32)
    codec = quant_codec.get("binary")
    enc = codec.encode_np(mat)
    recon = codec.decode_np(enc.data, enc.scales)
    np.testing.assert_array_equal(np.sign(recon), np.where(mat >= 0, 1, -1))
    np.testing.assert_allclose(
        np.abs(recon),
        np.broadcast_to(np.abs(mat).mean(axis=1)[:, None], mat.shape),
        rtol=1e-5)


def test_bytes_per_doc_ladder_and_single_chip_density():
    """The ladder's density story at the bench shape (768 d): binary
    clears 100M docs in a 16 GB HBM chip; int8 does not."""
    assert quant_codec.bytes_per_doc("f32", 768) == 768 * 4 + 4
    assert quant_codec.bytes_per_doc("bf16", 768) == 768 * 2 + 4
    assert quant_codec.bytes_per_doc("int8", 768) == 768 + 8
    assert quant_codec.bytes_per_doc("int4", 768) == 384 + 8
    assert quant_codec.bytes_per_doc("binary", 768) == 96 + 8
    hbm = 16 * 1024**3
    assert hbm // quant_codec.bytes_per_doc("binary", 768) >= 100_000_000
    assert hbm // quant_codec.bytes_per_doc("int8", 768) < 100_000_000


def test_packed_dims_constraints():
    with pytest.raises(ValueError):
        quant_codec.get("int4").encode_np(np.zeros((2, 7), np.float32))
    with pytest.raises(ValueError):
        quant_codec.get("binary").encode_np(np.zeros((2, 48), np.float32))


def test_unknown_codec_raises():
    with pytest.raises(KeyError):
        quant_codec.get("int2")


# ---------------------------------------------------------------------------
# recall gates per rung (the 768-d clustered bench shape, ops-level)
# ---------------------------------------------------------------------------

def _bench_shape(n=8192, d=768, nq=16):
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((64, d)).astype(np.float32) * 2.0
    vecs = (centers[rng.integers(0, 64, size=n)]
            + rng.standard_normal((n, d)).astype(np.float32))
    qs = (vecs[rng.integers(0, n, size=nq)]
          + 0.3 * rng.standard_normal((nq, d)).astype(np.float32))
    return vecs, qs


@pytest.fixture(scope="module")
def bench_shape():
    import jax.numpy as jnp
    vecs, qs = _bench_shape()
    c32 = knn_ops.build_corpus(vecs, dtype="f32")
    _, i_ref = knn_ops.knn_search(jnp.asarray(qs), c32, 10, precision="f32")
    return vecs, qs, np.asarray(i_ref)


@pytest.mark.parametrize("encoding", ["int4", "binary"])
def test_two_phase_recall_gate(bench_shape, encoding):
    """Coarse packed top-(k·oversample) + exact f32 rescore holds
    recall@10 >= 0.95 vs exact f32 at the DEFAULT oversample."""
    import jax.numpy as jnp
    vecs, qs, i_ref = bench_shape
    corpus = knn_ops.build_corpus(vecs, dtype=encoding)
    over = quant_rescore.DEFAULT_OVERSAMPLE[encoding]
    w = quant_rescore.coarse_window(10, over, limit=corpus.matrix.shape[0])
    k_b = dispatch.bucket_k(w, limit=corpus.matrix.shape[0])
    s, i = knn_ops.knn_search(jnp.asarray(qs), corpus, k_b)
    s, i = np.asarray(s)[:, :w], np.asarray(i)[:, :w]
    out_s, out_i, stats = quant_rescore.rescore_boards(
        qs, s, i, 10, lambda u: vecs[u], sim.COSINE)
    nq = len(qs)
    recall = np.mean([len(set(out_i[r]) & set(i_ref[r])) / 10
                      for r in range(nq)])
    assert recall >= 0.95, (encoding, recall)
    assert stats["window"] == w
    # rescored scores are EXACT f32 raw similarities
    qn = qs / np.linalg.norm(qs, axis=-1, keepdims=True)
    vn = vecs / np.linalg.norm(vecs, axis=-1, keepdims=True)
    for r in range(3):
        expect = np.einsum("d,cd->c", qn[r], vn[out_i[r]])
        np.testing.assert_allclose(out_s[r], expect, rtol=1e-5, atol=1e-6)


def test_corpus_from_encoded_blocks_is_byte_identical(bench_shape):
    """The columnar encoded-block assembly equals the monolithic encode
    byte for byte (rows encode independently)."""
    vecs, _, _ = bench_shape
    vecs = vecs[:1000]
    for encoding in ("int4", "binary"):
        mono = knn_ops.build_corpus(vecs, dtype=encoding)
        codec = quant_codec.get(encoding)
        normed = vecs / np.maximum(
            np.linalg.norm(vecs, axis=-1, keepdims=True), 1e-30)
        enc = codec.encode_np(normed)
        split = knn_ops.corpus_from_encoded(
            enc.data, enc.scales, vecs, dtype=encoding,
            pad_to=mono.matrix.shape[0])
        np.testing.assert_array_equal(np.asarray(mono.matrix),
                                      np.asarray(split.matrix))
        np.testing.assert_array_equal(np.asarray(mono.scales),
                                      np.asarray(split.scales))


# ---------------------------------------------------------------------------
# store-level integration (index_options → two-phase serving)
# ---------------------------------------------------------------------------

DIMS = 256


def _seg(seg_id, base, mat):
    n = mat.shape[0]
    return Segment(
        seg_id=seg_id, base=base, num_docs=n, postings={},
        field_lengths={}, total_terms={}, doc_values={},
        vectors={"v": (mat, np.ones(n, dtype=bool))},
        ids=[f"d{base + i}" for i in range(n)], sources=[None] * n,
        seq_nos=np.arange(base, base + n, dtype=np.int64))


def _mapper(otype=None, extra=None):
    params = {"type": "dense_vector", "dims": DIMS, "similarity": "cosine"}
    if otype is not None:
        opts = {"type": otype}
        opts.update(extra or {})
        params["index_options"] = opts
    return DenseVectorFieldMapper("v", params)


def _store(**kw):
    kw.setdefault("segments_background_merge", False)
    return VectorStoreShard(**kw)


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(3)
    centers = rng.standard_normal((16, DIMS)).astype(np.float32) * 2.0
    mat = (centers[rng.integers(0, 16, size=900)]
           + 0.5 * rng.standard_normal((900, DIMS)).astype(np.float32))
    # held-out-query style (the bench convention): perturbations of
    # corpus documents, not unrelated noise — a pure-noise query has no
    # meaningful neighbors for a recall gate to measure
    qs = (mat[rng.integers(0, 900, size=4)]
          + 0.3 * rng.standard_normal((4, DIMS)).astype(np.float32))
    return mat, qs


def _reader(*mats):
    segs, base = [], 0
    for i, m in enumerate(mats):
        segs.append(_seg(i, base, m))
        base += m.shape[0]
    return ShardReader([SegmentView(s) for s in segs])


class TestStoreTwoPhase:
    def test_packed_flat_recall_and_counters(self, clustered):
        mat, qs = clustered
        ref = _store()
        ref.sync(_reader(mat), {"v": _mapper()})
        for otype in ("int4_flat", "binary_flat"):
            st = _store()
            st.sync(_reader(mat), {"v": _mapper(otype)})
            hits = 0
            for q in qs:
                r_rows, _ = ref.search("v", q, 10, precision="f32")
                rows, scores = st.search("v", q, 10)
                assert len(rows) == 10
                hits += len(set(rows) & set(r_rows))
            assert hits / (10 * len(qs)) >= 0.9, otype
            assert st.knn_stats["rescore_searches"] == len(qs)
            assert st.last_knn_phases["rescore"]["window"] > 10
            fs = st.field_stats()["v"]
            assert fs["encoding"] == otype.split("_")[0]
            assert fs["bytes_per_doc"] == quant_codec.bytes_per_doc(
                fs["encoding"], DIMS)
            assert fs["rescore"] is True

    def test_rescore_oversample_is_honored(self, clustered):
        mat, qs = clustered
        st = _store()
        st.sync(_reader(mat), {"v": _mapper(
            "int4_flat", {"rescore_oversample": 7})})
        st.search("v", qs[0], 10)
        assert st.last_knn_phases["rescore"]["window"] == 70
        st2 = _store()
        st2.sync(_reader(mat), {"v": _mapper(
            "int4_flat", {"rescore": False})})
        st2.search("v", qs[0], 10)
        assert st2.knn_stats["rescore_searches"] == 0

    def test_unknown_index_options_type_raises_clearly(self, clustered):
        """The store-level small fix: a hand-built mapper with an
        unknown type must error, not silently serve f32 flat."""
        mat, _ = clustered
        mapper = _mapper()
        mapper.params["index_options"] = {"type": "int2_flat"}
        st = _store()
        with pytest.raises(MapperParsingError, match="int2_flat"):
            st.sync(_reader(mat), {"v": mapper})

    def test_mapper_validates_new_types_and_constraints(self):
        with pytest.raises(MapperParsingError):
            DenseVectorFieldMapper("v", {
                "type": "dense_vector", "dims": 31, "similarity": "cosine",
                "index_options": {"type": "binary_flat"}})
        with pytest.raises(MapperParsingError):
            DenseVectorFieldMapper("v", {
                "type": "dense_vector", "dims": 33, "similarity": "cosine",
                "index_options": {"type": "int4_flat"}})
        with pytest.raises(MapperParsingError):
            DenseVectorFieldMapper("v", {
                "type": "dense_vector", "dims": 64,
                "similarity": "l2_norm",
                "index_options": {"type": "binary_flat"}})
        # MIP rankings depend on magnitudes the sign sketch discards
        with pytest.raises(MapperParsingError):
            DenseVectorFieldMapper("v", {
                "type": "dense_vector", "dims": 64,
                "similarity": "max_inner_product",
                "index_options": {"type": "binary_flat"}})
        with pytest.raises(MapperParsingError):
            DenseVectorFieldMapper("v", {
                "type": "dense_vector", "dims": 64, "similarity": "cosine",
                "index_options": {"type": "int4_flat",
                                  "rescore_oversample": 0}})

    def test_int4_ivf_two_phase(self, clustered):
        mat, qs = clustered
        ref = _store()
        ref.sync(_reader(mat), {"v": _mapper()})
        st = _store()
        st.sync(_reader(mat), {"v": _mapper("int4_ivf", {"nprobe": 8})})
        hits = 0
        for q in qs:
            r_rows, _ = ref.search("v", q, 10, precision="f32")
            rows, _ = st.search("v", q, 10)
            hits += len(set(rows) & set(r_rows))
        assert st.knn_stats["ivf_searches"] == len(qs)
        assert st.knn_stats["rescore_searches"] == len(qs)
        # IVF prunes AND quantizes; the rescore window still recovers
        # most of exact top-10 on this clustered shape
        assert hits / (10 * len(qs)) >= 0.8


class TestDtypeChangeOnMergeThread:
    def test_reencode_never_full_rebuilds_and_stays_byte_stable(
            self, clustered):
        mat, qs = clustered
        st = _store()
        st.sync(_reader(mat), {"v": _mapper("int8_flat")})
        before = [st.search("v", q, 10) for q in qs]
        # mapping update int8 → int4: absorbed as a retarget, NOT a
        # serving-path rebuild
        st.sync(_reader(mat), {"v": _mapper("int4_flat")})
        assert st.segment_counters["full_rebuilds"] == 0
        assert st.segment_counters["rebuild_reasons"].get(
            "dtype_change", 0) == 0
        assert st.segment_counters["rebuilds_avoided"] == 1
        gc = st._gens["v"]
        assert gc.stats["dtype_retargets"] == 1
        # searches during the re-encode window serve the OLD encoding
        # byte-stably (the int8 base is still installed)
        for (b_rows, b_sc), q in zip(before, qs):
            rows, sc = st.search("v", q, 10)
            np.testing.assert_array_equal(rows, b_rows)
            np.testing.assert_array_equal(sc, b_sc)
        # the budgeted merger re-encodes on ITS thread
        assert gc.merge_pending()
        assert gc.run_merges() >= 1
        assert gc.stats["dtype_reencodes"] >= 1
        assert str(gc.snapshot().generations[0].corpus.matrix.dtype) \
            == "uint8"
        assert st.segment_counters["full_rebuilds"] == 0
        # post-re-encode serving is two-phase and keeps quality
        ref = _store()
        ref.sync(_reader(mat), {"v": _mapper()})
        hits = 0
        for q in qs:
            rows, _ = st.search("v", q, 10)
            r_rows, _ = ref.search("v", q, 10, precision="f32")
            hits += len(set(rows) & set(r_rows))
        assert hits / (10 * len(qs)) >= 0.9
        assert st.knn_stats["rescore_searches"] >= len(qs)

    def test_new_seals_encode_at_target_while_base_lags(self, clustered):
        mat, qs = clustered
        st = _store()
        st.sync(_reader(mat[:700]), {"v": _mapper("int8_flat")})
        st.sync(_reader(mat[:700], mat[700:]),
                {"v": _mapper("int4_flat")})
        gc = st._gens["v"]
        snap = gc.snapshot()
        dtypes = {str(g.corpus.matrix.dtype) for g in snap.generations}
        # mixed mid-transition: the int8 base serves beside the freshly
        # int4-sealed delta; search still answers
        assert dtypes == {"int8", "uint8"}
        rows, _ = st.search("v", qs[0], 10)
        assert len(rows) == 10
        gc.run_merges()
        snap = gc.snapshot()
        assert {str(g.corpus.matrix.dtype)
                for g in snap.generations} == {"uint8"}


class TestEncodedColumnarBlocks:
    def test_encoded_blocks_cache_delta_on_append(self, clustered):
        from elasticsearch_tpu import columnar
        mat, _ = clustered
        columnar.STORE.reset()
        # segment OBJECTS persist across refreshes (the engine's NRT
        # contract the weakref block cache keys on)
        seg0, seg1 = _seg(0, 0, mat[:600]), _seg(1, 600, mat[600:])
        st = _store(segments_enabled=False)
        st.sync(ShardReader([SegmentView(seg0)]),
                {"v": _mapper("int4_flat")})
        stats = columnar.STORE.stats()
        enc = stats["fields"].get("v:vector_enc")
        assert enc is not None and enc["extracts"] == 1
        # append-only refresh: the old segment's ENCODED block is a
        # cache hit; only the delta segment encodes
        st.sync(ShardReader([SegmentView(seg0), SegmentView(seg1)]),
                {"v": _mapper("int4_flat")})
        stats = columnar.STORE.stats()
        enc = stats["fields"]["v:vector_enc"]
        assert enc["extracts"] == 2 and enc["hits"] >= 1
        assert enc["compositions"]["delta"] == 1


@pytest.mark.multidevice
class TestMeshPackedParity:
    @pytest.mark.parametrize("encoding", ["int4", "binary"])
    def test_sharded_packed_matches_single_device(self, encoding):
        """A packed corpus served as ONE SPMD program returns the same
        rows/scores as the single-device packed kernel (byte parity —
        the shard-local math is identical and the merge is exact)."""
        import jax
        import jax.numpy as jnp

        from elasticsearch_tpu.parallel import mesh as mesh_lib
        from elasticsearch_tpu.parallel.sharded_knn import (
            build_sharded_corpus, distributed_knn_search)
        rng = np.random.default_rng(5)
        vecs = rng.standard_normal((1024, 64)).astype(np.float32)
        qs = rng.standard_normal((8, 64)).astype(np.float32)
        assert jax.device_count() >= 4
        mesh = mesh_lib.make_mesh(num_shards=4, dp=1)
        corpus, layout = build_sharded_corpus(
            vecs, mesh, metric=sim.COSINE, dtype=encoding)
        s_mesh, gids = distributed_knn_search(
            jnp.asarray(qs), corpus, k=10, mesh=mesh, metric=sim.COSINE)
        orig = layout.to_original_ids(np.asarray(gids))
        single = knn_ops.build_corpus(vecs, dtype=encoding)
        s_one, i_one = knn_ops.knn_search(jnp.asarray(qs), single, 10)
        s_one, i_one = np.asarray(s_one), np.asarray(i_one)
        for r in range(len(qs)):
            assert set(orig[r].tolist()) == set(i_one[r].tolist())
        np.testing.assert_allclose(np.sort(np.asarray(s_mesh), axis=1),
                                   np.sort(s_one, axis=1),
                                   rtol=1e-5, atol=1e-5)
