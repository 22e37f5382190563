"""Interpret-mode parity for the Pallas binned kNN kernels (ROADMAP
portability slice): the north-star int8 Pallas path only compiles on TPU
backends, so without these tests its program structure was never
regression-tested in tier-1 — r06's `run_north_star_10m_int8` errored on
the CPU floor and PR 4 merely downgraded that to a labeled skip. Pallas
interpret mode executes the same kernel body with jnp semantics on any
backend, so structural regressions (packing/decode math, bin geometry,
dequant scales, validity masking) fail HERE instead of on the next TPU
capture."""

import numpy as np
import pytest

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import pallas_knn_binned as binned
from elasticsearch_tpu.ops import similarity as sim

N, D, K, NQ = 6000, 32, 4, 8  # one BLOCK_N tile, padded 6000 -> 8192


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(42)
    vecs = rng.standard_normal((N, D)).astype(np.float32)
    qs = rng.standard_normal((NQ, D)).astype(np.float32)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    qn = qs / np.linalg.norm(qs, axis=1, keepdims=True)
    exact = qn @ vn.T
    top_exact = np.argsort(-exact, axis=1)[:, :K]
    return vecs, qs, exact, top_exact


def _recall(ids, top_exact):
    return float(np.mean([len(set(ids[i]) & set(top_exact[i])) / K
                          for i in range(NQ)]))


def test_interpret_binned_matches_exact_structure(data):
    vecs, qs, exact, top_exact = data
    corpus = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="f32",
                                  pad_to=binned.BLOCK_N)
    s, ids = binned.binned_knn_search(np.asarray(qs), corpus, k=K,
                                     metric=sim.COSINE, interpret=True)
    s, ids = np.asarray(s), np.asarray(ids)
    # every returned id is a real (non-padding) row and its packed score
    # decodes to the true cosine of that row (bf16 matmul + 6 masked
    # mantissa bits bound the error)
    assert (ids >= 0).all() and (ids < N).all()
    for i in range(NQ):
        assert len(set(ids[i].tolist())) == K  # no duplicate winners
        for j in range(K):
            assert abs(s[i, j] - exact[i, ids[i, j]]) < 0.05
    # binned reduction keeps one candidate per 64-row bin: recall@k is
    # bounded by bin collisions, not broken structure
    assert _recall(ids, top_exact) >= 0.85


def test_interpret_binned_int8_and_rescore_paths(data):
    vecs, qs, exact, top_exact = data
    corpus = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8",
                                  pad_to=binned.BLOCK_N)
    _, ids = binned.binned_knn_search(np.asarray(qs), corpus, k=K,
                                      metric=sim.COSINE, interpret=True)
    base_recall = _recall(np.asarray(ids), top_exact)
    assert base_recall >= 0.7
    s8, ids8 = binned.binned_knn_search_rescored_packed(
        np.asarray(qs), corpus, k=K, metric=sim.COSINE,
        rescore_candidates=128, interpret=True)
    ids8 = np.asarray(ids8)
    assert (ids8 >= 0).all() and (ids8 < N).all()
    # rescoring re-ranks a superset of the base picks with the
    # unquantized query: it may only help
    assert _recall(ids8, top_exact) >= base_recall - 1e-9


def test_interpret_binned_validity_mask_excludes_padding(data):
    vecs, qs, _, _ = data
    # tiny corpus inside one tile: padding rows dominate and must never win
    small = vecs[:100]
    corpus = knn_ops.build_corpus(small, metric=sim.COSINE, dtype="f32",
                                  pad_to=binned.BLOCK_N)
    _, ids = binned.binned_knn_search(np.asarray(qs), corpus, k=K,
                                      metric=sim.COSINE, interpret=True)
    ids = np.asarray(ids)
    assert (ids < 100).all()


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_interpret_binned_query_tiles_match_one_tile(data, dtype, monkeypatch):
    """Buckets above QUERY_TILE walk a (corpus tile, query tile) grid.
    Every query row must come back exactly as it does from a one-tile
    call: the tiling changes which grid step scores a row, never its
    score (two corpus tiles, four query tiles of 8)."""
    vecs, qs, _, _ = data
    rng = np.random.default_rng(5)
    more = np.concatenate([vecs, rng.standard_normal(
        (4000, D)).astype(np.float32)])
    queries = np.concatenate([qs] * 4)            # 32 rows
    corpus = knn_ops.build_corpus(more, metric=sim.COSINE, dtype=dtype,
                                  pad_to=2 * binned.BLOCK_N)
    s1, i1 = binned.binned_knn_search(queries, corpus, k=K,
                                      metric=sim.COSINE, interpret=True)
    s1, i1 = np.asarray(s1), np.asarray(i1)
    monkeypatch.setattr(binned, "QUERY_TILE", 8)
    dispatch.DISPATCH.clear()   # the tile size is baked into the program
    try:
        s4, i4 = binned.binned_knn_search(queries, corpus, k=K,
                                          metric=sim.COSINE,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(i4), i1)
        np.testing.assert_array_equal(np.asarray(s4), s1)
    finally:
        dispatch.DISPATCH.clear()


def test_binned_route_is_a_rule_of_shapes_and_backend(monkeypatch):
    """The route to the Pallas kernel is decided from the backend, the
    metric and the corpus shape — and a probe that raises propagates
    instead of reading as "not an accelerator"."""
    import jax.numpy as jnp
    monkeypatch.setattr(dispatch, "backend_platform", lambda: "tpu")
    ok = dict(n_pad=1 << 20, d=768, matrix_dtype=jnp.bfloat16,
              metric=sim.COSINE)
    assert knn_ops.binned_route(**ok)
    assert not knn_ops.binned_route(**{**ok, "metric": sim.L2_NORM})
    assert not knn_ops.binned_route(**{**ok, "n_pad": (1 << 20) + 128})
    assert not knn_ops.binned_route(**{**ok, "matrix_dtype": jnp.uint8})
    assert not knn_ops.binned_route(**{**ok, "d": 4096})      # VMEM rule
    assert knn_ops.binned_route(**{**ok, "d": 4096,
                                   "matrix_dtype": jnp.int8})
    # padding follows the same rule
    assert knn_ops.preferred_pad_multiple(1 << 20, 768, "bf16") \
        == binned.BLOCK_N
    assert knn_ops.preferred_pad_multiple(1 << 20, 4096, "bf16") \
        == knn_ops.LANE
    assert knn_ops.preferred_pad_multiple(1 << 20, 768, "int4") \
        == knn_ops.LANE
    monkeypatch.setattr(dispatch, "backend_platform", lambda: "cpu")
    assert not knn_ops.binned_route(**ok)
    assert dispatch.pallas_interpret(None) is True

    def boom():
        raise RuntimeError("no backend")
    monkeypatch.setattr(dispatch, "backend_platform", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        knn_ops.binned_route(**ok)
    with pytest.raises(RuntimeError, match="no backend"):
        dispatch.pallas_interpret(None)
    assert dispatch.pallas_interpret(False) is False


def test_kernel_error_propagates_from_knn_search_auto(data, monkeypatch):
    """A kernel that fails must fail the search, not be swapped for the
    exact path behind the caller's back."""
    vecs, qs, _, _ = data
    corpus = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="bf16",
                                  pad_to=binned.BLOCK_N)
    monkeypatch.setattr(dispatch, "is_accelerator_backend", lambda: True)

    def boom(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")
    monkeypatch.setattr(binned, "binned_knn_search", boom)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        knn_ops.knn_search_auto(np.asarray(qs), corpus, k=K,
                                metric=sim.COSINE)


# ---------------------------------------------------------------------------
# fused IVF gather+score kernel (ops/pallas_ivf_fused.py): the scalar-
# prefetch gather must reproduce the scan-based probe scorer exactly
# ---------------------------------------------------------------------------

IVF_N, IVF_D, IVF_NLIST, IVF_NPROBE = 2048, 64, 32, 8


@pytest.fixture(scope="module")
def ivf_layouts():
    from elasticsearch_tpu.ann.ivf_index import build_ivf_index
    rng = np.random.default_rng(17)
    vecs = rng.standard_normal((IVF_N, IVF_D)).astype(np.float32)
    qs = rng.standard_normal((NQ, IVF_D)).astype(np.float32)
    out = {}
    for dt in ("f32", "bf16", "int8", "int4"):
        out[dt] = build_ivf_index(vecs, metric=sim.COSINE,
                                  nlist=IVF_NLIST, dtype=dt)
    return vecs, qs, out


@pytest.mark.parametrize("dt", ["f32", "bf16", "int8", "int4"])
def test_interpret_fused_probe_matches_scan_scorer(ivf_layouts, dt):
    """Byte parity of the fused gather+score board against the
    jnp.take-based scan scorer: identical winner rows, near-identical
    scores (both run the same bf16 matmul + dequant math)."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn_ivf
    from elasticsearch_tpu.ops import pallas_ivf_fused as fused
    _, qs, layouts = ivf_layouts
    parts = layouts[dt].device_partitions()
    q = knn_ivf._prep_queries(jnp.asarray(qs), sim.COSINE)
    probe_ids, _ = knn_ivf.route(q, parts, IVF_NPROBE, metric=sim.COSINE)
    s_scan, r_scan = knn_ivf.score_probes(q, parts, probe_ids, 10,
                                          metric=sim.COSINE)
    s_f, r_f = fused.fused_probe_scores(q, parts, probe_ids, 10,
                                        metric=sim.COSINE, interpret=True)
    np.testing.assert_array_equal(np.asarray(r_scan), np.asarray(r_f))
    np.testing.assert_allclose(np.asarray(s_scan), np.asarray(s_f),
                               rtol=2e-3, atol=2e-3)


def test_interpret_fused_probe_validity_mask_excludes_padding(ivf_layouts):
    """Partition-capacity padding rows (part_rows == -1, zero scales)
    must never win a top-k slot, even when probed partitions are mostly
    padding."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ann.ivf_index import build_ivf_index
    from elasticsearch_tpu.ops import knn_ivf
    from elasticsearch_tpu.ops import pallas_ivf_fused as fused
    rng = np.random.default_rng(23)
    tiny = rng.standard_normal((40, IVF_D)).astype(np.float32)
    idx = build_ivf_index(tiny, metric=sim.COSINE, nlist=4, dtype="f32")
    parts = idx.device_partitions()
    qs = rng.standard_normal((8, IVF_D)).astype(np.float32)
    q = knn_ivf._prep_queries(jnp.asarray(qs), sim.COSINE)
    probe_ids, _ = knn_ivf.route(q, parts, 4, metric=sim.COSINE)
    s, r = fused.fused_probe_scores(q, parts, probe_ids, 16,
                                    metric=sim.COSINE, interpret=True)
    s, r = np.asarray(s), np.asarray(r)
    real = r >= 0
    assert (r[real] < 40).all()
    assert (s[~real] < -1e37).all()  # padding slots carry the sentinel


def test_interpret_fused_probe_zero_recompile_second_pass(ivf_layouts):
    """The fused kernel's compile set is closed: a second pass over the
    warmed (Q bucket, nprobe, k) grid compiles nothing under strict
    dispatch."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn_ivf
    from elasticsearch_tpu.ops import pallas_ivf_fused as fused
    _, qs, layouts = ivf_layouts
    parts = layouts["int8"].device_partitions()
    q = knn_ivf._prep_queries(jnp.asarray(qs), sim.COSINE)
    probe_ids, _ = knn_ivf.route(q, parts, IVF_NPROBE, metric=sim.COSINE)
    fused.fused_probe_scores(q, parts, probe_ids, 10, metric=sim.COSINE,
                             interpret=True)
    before = dispatch.DISPATCH.compile_count()
    strict_before = dispatch.DISPATCH.strict
    dispatch.DISPATCH.strict = True
    try:
        fused.fused_probe_scores(q, parts, probe_ids, 10,
                                 metric=sim.COSINE, interpret=True)
    finally:
        dispatch.DISPATCH.strict = strict_before
    assert dispatch.DISPATCH.compile_count() == before


def test_interpret_binned_steady_state_zero_recompile(data):
    vecs, qs, _, _ = data
    corpus = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="f32",
                                  pad_to=binned.BLOCK_N)
    binned.binned_knn_search(np.asarray(qs), corpus, k=K,
                             metric=sim.COSINE, interpret=True)
    before = dispatch.DISPATCH.compile_count()
    strict_before = dispatch.DISPATCH.strict
    dispatch.DISPATCH.strict = True
    try:
        binned.binned_knn_search(np.asarray(qs), corpus, k=K,
                                 metric=sim.COSINE, interpret=True)
    finally:
        dispatch.DISPATCH.strict = strict_before
    assert dispatch.DISPATCH.compile_count() == before
