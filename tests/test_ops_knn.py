"""Unit tests for the device kNN ops (exactness vs numpy reference)."""

import numpy as np
import jax.numpy as jnp
import pytest

from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.ops import similarity as sim
from elasticsearch_tpu.ops.quantization import quantize_int8, dequantize_int8
from elasticsearch_tpu.ops.topk import masked_top_k, merge_top_k, top_k

RNG = np.random.default_rng(42)


def ref_scores(queries, corpus, metric):
    q = queries.astype(np.float64)
    c = corpus.astype(np.float64)
    if metric == sim.COSINE:
        q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-30)
        c = c / np.maximum(np.linalg.norm(c, axis=-1, keepdims=True), 1e-30)
        return q @ c.T
    if metric == sim.DOT_PRODUCT:
        return q @ c.T
    if metric == sim.L2_NORM:
        d = ((q[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        return -d
    raise ValueError(metric)


def recall_at_k(ids, ref_ids):
    hits = 0
    for row, ref_row in zip(ids, ref_ids):
        hits += len(set(row.tolist()) & set(ref_row.tolist()))
    return hits / ref_ids.size


@pytest.mark.parametrize("metric", [sim.COSINE, sim.DOT_PRODUCT, sim.L2_NORM])
def test_knn_exact_f32(metric):
    corpus = RNG.standard_normal((500, 32)).astype(np.float32)
    queries = RNG.standard_normal((7, 32)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=metric, dtype="f32")
    scores, ids = knn_ops.knn_search(jnp.asarray(queries), c, k=10,
                                     metric=metric, precision="f32")
    ref = ref_scores(queries, corpus, metric)
    ref_ids = np.argsort(-ref, axis=1)[:, :10]
    assert recall_at_k(np.asarray(ids), ref_ids) == 1.0
    ref_top = np.take_along_axis(ref, np.asarray(ids), axis=1)
    np.testing.assert_allclose(np.asarray(scores), ref_top, rtol=2e-4, atol=2e-4)


def test_knn_bf16_recall():
    corpus = RNG.standard_normal((2000, 64)).astype(np.float32)
    queries = RNG.standard_normal((16, 64)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.COSINE, dtype="bf16")
    _, ids = knn_ops.knn_search(jnp.asarray(queries), c, k=10, metric=sim.COSINE)
    ref = ref_scores(queries, corpus, sim.COSINE)
    ref_ids = np.argsort(-ref, axis=1)[:, :10]
    assert recall_at_k(np.asarray(ids), ref_ids) >= 0.95


def test_knn_int8_recall():
    corpus = RNG.standard_normal((2000, 64)).astype(np.float32)
    queries = RNG.standard_normal((16, 64)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.COSINE, dtype="int8")
    assert c.matrix.dtype == jnp.int8
    _, ids = knn_ops.knn_search(jnp.asarray(queries), c, k=10, metric=sim.COSINE)
    ref = ref_scores(queries, corpus, sim.COSINE)
    ref_ids = np.argsort(-ref, axis=1)[:, :10]
    assert recall_at_k(np.asarray(ids), ref_ids) >= 0.95


def test_padding_never_matches():
    corpus = RNG.standard_normal((3, 16)).astype(np.float32)  # pads to 128
    queries = RNG.standard_normal((2, 16)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.DOT_PRODUCT, dtype="f32")
    scores, ids = knn_ops.knn_search(jnp.asarray(queries), c, k=5,
                                     metric=sim.DOT_PRODUCT, precision="f32")
    ids = np.asarray(ids)
    scores = np.asarray(scores)
    # only 3 real hits; the rest must be NEG_INF sentinels
    assert (scores[:, 3:] < -1e37).all()
    assert set(ids[:, :3].flatten().tolist()) <= {0, 1, 2}


def test_filtered_knn():
    corpus = RNG.standard_normal((300, 16)).astype(np.float32)
    queries = RNG.standard_normal((4, 16)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.COSINE, dtype="f32")
    n_pad = c.matrix.shape[0]
    allowed = np.zeros(n_pad, dtype=bool)
    allowed_ids = RNG.choice(300, size=50, replace=False)
    allowed[allowed_ids] = True
    scores, ids = knn_ops.knn_search(jnp.asarray(queries), c, k=10, metric=sim.COSINE,
                                     filter_mask=jnp.asarray(allowed), precision="f32")
    assert set(np.asarray(ids).flatten().tolist()) <= set(allowed_ids.tolist())
    ref = ref_scores(queries, corpus, sim.COSINE)
    ref[:, ~allowed[:300]] = -np.inf
    ref_ids = np.argsort(-ref, axis=1)[:, :10]
    assert recall_at_k(np.asarray(ids), ref_ids) == 1.0


def test_blocked_matches_single_shot():
    corpus = RNG.standard_normal((1000, 32)).astype(np.float32)
    queries = RNG.standard_normal((5, 32)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.L2_NORM, dtype="f32", pad_to=1024)
    s1, i1 = knn_ops.knn_search(jnp.asarray(queries), c, k=10, metric=sim.L2_NORM,
                                precision="f32")
    s2, i2 = knn_ops.knn_search(jnp.asarray(queries), c, k=10, metric=sim.L2_NORM,
                                precision="f32", block_size=128)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5)


def test_merge_top_k_tiebreak_by_shard():
    # two shards produce identical scores; merged ids must prefer shard 0
    s = jnp.asarray([[[1.0, 0.5]], [[1.0, 0.5]]])  # [B=2, Q=1, k=2]
    i = jnp.asarray([[[10, 11]], [[20, 21]]])
    vals, ids = merge_top_k(s, i, k=2)
    assert ids[0, 0] == 10  # shard 0 wins the tie
    assert vals[0, 0] == 1.0


def test_masked_top_k():
    scores = jnp.asarray([[5.0, 4.0, 3.0, 2.0]])
    mask = jnp.asarray([[False, True, False, True]])
    vals, ids = masked_top_k(scores, mask, k=2)
    assert ids.tolist() == [[1, 3]]
    assert vals.tolist() == [[4.0, 2.0]]


def test_quantization_roundtrip():
    m = RNG.standard_normal((64, 32)).astype(np.float32) * 5
    q, scales = quantize_int8(jnp.asarray(m))
    deq = np.asarray(dequantize_int8(q, scales, dtype=jnp.float32))
    np.testing.assert_allclose(deq, m, atol=np.abs(m).max() / 127 + 1e-6)


def test_es_score_conventions():
    raw = jnp.asarray([1.0, 0.0, -1.0])
    np.testing.assert_allclose(np.asarray(sim.to_es_score(raw, sim.COSINE)), [1.0, 0.5, 0.0])
    d2 = jnp.asarray([-0.0, -1.0, -3.0])  # raw l2 = -distance^2
    np.testing.assert_allclose(np.asarray(sim.to_es_score(d2, sim.L2_NORM)), [1.0, 0.5, 0.25])


def test_binned_kernel_interpret_mode():
    """The binned Pallas kernel runs in interpreter mode on CPU and agrees
    with the exact path (small corpus → zero bin-collision loss)."""
    from elasticsearch_tpu.ops.pallas_knn_binned import binned_knn_search, BLOCK_N
    corpus = RNG.standard_normal((BLOCK_N * 2 - 100, 32)).astype(np.float32)
    queries = RNG.standard_normal((8, 32)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.COSINE, dtype="bf16",
                             pad_to=BLOCK_N * 2)
    s_b, i_b = binned_knn_search(jnp.asarray(queries), c, k=5, interpret=True)
    s_x, i_x = knn_ops.knn_search(jnp.asarray(queries), c, k=5, metric=sim.COSINE)
    i_b, i_x = np.asarray(i_b), np.asarray(i_x)
    overlap = np.mean([len(set(i_b[r]) & set(i_x[r])) / 5 for r in range(8)])
    assert overlap >= 0.8  # bf16 ties may reorder; bulk must agree
    # ids all within valid range
    assert (i_b < BLOCK_N * 2 - 100).all() if overlap == 1.0 else True


def test_knn_search_auto_cpu_fallback():
    corpus = RNG.standard_normal((500, 16)).astype(np.float32)
    queries = RNG.standard_normal((3, 16)).astype(np.float32)
    c = knn_ops.build_corpus(corpus, metric=sim.COSINE, dtype="f32")
    s, i = knn_ops.knn_search_auto(jnp.asarray(queries), c, k=5, metric=sim.COSINE,
                                   precision="f32")
    s2, i2 = knn_ops.knn_search(jnp.asarray(queries), c, k=5, metric=sim.COSINE,
                                precision="f32")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


def test_binned_rescore_variants_interpret_mode():
    """Packed-candidate rescore agrees with (or beats) the base binned
    kernel's recall against exact f32, and only returns valid rows
    (interpret-mode CPU check of the TPU recall-headroom variant)."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import knn as knn_ops
    from elasticsearch_tpu.ops import pallas_knn_binned as binned
    from elasticsearch_tpu.ops import similarity as sim

    rng = np.random.default_rng(11)
    n, d, nq, k = 16384, 64, 16, 10
    centers = rng.standard_normal((256, d)).astype(np.float32) * 2.0
    vecs = centers[rng.integers(0, 256, n)] \
        + 0.7 * rng.standard_normal((n, d)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    queries = vecs[rng.integers(0, n, nq)] \
        + 0.3 * rng.standard_normal((nq, d)).astype(np.float32)
    corpus = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8",
                                  pad_to=n)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    exact = qn @ vecs.T
    ref = np.argsort(-exact, axis=1)[:, :k]

    def recall(ids):
        ids = np.asarray(ids)
        return sum(len(set(ids[i].tolist()) & set(ref[i].tolist()))
                   for i in range(nq)) / (nq * k)

    q = jnp.asarray(queries)
    _, i0 = binned.binned_knn_search(q, corpus, k, interpret=True)
    base = recall(i0)
    s, ids = binned.binned_knn_search_rescored_packed(
        q, corpus, k, rescore_candidates=64, interpret=True)
    ids = np.asarray(ids)
    assert ids.shape == (nq, k)
    assert (ids >= 0).all() and (ids < n).all()
    # rescoring may only help
    assert recall(ids) >= base - 1e-9
    # scores descend
    s = np.asarray(s)
    assert (np.diff(s, axis=1) <= 1e-5).all()


def test_int8_residual_reconstruction():
    """The optional second int8 level reconstructs rows to ~1e-4 relative
    error (vs ~1/254 for bare int8), and costs exactly one extra int8
    matrix (bf16 storage parity) that the main scan never reads."""
    from elasticsearch_tpu.ops import knn as knn_ops
    from elasticsearch_tpu.ops import similarity as sim

    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((256, 32)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    c = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8")
    assert c.residual is not None and c.residual.dtype == jnp.int8
    recon = (np.asarray(c.matrix, dtype=np.float32)
             * np.asarray(c.scales)[:, None]
             + np.asarray(c.residual, dtype=np.float32)
             * np.asarray(c.residual_scales)[:, None])
    err = np.abs(recon[:256] - vecs).max()
    bare = np.abs(np.asarray(c.matrix[:256], dtype=np.float32)
                  * np.asarray(c.scales[:256])[:, None] - vecs).max()
    assert err < 1e-4
    assert err < bare / 50
    c2 = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8",
                              residual=False)
    assert c2.residual is None


def test_auto_router_uses_residual_rescore(monkeypatch):
    """A corpus carrying the residual level routes knn_search_auto through
    the packed rescore on TPU backends (the production effect of
    index_options.rescore: true)."""
    from elasticsearch_tpu.ops import knn as knn_ops
    from elasticsearch_tpu.ops import pallas_knn_binned as binned
    from elasticsearch_tpu.ops import similarity as sim

    rng = np.random.default_rng(5)
    n = binned.BLOCK_N
    vecs = rng.standard_normal((n, 32)).astype(np.float32)
    c_res = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8",
                                 pad_to=n)
    c_plain = knn_ops.build_corpus(vecs, metric=sim.COSINE, dtype="int8",
                                   pad_to=n, residual=False)
    calls = []
    monkeypatch.setattr(
        binned, "binned_knn_search_rescored_packed",
        lambda *a, **k: calls.append("rescored") or (None, None))
    monkeypatch.setattr(
        binned, "binned_knn_search",
        lambda *a, **k: calls.append("base") or (None, None))

    class FakeDev:
        platform = "tpu"
    monkeypatch.setattr(knn_ops.jax, "devices", lambda: [FakeDev()])
    q = jnp.asarray(rng.standard_normal((2, 32)).astype(np.float32))
    knn_ops.knn_search_auto(q, c_res, k=5, metric=sim.COSINE)
    knn_ops.knn_search_auto(q, c_plain, k=5, metric=sim.COSINE)
    assert calls == ["rescored", "base"]
