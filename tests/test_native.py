"""C++ hot-loop kernels vs numpy reference implementations."""

import numpy as np
import pytest

from elasticsearch_tpu import native


@pytest.fixture(scope="module", autouse=True)
def built():
    native.require()  # raises with the compiler's output
    assert native.AVAILABLE


def test_concurrent_first_use_builds_once_and_all_load(tmp_path):
    """A fresh checkout has no `native/*.so`; every process that starts
    together builds on first import. Four importers against an empty
    build directory must all load the library (the old in-place `make`
    let a second process CDLL a half-written file and latch the failure
    for its lifetime)."""
    import os
    import shutil
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a scratch tree shaped like the checkout: the loader finds native/
    # relative to its own file, so no option of the program is needed
    pkg = tmp_path / "elasticsearch_tpu" / "native"
    pkg.mkdir(parents=True)
    (tmp_path / "elasticsearch_tpu" / "__init__.py").write_text("")
    shutil.copy(os.path.join(repo, "elasticsearch_tpu", "native",
                             "__init__.py"), pkg / "__init__.py")
    build = tmp_path / "native"
    build.mkdir()
    for name in ("Makefile", "es_native.cc"):
        shutil.copy(os.path.join(repo, "native", name), build / name)
    code = ("import elasticsearch_tpu.native as n; "
            "assert n.AVAILABLE, n._load_error; "
            "assert n.__file__.startswith(%r); "
            "print(n.topk(__import__('numpy').arange(5.0), 2).tolist())"
            % str(tmp_path))
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              cwd=str(tmp_path), stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(4)]
    outs = [p.communicate(timeout=170) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err
        assert out.strip() == "[4, 3]"
    left = sorted(os.listdir(build))
    assert "libes_native.so" in left
    assert not [f for f in left if f.endswith(".tmp")], left


def test_require_raises_with_build_output(monkeypatch):
    """A caller with no fallback gets the build error, not a bare None."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error",
                        "make libes_native.so failed (rc 2):\ng++: boom")
    with pytest.raises(RuntimeError, match="g\\+\\+: boom"):
        native.require()
    # ... while a wrapper with a numpy fallback goes on without it
    freqs, lengths = np.array([1, 3], np.int32), np.array([50., 80.], np.float32)
    np.testing.assert_allclose(
        native.bm25_score(freqs, lengths, 1.7, 80.0, 1.2, 0.75, 2.0),
        ref_bm25(freqs, lengths, 1.7, 80.0, 1.2, 0.75, 2.0), rtol=1e-5)


def ref_bm25(freqs, lengths, idf, avg_len, k1, b, boost):
    f = freqs.astype(np.float64)
    tf = f / (f + k1 * (1.0 - b + b * lengths.astype(np.float64) / avg_len))
    return boost * idf * (k1 + 1.0) * tf


def test_bm25_matches_reference_formula():
    rng = np.random.default_rng(7)
    freqs = rng.integers(1, 50, 1000).astype(np.int32)
    lengths = rng.integers(1, 500, 1000).astype(np.float32)
    got = native.bm25_score(freqs, lengths, idf=2.37, avg_len=120.5,
                            k1=1.2, b=0.75, boost=1.3)
    want = ref_bm25(freqs, lengths, 2.37, 120.5, 1.2, 0.75, 1.3)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_intersect_matches_numpy():
    rng = np.random.default_rng(11)
    for na, nb in [(0, 10), (10, 0), (1, 1), (100, 10000), (5000, 5000)]:
        a = np.unique(rng.integers(0, 20000, na)).astype(np.int64)
        b = np.unique(rng.integers(0, 20000, nb)).astype(np.int64)
        ia, ib = native.intersect_sorted(a, b)
        _, ria, rib = np.intersect1d(a, b, assume_unique=True,
                                     return_indices=True)
        np.testing.assert_array_equal(ia, ria)
        np.testing.assert_array_equal(ib, rib)
        if len(ia):
            np.testing.assert_array_equal(a[ia], b[ib])


def test_union_sum_matches_reference():
    rng = np.random.default_rng(13)
    a = np.unique(rng.integers(0, 500, 200)).astype(np.int64)
    b = np.unique(rng.integers(0, 500, 300)).astype(np.int64)
    sa = rng.random(len(a)).astype(np.float32)
    sb = rng.random(len(b)).astype(np.float32)
    rows, scores = native.union_sum(a, sa, b, sb)
    want_rows = np.union1d(a, b)
    want = np.zeros(len(want_rows), dtype=np.float64)
    want[np.searchsorted(want_rows, a)] += sa
    want[np.searchsorted(want_rows, b)] += sb
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_allclose(scores, want, rtol=1e-6)


def test_union_sum_null_scores():
    a = np.array([1, 3, 5], dtype=np.int64)
    b = np.array([3, 4], dtype=np.int64)
    rows, scores = native.union_sum(a, None, b,
                                    np.array([2.0, 7.0], dtype=np.float32))
    np.testing.assert_array_equal(rows, [1, 3, 4, 5])
    np.testing.assert_allclose(scores, [0.0, 2.0, 7.0, 0.0])


def test_topk_order_and_tiebreak():
    scores = np.array([1.0, 5.0, 5.0, 0.5, 9.0, 5.0], dtype=np.float32)
    idx = native.topk(scores, 4)
    # score desc, index asc on ties: 9.0@4, then the 5.0s at 1, 2, 5
    np.testing.assert_array_equal(idx, [4, 1, 2, 3 + 2])


def test_fallbacks_match_native(monkeypatch):
    """A host without g++ must produce byte-identical results."""
    rng = np.random.default_rng(23)
    scores = rng.integers(0, 50, 2000).astype(np.float32)  # many ties
    a = np.unique(rng.integers(0, 5000, 800)).astype(np.int64)
    b = np.unique(rng.integers(0, 5000, 1200)).astype(np.int64)
    sa = rng.random(len(a)).astype(np.float32)
    sb = rng.random(len(b)).astype(np.float32)

    n_topk = native.topk(scores, 25)
    n_int = native.intersect_sorted(a, b)
    n_union = native.union_sum(a, sa, b, sb)
    n_bm25 = native.bm25_score(np.arange(1, 100, dtype=np.int32),
                               np.full(99, 50.0, np.float32),
                               1.7, 80.0, 1.2, 0.75, 2.0)

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load", lambda: None)

    np.testing.assert_array_equal(native.topk(scores, 25), n_topk)
    for got, want in zip(native.intersect_sorted(a, b), n_int):
        np.testing.assert_array_equal(got, want)
    rows, ssum = native.union_sum(a, sa, b, sb)
    np.testing.assert_array_equal(rows, n_union[0])
    np.testing.assert_allclose(ssum, n_union[1], rtol=1e-6)
    np.testing.assert_allclose(
        native.bm25_score(np.arange(1, 100, dtype=np.int32),
                          np.full(99, 50.0, np.float32),
                          1.7, 80.0, 1.2, 0.75, 2.0),
        n_bm25, rtol=1e-5)


def test_topk_k_exceeds_n_and_randomized():
    rng = np.random.default_rng(17)
    scores = rng.random(1000).astype(np.float32)
    for k in [0, 1, 10, 999, 1000, 5000]:
        idx = native.topk(scores, k)
        kk = min(k, len(scores))
        assert len(idx) == kk
        want = np.argsort(-scores, kind="stable")[:kk]
        np.testing.assert_array_equal(idx, want)
