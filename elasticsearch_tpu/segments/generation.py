# tpulint: hot-path
"""One device generation: an immutable sealed slice of the corpus.

The device analog of a sealed Lucene segment: a `Corpus` pytree padded to
the pow-2 row-bucket ladder (`ops/dispatch.bucket_gen_rows`) plus the host
bookkeeping a generation carries through its life — the engine-row map,
a `columnar.RowSource` resolving the raw host rows through the SHARED
segment block store (the merge scheduler's input — generations pin no
private corpus-sized copy), and the tombstone mask deletes flip instead
of triggering a rebuild.

Generations are copy-on-write: tombstoning returns a NEW object sharing
the device corpus, so a search dispatched against a previously-installed
generation set keeps reading valid arrays (same contract as
`ShardedFieldState.append`).

The per-generation search dispatches `segments.knn` — the exact-kNN
implementation under a grid predicate that additionally pins the row
count to the sealed-generation ladder, so the `segments.*` compile set
stays closed under `ES_TPU_DISPATCH_STRICT=1`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.ops import knn as knn_ops
from elasticsearch_tpu.vectors import filter_mask


def generation_tier(n_rows: int) -> int:
    """Size tier of a generation (the Lucene TieredMergePolicy band):
    tier t holds generations whose row bucket is GEN_ROW_BUCKET_MIN << t.
    Rows past the bucket cap all land in the top band."""
    bucket = dispatch.bucket_gen_rows(max(int(n_rows), 1))
    return max(0, (bucket // dispatch.GEN_ROW_BUCKET_MIN).bit_length() - 1)


def _grid_segments_knn(statics, sigs) -> bool:
    """Closed sealed-generation grid: bucketed query count, k on the
    ladder (or clamped to the generation), rows on the pow-2 generation
    ladder."""
    q_shape = sigs[0][0]          # queries [Q, D]
    n_rows = sigs[1][0][0]        # corpus.matrix [N_bucket, D]
    return (dispatch.is_query_bucket(q_shape[0])
            and dispatch.in_k_grid(int(statics["k"]), limit=n_rows)
            and dispatch.in_gen_row_grid(n_rows))


# same implementation as knn.exact — a generation IS an exact corpus —
# but its own kernel name + grid: the monolithic kernel admits any
# lane-padded row count, while sealed generations must sit on the pow-2
# bucket ladder or the per-refresh seal stream would compile per shape
dispatch.DISPATCH.register(
    "segments.knn", knn_ops._knn_search_impl,
    static_argnames=("k", "metric", "precision", "block_size"),
    grid_check=_grid_segments_knn)


class Generation:
    """Immutable device generation + host bookkeeping."""

    __slots__ = ("gen_id", "corpus", "row_map", "locator", "source",
                 "tombstones", "kernel", "router", "mesh_state",
                 "_live_cache")

    def __init__(self, gen_id: int, corpus, row_map: np.ndarray,
                 source, tombstones: Optional[np.ndarray] = None,
                 kernel: str = "segments.knn", router=None,
                 mesh_state=None, locator=None):
        self.gen_id = gen_id
        self.corpus = corpus              # knn_ops.Corpus (device pytree)
        self.row_map = row_map            # [n_rows] engine global rows
        # engine global row -> row of this generation, for a filter's
        # mask (`vectors/filter_mask.py`): built here, at the seal or the
        # merge, and shared by the tombstone copies
        self.locator = (locator if locator is not None
                        else filter_mask.RowLocator(row_map))
        # columnar.RowSource: the merge scheduler's host-row input,
        # resolved through the SHARED segment block store on demand — a
        # generation never retains a private corpus-sized f32 copy
        # (the pre-columnar `host_vectors` pin doubled host RAM)
        self.source = source
        self.tombstones = (np.zeros(len(row_map), dtype=bool)
                           if tombstones is None else tombstones)
        # dispatch kernel: "knn.exact" for the legacy lane-padded full
        # build (reuses the store's warmed monolithic grid), "segments.knn"
        # for bucket-padded sealed/merged generations
        self.kernel = kernel
        self.router = router              # ann.IVFRouter (graduated base)
        self.mesh_state = mesh_state      # parallel ShardedFieldState
        self._live_cache = None

    # ------------------------------------------------------------ shape
    @property
    def n_rows(self) -> int:
        return len(self.row_map)

    @property
    def n_pad(self) -> int:
        return self.corpus.matrix.shape[0]

    @property
    def tier(self) -> int:
        return generation_tier(self.n_rows)

    @property
    def host_vectors(self) -> np.ndarray:
        """Materialize this generation's raw f32 rows from the shared
        block store (transient — callers must not hold the result; the
        compat shape of the retired pinned array)."""
        return self.source.gather()

    def host_pinned_nbytes(self) -> int:
        """Host bytes this generation PINS privately beyond the shared
        segment blocks — 0 on every store-backed path (the
        merge-does-not-pin invariant)."""
        return self.source.private_nbytes()

    @property
    def dead_rows(self) -> int:
        return int(self.tombstones.sum())

    @property
    def live_rows(self) -> int:
        return self.n_rows - self.dead_rows

    @property
    def has_tombstones(self) -> bool:
        return bool(self.tombstones.any())

    @property
    def nbytes(self) -> int:
        """Resident device bytes (matrix + norms + scales + residual);
        none while the copy is deferred to its first use."""
        if not knn_ops.is_resident(self.corpus):
            return 0
        total = 0
        for arr in (self.corpus.matrix, self.corpus.sq_norms,
                    self.corpus.scales, self.corpus.residual,
                    self.corpus.residual_scales):
            if arr is not None:
                total += int(np.prod(arr.shape)) * arr.dtype.itemsize
        return total

    # ----------------------------------------------------------- copies
    def with_tombstones(self, tombstones: np.ndarray) -> "Generation":
        """Copy-on-write tombstone install: shares the device corpus and
        the row source, drops the graduated router (its partition layout
        would keep returning dead rows — the merge scheduler rebuilds it
        at compaction); the mesh state stays (searches mask it)."""
        return Generation(self.gen_id, self.corpus, self.row_map,
                          self.source, tombstones=tombstones,
                          kernel=self.kernel, router=None,
                          mesh_state=self.mesh_state, locator=self.locator)

    def live_mask(self) -> np.ndarray:
        """[n_rows] bool — True for live (non-tombstoned) rows."""
        if self._live_cache is None:
            self._live_cache = ~self.tombstones
        return self._live_cache

    # ----------------------------------------------------------- warmup
    def warmup_entries(self, dims: int, metric: str):
        """(kernel, specs, statics) entries pre-compiling this
        generation's search grid over the interactive buckets."""
        corpus_spec = dispatch.specs_like(self.corpus)
        entries = []
        for q in dispatch.WARMUP_QUERY_BUCKETS:
            qspec = dispatch.query_spec(q, dims)
            for k in dispatch.WARMUP_K_BUCKETS:
                k_b = dispatch.bucket_k(min(k, self.n_pad),
                                        limit=self.n_pad)
                entries.append((
                    self.kernel, (qspec, corpus_spec, None),
                    {"k": k_b, "metric": metric,
                     "precision": "bf16", "block_size": None}))
        return entries


def build_generation(gen_id: int, vectors: np.ndarray, row_map: np.ndarray,
                     metric: str, dtype: str, rescore: bool = False,
                     source=None) -> Generation:
    """Seal host rows into a device generation padded to the pow-2
    row-bucket ladder — the refresh path's ONLY device work, O(delta).

    `source` is the columnar RowSource covering exactly these rows (the
    store-backed, pin-free merge input). When omitted (direct test
    construction), the materialized `vectors` array is wrapped as a
    private source — which pins it, so production callers always pass
    the store-backed source."""
    vectors = np.asarray(vectors, dtype=np.float32)
    n = len(vectors)
    corpus = knn_ops.build_corpus(
        vectors, metric=metric, dtype=dtype,
        pad_to=dispatch.bucket_gen_rows(n), residual=rescore)
    if source is None:
        from elasticsearch_tpu.columnar import RowSource
        source = RowSource.from_array(vectors)
    return Generation(gen_id, corpus, np.asarray(row_map, dtype=np.int64),
                      source, kernel="segments.knn")
