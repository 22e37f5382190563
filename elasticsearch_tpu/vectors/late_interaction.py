"""Late-interaction (`rank_vectors`) field store: coarse-then-MaxSim.

Serving shape per field, mirroring the two-phase rescore the single-
vector packed rungs already run (`vectors/store.py`):

* build (lazy, per reader snapshot — `ops/bm25.LexicalShard`'s sync
  discipline): per-segment token blocks come codec-encoded from the
  columnar store (`columnar.STORE.token_block`, cached per (segment,
  field, encoding, metric, dims), so refresh re-encodes only delta
  segments), then assemble into ONE device tile [N_pad, cap, W] plus
  per-token scales [N_pad, cap] — cap is the pow-2 max tokens/doc,
  N_pad is `_pow2(n+1)` so at least one all-zero PADDING ROW always
  exists (invalid coarse candidates clamp onto it and score NEG_INF).
  The pooled per-doc centroids build a standard coarse corpus
  (`ops/knn.build_corpus`) at the mapping's coarse rung.

* search: pooled query centroids retrieve a top-(k·oversample)
  candidate window through the existing exact single-vector path
  (`knn.exact` — bucketed, warmed, strict-mode-clean), then ONE
  `maxsim.rescore` dispatch (`ops/pallas_maxsim.py`) rescores the
  whole batch's windows against the resident token tile. Ordering ties
  break by ascending global row, the engine-wide convention.

The exact oracle this path is recall-gated against is the pure-host
walker (`search/queries_ext.LateInteractionQuery`): raw f32 stored
tokens, no coarse pruning — recall@k measures what the centroid prune
plus the storage rung's quantization cost together.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops import dispatch, knn
from elasticsearch_tpu.ops.bm25 import _pow2
from elasticsearch_tpu.quant import tokens as quant_tokens

# widest device-eligible query, in tokens: ColBERT-style encoders emit
# 32-64; past this the [Q, Tq, D] query block's pad cost lands on every
# query sharing the batch, so wider bodies walk the host oracle (the
# plan layer counts the fallback)
MAX_QUERY_TOKENS = 128
_TQ_MIN = 8


class LateInteractionField:
    """One `rank_vectors` field's token tile + coarse corpus over a
    reader snapshot. Host numpy arrays are the source of truth; device
    mirrors upload lazily on first dispatch."""

    def __init__(self, field: str, dims: int, metric: str = "cosine",
                 encoding: str = "int8", coarse: str = "f32",
                 oversample: int = 4):
        self.field = field
        self.dims = int(dims)
        self.metric = metric
        self.encoding = encoding
        self.coarse_dtype = coarse
        self.oversample = int(oversample)
        self.version: tuple = ()
        self.n_docs = 0                 # docs bearing >= 1 token
        self.cap = 1                    # pow-2 max tokens/doc
        self.n_pad = 1                  # pow-2 tile rows (> n_docs)
        self.row_map = np.zeros(0, dtype=np.int64)
        self.tokens_total = 0
        self.tile = None                # [N_pad, cap, W] host
        self.tile_scales = None         # [N_pad, cap] f32 host
        self.coarse_corpus = None       # ops.knn.Corpus over pooled rows
        self.columnar_refresh: dict = {}
        self._device = None
        self._device_version: tuple = ()

    # ------------------------------------------------------------- build
    def sync(self, reader) -> bool:
        """(Re)assemble the token tile + coarse corpus; True if rebuilt.
        Per-segment encode work is cached in the columnar store keyed by
        (encoding, metric, dims), so a cap change (one long new doc)
        only re-assembles the tile, never re-encodes old segments."""
        from elasticsearch_tpu import columnar
        version = tuple((v.segment.seg_id, v.segment.num_docs,
                         int(v.live.sum())) for v in reader.views)
        if version == self.version:
            return False
        variant_blocks = []
        n_cached = n_extracted = 0
        for view in reader.views:
            blk, was_cached = columnar.STORE.token_block(
                view, self.field, self.encoding, self.metric, self.dims)
            if was_cached:
                n_cached += 1
            else:
                n_extracted += 1
            if blk is not None and blk.n_rows:
                variant_blocks.append(blk)
        mode = columnar.STORE.note_composition(
            self.field, "tokens", n_cached, n_extracted)
        self.columnar_refresh = {
            "blocks": n_cached + n_extracted, "cached": n_cached,
            "extracted": n_extracted, "mode": mode}

        n = sum(b.n_rows for b in variant_blocks)
        max_tokens = max((int(b.counts.max()) for b in variant_blocks
                          if len(b.counts)), default=1)
        w = quant_tokens.packed_width(self.encoding, self.dims)
        self.n_docs = n
        self.cap = _pow2(max(max_tokens, 1))
        self.n_pad = _pow2(n + 1)
        dtype = (variant_blocks[0].data.dtype if variant_blocks
                 else np.uint8)
        tile = np.zeros((self.n_pad, self.cap, w), dtype=dtype)
        scales = np.zeros((self.n_pad, self.cap), dtype=np.float32)
        pooled = np.zeros((max(n, 1), self.dims), dtype=np.float32)
        row_parts = []
        doc = 0
        total_tokens = 0
        for b in variant_blocks:
            row_parts.append(b.rows)
            pooled[doc:doc + b.n_rows] = b.pooled
            tok = 0
            for i in range(b.n_rows):
                c = int(b.counts[i])
                tile[doc + i, :c] = b.data[tok:tok + c]
                scales[doc + i, :c] = b.scales[tok:tok + c]
                tok += c
            total_tokens += tok
            doc += b.n_rows
        self.tokens_total = total_tokens
        self.row_map = (np.concatenate(row_parts) if row_parts
                        else np.zeros(0, dtype=np.int64))
        self.tile = tile
        self.tile_scales = scales
        self.coarse_corpus = (knn.build_corpus(
            pooled[:n], metric=self.metric, dtype=self.coarse_dtype,
            residual=False) if n else None)
        self.version = version
        return True

    def nbytes(self) -> int:
        if self.tile is None:
            return 0
        return int(self.tile.nbytes + self.tile_scales.nbytes)

    def _device_arrays(self):
        if self._device is not None and self._device_version == self.version:
            return self._device
        # scales ride as [n_pad, 1, cap]: the kernel's one-row block
        # needs the singleton axis resident (ops/pallas_maxsim.py)
        self._device = (jnp.asarray(self.tile),
                        jnp.asarray(self.tile_scales[:, None, :]))
        self._device_version = self.version
        return self._device

    # ------------------------------------------------------------ search
    def coarse_window(self, k: int) -> int:
        """Bucketed candidate-window width for the fused rescore: the
        oversampled k, clamped to the coarse corpus then rounded up the
        k ladder (a clamp lands on the LANE-padded corpus row count,
        which the maxsim grid also admits)."""
        rows = int(self.coarse_corpus.matrix.shape[0])
        win = min(max(k * self.oversample, k), max(self.n_docs, 1))
        return dispatch.bucket_k(win, limit=rows)

    def plan_queries(self, queries: Sequence[Tuple[np.ndarray, float]]
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(q_tokens [Qp, Tq_pad, d_pad] f32, centroids [Qp, dims] f32,
        boosts [n_real]) — tokens metric-prepped through the SAME
        `quant/tokens.py` prep the stored blocks ran, zero-padded to a
        pow-2 token count and the tile's lane width; the query batch
        pads to its dispatch bucket with all-zero queries."""
        n_real = len(queries)
        n_bucket = dispatch.bucket_queries(max(n_real, 1))
        tq = 1
        prepped = []
        boosts = np.ones(n_real, dtype=np.float32)
        for i, (tokens, boost) in enumerate(queries):
            t = quant_tokens.prep_tokens(
                np.asarray(tokens, dtype=np.float32).reshape(-1, self.dims),
                self.metric)
            prepped.append(t)
            boosts[i] = np.float32(boost)
            tq = max(tq, len(t))
        tq_pad = _pow2(max(tq, _TQ_MIN))
        d_pad = quant_tokens.pad_dim(self.dims)
        q = np.zeros((n_bucket, tq_pad, d_pad), dtype=np.float32)
        cent = np.zeros((n_bucket, self.dims), dtype=np.float32)
        for i, t in enumerate(prepped):
            q[i, :len(t), :self.dims] = t
            cent[i] = quant_tokens.pool_doc(t, self.metric)
        return q, cent, boosts

    def search_batch(self, queries: Sequence[Tuple[np.ndarray, float]],
                     k: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Two-phase batch: coarse centroid top-W through `knn.exact`,
        fused `maxsim.rescore` over the window, per-query top-k with
        (-score, ascending row) ties. Returns [(global rows, f32
        scores)] per query."""
        empty = (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32))
        if self.n_docs == 0:
            return [empty for _ in queries]
        q, cent, boosts = self.plan_queries(queries)
        wc = self.coarse_window(k)
        _scores_c, ids_c = knn.knn_search(
            jnp.asarray(cent), self.coarse_corpus, k=wc,
            metric=self.metric)
        ids_np = np.asarray(ids_c)
        # invalid coarse slots (padding rows of the coarse corpus, or
        # windows wider than the live doc count) clamp onto the token
        # tile's reserved all-zero padding row -> NEG_INF in the board
        invalid = (ids_np < 0) | (ids_np >= self.n_docs)
        ids_np = np.where(invalid, self.n_docs, ids_np).astype(np.int32)
        toks_d, scales_d = self._device_arrays()
        from elasticsearch_tpu.ops import pallas_maxsim
        board = np.asarray(pallas_maxsim.maxsim_rescore(
            jnp.asarray(ids_np), jnp.asarray(q), toks_d, scales_d))
        out = []
        for qi in range(len(queries)):
            s = board[qi]
            keep = ~invalid[qi] & (s > -np.inf) & np.isfinite(s)
            cand = ids_np[qi][keep]
            sv = s[keep]
            rows = self.row_map[cand]
            order = np.lexsort((rows, -sv))[:k]
            out.append((rows[order],
                        (sv[order] * boosts[qi]).astype(np.float32)))
        return out


class LateInteractionShard:
    """Per-reader late-interaction store: one LateInteractionField per
    `rank_vectors` field, lazily synced on first hybrid use."""

    def __init__(self):
        self._fields: Dict[str, LateInteractionField] = {}
        self._lock = threading.Lock()
        self.stats = {"searches": 0, "queries": 0, "rebuilds": 0,
                      "score_nanos": 0}

    def field(self, reader, mapper) -> LateInteractionField:
        """mapper: the field's RankVectorsFieldMapper (geometry +
        encoding come from the mapping, not the caller)."""
        with self._lock:
            lf = self._fields.get(mapper.name)
            if lf is None:
                lf = LateInteractionField(
                    mapper.name, mapper.dims, metric=mapper.similarity,
                    encoding=mapper.encoding, coarse=mapper.coarse,
                    oversample=mapper.oversample)
                self._fields[mapper.name] = lf
            if lf.sync(reader):
                self.stats["rebuilds"] += 1
            return lf

    def search_batch(self, reader, mapper, queries, k: int):
        lf = self.field(reader, mapper)
        t0 = time.perf_counter_ns()
        out = lf.search_batch(queries, k)
        self.stats["searches"] += 1
        self.stats["queries"] += len(queries)
        self.stats["score_nanos"] += time.perf_counter_ns() - t0
        return out

    def field_stats(self) -> Dict[str, dict]:
        with self._lock:
            return {name: {
                "docs": lf.n_docs, "tokens": lf.tokens_total,
                "cap": lf.cap, "encoding": lf.encoding,
                "tile_bytes": lf.nbytes(),
                "columnar_refresh": dict(lf.columnar_refresh),
            } for name, lf in self._fields.items()}

    def warmup_entries(self, reader, mapper, k: int = 10):
        """Shape-only `maxsim.rescore` warmup entries for this field's
        CURRENT tile geometry (call after a sync; a later cap/N change
        warms again on its first dispatch)."""
        import jax

        from elasticsearch_tpu.ops import pallas_maxsim
        lf = self.field(reader, mapper)
        if lf.n_docs == 0:
            return []
        w = quant_tokens.packed_width(lf.encoding, lf.dims)
        tok_dtype = jnp.uint8 if lf.encoding == "int4" else \
            jnp.asarray(lf.tile[:1, :1]).dtype
        return pallas_maxsim.warmup_entries(
            lf.n_pad, lf.cap, w, tok_dtype,
            tq_rungs=(_TQ_MIN, 32), w_buckets=(lf.coarse_window(k),),
            query_buckets=(1, 8))
