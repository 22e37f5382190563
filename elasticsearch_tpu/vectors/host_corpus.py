"""CPU-resident int8 mirror of a vector field for latency serving.

A TPU dispatch costs a fixed host↔device round trip (measured once per
process, `serving/batcher.device_overhead_ms`); where one VNNI pass over a
small corpus on the host CPU beats that overhead, the serving layer
(serving/batcher.py `CostModel`) routes the search here, and keeps the
device path for throughput batches and large corpora. Every search served
here is counted (`_nodes/stats indices.knn.host_mirror_searches`); ROADMAP
D4 decides whether the path stays. The reference has no such split —
Lucene scores every vector per-doc in Java (`ScoreScriptUtils.java:86-171`);
this mirror is the host-side analog of the device `Corpus`, sharing its
metric conventions (ops/similarity.py raw scores) so results are
path-independent.

Quality: rows are symmetric int8 (per-row scales); a bf16-rounded copy
re-scores an oversampled candidate set so final top-k ordering matches the
device's bf16 matmul quality rather than raw int8.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from elasticsearch_tpu import native
from elasticsearch_tpu.ops import similarity as sim

# over-retrieve factor for the int8 pass feeding the bf16 rescore
OVERSAMPLE = 3
MIN_CANDIDATES = 32


def packed_nbytes(n: int, dims: int) -> int:
    """Host memory the mirror will take (packed u8 + bf16 rescore copy)."""
    d4 = (dims + 3) // 4
    ng = (n + 15) // 16
    return ng * 16 * d4 * 4 + 2 * n * dims


class HostFieldCorpus:
    """Packed int8 corpus + bf16 rescore copy for one vector field."""

    __slots__ = ("packed", "n", "dims", "d4", "ng", "row_scales",
                 "metric", "sq_norms", "rescore_bf16")

    def __init__(self, vectors: np.ndarray, metric: str):
        vectors = np.asarray(vectors, dtype=np.float32)
        n, dims = vectors.shape
        if metric == sim.COSINE:
            norms = np.linalg.norm(vectors, axis=-1, keepdims=True)
            vectors = vectors / np.maximum(norms, 1e-30)
        self.n = n
        self.dims = dims
        self.metric = metric
        self.d4 = (dims + 3) // 4
        self.ng = (n + 15) // 16
        self.sq_norms = (vectors * vectors).sum(axis=-1).astype(np.float32)

        # the codec registry's one int8 recipe (max-abs/127 scale,
        # 1e-30 floor — an all-zero row round-trips to zeros either way)
        from elasticsearch_tpu.quant import codec as quant_codec
        enc = quant_codec.get("int8").encode_np(vectors)
        q, scales = enc.data, enc.scales
        # u8 with +128 offset: the corpus sits in vpdpbusd's unsigned operand
        rows_u8 = (q.astype(np.int16) + 128).astype(np.uint8)
        padded = np.full((self.ng * 16, self.d4 * 4), 128, dtype=np.uint8)
        padded[:n, :dims] = rows_u8
        self.packed = np.ascontiguousarray(
            padded.reshape(self.ng, 16, self.d4, 4).transpose(0, 2, 1, 3))
        self.row_scales = np.zeros(self.ng * 16, dtype=np.float32)
        self.row_scales[:n] = scales.astype(np.float32)
        # bf16-rounded copy for candidate rescore (2 bytes/element, matching
        # packed_nbytes' budget; candidate rows are widened to f32 at use)
        import ml_dtypes
        self.rescore_bf16 = vectors.astype(ml_dtypes.bfloat16)

    def nbytes(self) -> int:
        return self.packed.nbytes + self.rescore_bf16.nbytes

    def _prep(self, queries: np.ndarray) -> np.ndarray:
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        if self.metric == sim.COSINE:
            qn = np.linalg.norm(queries, axis=-1, keepdims=True)
            queries = queries / np.maximum(qn, 1e-30)
        return queries

    def search(self, queries: np.ndarray, k: int,
               mask: Optional[np.ndarray] = None,
               rescore: bool = True) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k host search. queries [B, D]; mask None / [n] / [B, n] bool.

        Returns (raw_scores [B, k], rows [B, k]) in ops/similarity.py raw
        conventions, -inf / -1 padding — the same contract as the device
        `knn_search`, so callers can't tell which path served them.
        """
        queries = self._prep(queries)
        b = queries.shape[0]
        k_eff = min(k, self.n)
        if k_eff == 0:
            return (np.full((b, k), -np.inf, dtype=np.float32),
                    np.full((b, k), -1, dtype=np.int32))
        m = k_eff if not rescore else min(
            self.n, max(OVERSAMPLE * k_eff, MIN_CANDIDATES))

        if self.metric == sim.L2_NORM:
            dot_mul, bias = 2.0, np.zeros(self.ng * 16, dtype=np.float32)
            bias[:self.n] = -self.sq_norms
        else:
            dot_mul, bias = 1.0, None

        kmask = None
        if mask is not None:
            mask = np.asarray(mask)
            if mask.ndim == 1:
                kmask = np.zeros(self.ng * 16, dtype=np.uint8)
                kmask[:self.n] = mask
            else:
                kmask = np.zeros((b, self.ng * 16), dtype=np.uint8)
                kmask[:, :self.n] = mask

        scores, rows = native.knn_i8p_topk(
            queries, self.packed, self.n, self.d4, self.row_scales,
            bias, dot_mul, kmask, m)

        if self.metric == sim.L2_NORM:
            # kernel returns 2·dot − ‖c‖²; raw convention subtracts ‖q‖² too
            q_sq = (queries * queries).sum(axis=-1, keepdims=True)
            scores = np.where(rows >= 0, scores - q_sq, scores)

        if not rescore:
            if scores.shape[1] < k:  # k > n: pad to the documented [B, k]
                pad = k - scores.shape[1]
                scores = np.pad(scores, ((0, 0), (0, pad)),
                                constant_values=-np.inf)
                rows = np.pad(rows, ((0, 0), (0, pad)), constant_values=-1)
            return scores[:, :k], rows[:, :k]

        # bf16 rescore of the oversampled candidates: removes the int8
        # quantization error from the final ordering (device-path quality)
        out_s = np.full((b, k), -np.inf, dtype=np.float32)
        out_r = np.full((b, k), -1, dtype=np.int32)
        for qi in range(b):
            cand = rows[qi][rows[qi] >= 0]
            if len(cand) == 0:
                continue
            sub = self.rescore_bf16[cand].astype(np.float32)
            dots = sub @ queries[qi]
            if self.metric == sim.L2_NORM:
                raw = 2.0 * dots - (queries[qi] * queries[qi]).sum() \
                    - self.sq_norms[cand]
            else:
                raw = dots
            kk = min(k, len(cand))
            sel = native.topk(raw.astype(np.float32), kk)
            out_s[qi, :kk] = raw[sel]
            out_r[qi, :kk] = cand[sel]
        return out_s, out_r
