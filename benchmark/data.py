"""Rows and queries made from the seed, and the plain references.

Copied from `chip_smoke.py` `Data` (sound there; the yardstick keeps its own
copy so later PRs may change the smoke script, not this) and generalised to
what a configuration file states: dims, clustered centres, and the extra
fields of the mapping. Nothing here imports `elasticsearch_tpu` or JAX.

Block b of the corpus is a function of (seed, b) alone. Vectors are rounded
to 4 decimals BEFORE they are sent, so the float32 the server parses from the
JSON text is bit for bit the float32 the reference scores.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

QUERY_CHUNK = 1024


def zipf_weights(count: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1, dtype=np.float64) ** s
    return w / w.sum()


class Corpus:
    """The rows of one run: vectors and the mapping's other fields."""

    def __init__(self, seed: int, config: dict):
        self.seed = int(seed)
        self.dims = int(config["dims"])
        data = config["data"]
        self.block_docs = int(data["block_docs"])
        self.row_noise = float(data["row_noise"])
        self.query_noise = float(data["query_noise"])
        self.vector_field = data["vector_field"]
        self.fields = data["fields"]
        rng = np.random.default_rng([self.seed, 0])
        self.centres = rng.standard_normal(
            (int(data["centres"]), self.dims)).astype(np.float32)
        self._blocks: Dict[int, dict] = {}

    def block(self, b: int) -> dict:
        got = self._blocks.get(b)
        if got is not None:
            return got
        rng = np.random.default_rng([self.seed, 1, b])
        n = self.block_docs
        vecs = (self.centres[rng.integers(0, len(self.centres), size=n)]
                + self.row_noise * rng.standard_normal((n, self.dims),
                                                       dtype=np.float32))
        got = {"vectors": np.round(vecs.astype(np.float64), 4)}
        for f in self.fields:
            if f["type"] == "keyword":
                got[f["name"]] = rng.choice(
                    f["values"], size=n,
                    p=zipf_weights(f["values"], float(f["zipf_s"])))
            elif f["type"] == "date":
                got[f["name"]] = f["base_ms"] + rng.integers(
                    0, f["days"] * 86_400_000, size=n)
            elif f["type"] != "long":
                raise ValueError(f"no generator for a {f['type']} field")
        self._blocks[b] = got
        return got

    def bulk_body(self, b: int, index: str,
                  docs: Optional[int] = None) -> bytes:
        """One `_bulk` body: the first `docs` rows of block b, each under
        its place in the corpus as its id."""
        blk = self.block(b)
        n = self.block_docs if docs is None else docs
        lo = b * self.block_docs
        cols = []
        for f in self.fields:
            if f["type"] == "keyword":
                cols.append([f'"{f["name"]}":"{f["prefix"]}{v}"'
                             for v in blk[f["name"]][:n]])
            elif f["type"] == "date":
                cols.append([f'"{f["name"]}":{v}'
                             for v in blk[f["name"]][:n]])
            else:
                cols.append([f'"{f["name"]}":{lo + j}' for j in range(n)])
        lines = []
        for j, vec in enumerate(blk["vectors"][:n].tolist()):
            lines.append('{"index":{"_index":"%s","_id":"%d"}}'
                         % (index, lo + j))
            rest = "".join("," + c[j] for c in cols)
            lines.append('{"%s":%s%s}' % (
                self.vector_field,
                json.dumps(vec, separators=(",", ":")), rest))
        return ("\n".join(lines) + "\n").encode()

    def rows(self, blocks: Sequence[Tuple[int, int]]) -> "Rows":
        """The flat arrays of what was sent: `blocks` is (block, docs)
        pairs in id order."""
        parts = [(self.block(b), n) for b, n in blocks]
        vectors = np.concatenate(
            [p["vectors"][:n] for p, n in parts]).astype(np.float32)
        fields = {f["name"]: np.concatenate([p[f["name"]][:n]
                                             for p, n in parts])
                  for f in self.fields if f["type"] != "long"}
        self._blocks.clear()
        return Rows(self, vectors, fields)


class Rows:
    """What the index holds, flat; the source of queries and the reference."""

    def __init__(self, corpus: Corpus, vectors: np.ndarray, fields: dict):
        self.corpus = corpus
        self.vectors = vectors
        self.fields = fields
        norms = np.maximum(np.linalg.norm(vectors, axis=1, keepdims=True),
                           1e-30)
        self.unit = vectors / norms

    def __len__(self) -> int:
        return len(self.vectors)

    def positions(self, doc_ids: List[int]) -> List[int]:
        """Row positions of served document ids (a document's id is its
        row); -1 for an id that no row has."""
        return [i if 0 <= i < len(self.vectors) else -1 for i in doc_ids]

    def queries(self, first: int, count: int,
                filter_field: Optional[str] = None):
        """Queries first..first+count of the run's one stream: a row plus
        noise, rounded as the rows are; with `filter_field`, a value of that
        field drawn with the rows' own frequencies (another row's value).
        Query i is a function of (seed, i) and the rows alone."""
        vecs, tags = [], []
        for c in range(first // QUERY_CHUNK,
                       (first + count - 1) // QUERY_CHUNK + 1):
            rng = np.random.default_rng([self.corpus.seed, 2, c])
            anchors = rng.integers(0, len(self.vectors), size=QUERY_CHUNK)
            noise = rng.standard_normal((QUERY_CHUNK, self.corpus.dims),
                                        dtype=np.float32)
            donors = rng.integers(0, len(self.vectors), size=QUERY_CHUNK)
            lo = max(first, c * QUERY_CHUNK) - c * QUERY_CHUNK
            hi = min(first + count, (c + 1) * QUERY_CHUNK) - c * QUERY_CHUNK
            vecs.append(self.vectors[anchors[lo:hi]]
                        + self.corpus.query_noise * noise[lo:hi])
            if filter_field:
                tags.append(self.fields[filter_field][donors[lo:hi]])
        q = np.round(np.concatenate(vecs).astype(np.float64), 4)
        return q, (np.concatenate(tags) if filter_field else None)

    # -- the plain reference -------------------------------------------------
    def _scores(self, unit: np.ndarray, queries: np.ndarray) -> np.ndarray:
        q = queries.astype(np.float32)
        qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-30)
        return qn @ unit.T

    def topk(self, queries: np.ndarray, k: int,
             tags: Optional[np.ndarray] = None,
             filter_field: Optional[str] = None,
             unit: Optional[np.ndarray] = None):
        """Exact cosine top-k in float32, in blocks of 16 queries: ids and
        cosines. `unit` swaps in another copy of the normalised rows (the
        control's, in lower precision)."""
        unit = self.unit if unit is None else unit
        ids = np.empty((len(queries), k), dtype=np.int64)
        cos = np.empty((len(queries), k), dtype=np.float32)
        field = self.fields[filter_field] if filter_field else None
        for lo in range(0, len(queries), 16):
            s = self._scores(unit, queries[lo:lo + 16])
            if field is not None:
                s[field[None, :] != tags[lo:lo + 16, None]] = -np.inf
            part = np.argpartition(-s, k, axis=1)[:, :k]
            part_s = np.take_along_axis(s, part, axis=1)
            order = np.argsort(-part_s, axis=1, kind="stable")
            ids[lo:lo + 16] = np.take_along_axis(part, order, axis=1)
            cos[lo:lo + 16] = np.take_along_axis(part_s, order, axis=1)
        return ids, cos

    def cosines(self, queries: np.ndarray, ids: List[List[int]]) -> list:
        """The cosine of each served row against its query, computed as the
        configuration states it: the unit rows and the unit query each
        rounded to bfloat16, their products summed in float32."""
        q = queries.astype(np.float32)
        qn = bf16_round(
            q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-30))
        return [bf16_round(self.unit[np.asarray(row, dtype=np.int64)]) @ qn[i]
                if len(row) else np.zeros(0, np.float32)
                for i, row in enumerate(ids)]

    def int8_unit(self) -> np.ndarray:
        """The control's rows: the normalised rows held as int8 with one
        scale a row, dequantised."""
        return int8_round(self.unit)


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 to bfloat16 (round to nearest even) and back."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & 1)) & np.uint32(
        0xFFFF0000)
    return bits.view(np.float32)


def int8_round(x: np.ndarray) -> np.ndarray:
    """Symmetric int8 with one scale a row, and back to float32."""
    x = x.astype(np.float32)
    scale = np.maximum(np.abs(x).max(axis=1, keepdims=True), 1e-30) / 127.0
    return (np.clip(np.rint(x / scale), -127, 127)
            .astype(np.int8).astype(np.float32) * scale)
