"""The plain reference of `yfcc-192-uint8-tags`, in numpy alone: nothing
here imports `elasticsearch_tpu` or JAX.

The deployment (big-ann-benchmarks NeurIPS'23, filter track, `yfcc-10M`):
rows of 192 uint8 values under squared Euclidean distance, each row with a
BAG of tags; a query is a vector plus one or two tags, and a row answers it
only if its bag holds ALL of them; k nearest of those.

    rows, bags, queries   made from the seed (`TagCorpus`, `TagRows`): block
                          b of the corpus is a function of (seed, b) alone,
                          query i of (seed, i) and the rows
    matching(tags)        the rows whose bag holds every tag, from a plain
                          inverted list (sorted rows a tag), intersected
    topk(q, tags, k)      among them the k of least d2 = sum((q - row)^2),
                          every term an int64, ties to the lower row; fewer
                          than k rows match -> fewer than k answers, none ->
                          none
    score(d2)             1 / (1 + d2): `_score` of `l2_norm` as
                          `elasticsearch_tpu/ops/similarity.py` states it

What is stated is EXACT: a uint8 value is exact in bfloat16, a product of
two is exact in float32 and a sum of 192 of them stays under 2^24, so a
program that holds the rows in bf16 and sums in float32 computes the very
integer; what is left in `_score` is one float32 division.

The control is the nearest precision below, in the program's place: the
rows held as int8 with ONE scale a row (`data.int8_round`: 0..255 onto
127 steps loses the low bit). It scores, in int8, the rows the exact scan
ranks first, so it differs from a correct program in its distances alone.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from benchmark.data import QUERY_CHUNK, Corpus, int8_round, zipf_weights

_INT = [str(i) for i in range(256)]
SLACK = 4096.0      # `TagRows._near`: d2 this far past the k-th is measured


class TagCorpus(Corpus):
    """`data.Corpus` with uint8 vectors and a bag of tags a row.

    `data` in the configuration's file adds to `data.Corpus`'s keys:
    `value_offset`, `value_scale` (a float row x becomes
    clip(rint(offset + scale * x), 0, 255)) and `tags`: `field`,
    `vocabulary`, `zipf_s`, `poisson_mean`, `prefix`."""

    def __init__(self, seed: int, config: dict):
        super().__init__(seed, config)
        data = config["data"]
        self.offset = float(data["value_offset"])
        self.scale = float(data["value_scale"])
        tags = data["tags"]
        self.tag_field = tags["field"]
        self.tag_prefix = tags["prefix"]
        self.vocabulary = int(tags["vocabulary"])
        self.poisson_mean = float(tags["poisson_mean"])
        self._cdf = np.cumsum(zipf_weights(self.vocabulary,
                                           float(tags["zipf_s"])))

    def to_uint8(self, x: np.ndarray) -> np.ndarray:
        return np.clip(np.rint(self.offset + self.scale * x), 0,
                       255).astype(np.uint8)

    def _draw_tags(self, rng, count: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self._cdf, rng.random(count),
                                          side="right"),
                          self.vocabulary - 1).astype(np.int32)

    def _bags(self, rng, n: int):
        """1 + Poisson(mean) DISTINCT tags a row, each drawn with the
        vocabulary's Zipf weights (a tag a row already holds is drawn
        again): (tags sorted within each row, offsets [n + 1])."""
        counts = 1 + rng.poisson(self.poisson_mean, size=n)
        owner = np.repeat(np.arange(n), counts)
        tags = self._draw_tags(rng, len(owner))
        while True:
            order = np.lexsort((tags, owner))
            t = tags[order]
            again = np.zeros(len(t), dtype=bool)
            again[1:] = (owner[1:] == owner[:-1]) & (t[1:] == t[:-1])
            if not again.any():
                break
            tags[order[again]] = self._draw_tags(rng, int(again.sum()))
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        return t, offsets

    def block(self, b: int) -> dict:
        got = self._blocks.get(b)
        if got is not None:
            return got
        rng = np.random.default_rng([self.seed, 1, b])
        n = self.block_docs
        vecs = (self.centres[rng.integers(0, len(self.centres), size=n)]
                + self.row_noise * rng.standard_normal((n, self.dims),
                                                       dtype=np.float32))
        tags, offsets = self._bags(rng, n)
        got = {"vectors": self.to_uint8(vecs), "tags": tags,
               "offsets": offsets}
        self._blocks[b] = got
        return got

    def bulk_body(self, b: int, index: str,
                  docs: Optional[int] = None) -> bytes:
        blk = self.block(b)
        n = self.block_docs if docs is None else docs
        lo = b * self.block_docs
        off = blk["offsets"].tolist()
        names = [f'"{self.tag_prefix}{t}"' for t in blk["tags"].tolist()]
        lines = []
        for j, vec in enumerate(blk["vectors"][:n].tolist()):
            lines.append('{"index":{"_index":"%s","_id":"%d"}}'
                         % (index, lo + j))
            lines.append('{"%s":[%s],"%s":[%s]}' % (
                self.vector_field, ",".join([_INT[v] for v in vec]),
                self.tag_field, ",".join(names[off[j]:off[j + 1]])))
        return ("\n".join(lines) + "\n").encode()

    def rows(self, blocks: Sequence[Tuple[int, int]]) -> "TagRows":
        parts = [(self.block(b), n) for b, n in blocks]
        vectors = np.concatenate([p["vectors"][:n] for p, n in parts])
        tags = np.concatenate([p["tags"][:p["offsets"][n]]
                               for p, n in parts])
        counts = np.concatenate([np.diff(p["offsets"][:n + 1])
                                 for p, n in parts])
        self._blocks.clear()
        return TagRows(self, vectors, tags, counts)


class TagRows:
    """What the index holds, flat: the source of queries and the exact
    filtered reference."""

    def __init__(self, corpus: TagCorpus, vectors: np.ndarray,
                 tags: np.ndarray, counts: np.ndarray):
        self.corpus = corpus
        self.vectors = vectors                      # [n, dims] uint8
        self.tags = tags                            # bags, row after row
        self.offsets = np.zeros(len(vectors) + 1, dtype=np.int64)
        np.cumsum(counts, out=self.offsets[1:])
        self.sq = np.concatenate(
            [(vectors[lo:lo + 65536].astype(np.int64) ** 2).sum(axis=1)
             for lo in range(0, len(vectors), 65536)])
        # the inverted lists: rows by tag, ascending within a tag
        owner = np.repeat(np.arange(len(vectors), dtype=np.int64), counts)
        order = np.argsort(tags, kind="stable")
        self._post_rows = owner[order]
        self._post_at = np.searchsorted(
            tags[order], np.arange(corpus.vocabulary + 1))
        self._f32 = None                # `_near`'s copy, made on first use

    def __len__(self) -> int:
        return len(self.vectors)

    def bag(self, row: int) -> np.ndarray:
        return self.tags[self.offsets[row]:self.offsets[row + 1]]

    def name(self, tag: int) -> str:
        return f"{self.corpus.tag_prefix}{tag}"

    def postings(self, tag: int) -> np.ndarray:
        return self._post_rows[self._post_at[tag]:self._post_at[tag + 1]]

    def matching(self, tags: Sequence[int]) -> np.ndarray:
        """Sorted rows whose bag holds every one of `tags`."""
        rows = self.postings(tags[0])
        for t in tags[1:]:
            rows = np.intersect1d(rows, self.postings(t),
                                  assume_unique=True)
        return rows

    def holds(self, row: int, tags: Sequence[int]) -> bool:
        return bool(np.isin(tags, self.bag(row)).all())

    def queries(self, first: int, count: int):
        """Queries first..first+count of the run's one stream: (vectors
        [count, dims] of whole numbers 0..255, one tuple of one or two
        tags each). Query i is a function of (seed, i) and the rows: a
        row (its anchor) plus noise, rounded and clipped as the rows are;
        its tags are drawn from the anchor's own bag without replacement,
        two for about half of the queries (one where the bag holds one),
        so every conjunction matches at least its anchor."""
        corpus = self.corpus
        vecs, tags = [], []
        for c in range(first // QUERY_CHUNK,
                       (first + count - 1) // QUERY_CHUNK + 1):
            rng = np.random.default_rng([corpus.seed, 2, c])
            anchors = rng.integers(0, len(self.vectors), size=QUERY_CHUNK)
            noise = rng.standard_normal((QUERY_CHUNK, corpus.dims),
                                        dtype=np.float32)
            two = rng.random(QUERY_CHUNK) < 0.5
            pick = rng.random((QUERY_CHUNK, 2))
            lo = max(first, c * QUERY_CHUNK) - c * QUERY_CHUNK
            hi = min(first + count, (c + 1) * QUERY_CHUNK) - c * QUERY_CHUNK
            moved = (self.vectors[anchors[lo:hi]].astype(np.float32)
                     + corpus.scale * corpus.query_noise * noise[lo:hi])
            vecs.append(np.clip(np.rint(moved), 0, 255).astype(np.int64))
            for j in range(lo, hi):
                bag = self.bag(anchors[j])
                a = int(pick[j, 0] * len(bag))
                if two[j] and len(bag) > 1:
                    b = int(pick[j, 1] * (len(bag) - 1))
                    b += b >= a
                    tags.append((int(bag[a]), int(bag[b])))
                else:
                    tags.append((int(bag[a]),))
        return np.concatenate(vecs), tags

    def distances(self, query: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """d2 of `query` to each of `rows`, int64 throughout."""
        q = np.asarray(query, dtype=np.int64)
        out = np.empty(len(rows), dtype=np.int64)
        for lo in range(0, len(rows), 65536):
            part = self.vectors[rows[lo:lo + 65536]].astype(np.int64)
            out[lo:lo + 65536] = ((part - q) ** 2).sum(axis=1)
        return out

    def _near(self, query: np.ndarray, rows: np.ndarray, k: int):
        """Those of `rows` that can be among the k nearest: a float32 copy
        of the rows and one matrix product find the k-th least d2 (whole
        numbers under 2^24 and their sums are exact in float32, and
        `SLACK` is there for a BLAS that would not keep them so); only
        what lies within `SLACK` of it is then measured in int64."""
        if len(rows) <= 4 * k:
            return rows
        if self._f32 is None:
            self._f32 = self.vectors.astype(np.float32)
        q = np.asarray(query, dtype=np.float32)
        # a product over every row costs less than gathering an eighth
        dots = ((self._f32 @ q)[rows] if 8 * len(rows) > len(self._f32)
                else self._f32[rows] @ q)
        rough = self.sq[rows] + float(q @ q) - 2.0 * dots.astype(np.float64)
        kth = np.partition(rough, k - 1)[k - 1]
        return rows[rough <= kth + SLACK]

    def topk(self, query: np.ndarray, tags: Sequence[int], k: int):
        """(rows, d2, matching) of the exact filtered top k: at most k
        rows, least d2 first, ties to the lower row; `matching` is how
        many rows hold every tag."""
        rows = self.matching(tags)
        near = self._near(query, rows, k)
        d2 = self.distances(query, near)
        order = np.lexsort((near, d2))[:k]
        return near[order], d2[order], len(rows)


def score(d2) -> np.ndarray:
    """`_score` of a squared distance under `l2_norm`."""
    return 1.0 / (1.0 + np.asarray(d2, dtype=np.float64))


def control_answers(rows: TagRows, queries: np.ndarray,
                    exact: List[tuple]) -> List[tuple]:
    """The control's (ids, scores): the exact scan's own rows (`exact[i]`
    is `rows.topk` of query i), their distances computed with the rows
    held as int8 with one scale a row."""
    out = []
    for q, (ids, _d2, _n) in zip(queries, exact):
        held = int8_round(rows.vectors[ids].astype(np.float32))
        d2 = ((held.astype(np.float64) - q.astype(np.float64)) ** 2
              ).sum(axis=1)
        out.append((ids.tolist(), score(d2).tolist()))
    return out
