"""The plain reference of an int8 deployment, in numpy alone: nothing here
imports `elasticsearch_tpu` or JAX.

`benchmark/data.py` `Rows.cosines` scores as a bf16 deployment states it.
A configuration whose `device_dtype` is int8 states another arithmetic:

    the unit-normalised row held as int8 with ONE scale a row
        (scale = max|x| / 127, levels = round-half-even(x / scale),
         clipped to +-127: plain abs-max, which is also what the program's
         `quant/codec.py` int8 codec does; no departure found)
    the unit query in bfloat16
    their products summed in float32, the sum then times the row's scale

`Int8Rows` wraps a `Rows` and gives `verify.compare_answers` that cosine;
the exact float32 top-k (`recall_at_k`'s side) stays `Rows.topk`.

The control is the nearest precision below, in the program's place: the
rows held in int4 with one scale a row (scale = max|x| / 7, levels +-7).
It scores, in int4, the rows that the STATED precision ranks first (the
int8 scan's own top k), so it differs from a correct program in its
scores alone and has to fail `score_rms_err` and nothing else. An int4
scan that also CHOSE the rows would lose a tenth and more of the top 10 on
rows made as `data.Corpus` makes them (PERF.md, section 2), which is why
the program's own `int4_flat` re-ranks a window in float32; that reading is
printed beside the control's as `int4_scan_recall_at_k`, and judged by
nothing.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmark.data import bf16_round

QUERY_BLOCK = 16


def quantise(x: np.ndarray, levels: int):
    """Symmetric integer levels with one scale a row: (levels [n, d]
    float32 holding whole numbers, scales [n] float32)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    scale = np.maximum(np.abs(x).max(axis=1), 1e-30) / np.float32(levels)
    q = np.clip(np.rint(x / scale[:, None]), -levels, levels)
    return q.astype(np.float32), scale.astype(np.float32)


def unit_queries(queries: np.ndarray) -> np.ndarray:
    """The unit query in bfloat16, as float32."""
    q = queries.astype(np.float32)
    return bf16_round(
        q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-30))


class Int8Rows:
    """A `data.Rows` whose `cosines` are those of rows held in `levels`
    (127: int8, the stated precision; 7: int4, the control's)."""

    def __init__(self, rows, levels: int = 127):
        self.rows = rows
        self.levels = levels
        self.fields = rows.fields

    def positions(self, doc_ids: List[int]) -> List[int]:
        return self.rows.positions(doc_ids)

    def topk(self, queries, k, tags=None, filter_field=None, unit=None):
        return self.rows.topk(queries, k, tags, filter_field, unit=unit)

    def cosines(self, queries: np.ndarray, ids: List[List[int]]) -> list:
        """The cosine of each served row against its query as the
        configuration states it (module docstring)."""
        qn = unit_queries(queries)
        out = []
        for i, row in enumerate(ids):
            if not len(row):
                out.append(np.zeros(0, np.float32))
                continue
            q, scale = quantise(
                self.rows.unit[np.asarray(row, dtype=np.int64)], self.levels)
            out.append((q @ qn[i]).astype(np.float32) * scale)
        return out

    def scan(self, queries: np.ndarray, k: int):
        """The whole scan in this precision: (ids, cosines) of its own
        top k, ties to the lower row."""
        q_rows, scale = quantise(self.rows.unit, self.levels)
        qn = unit_queries(queries)
        ids = np.empty((len(qn), k), dtype=np.int64)
        cos = np.empty((len(qn), k), dtype=np.float32)
        for lo in range(0, len(qn), QUERY_BLOCK):
            s = (qn[lo:lo + QUERY_BLOCK] @ q_rows.T) * scale[None, :]
            part = np.argpartition(-s, k, axis=1)[:, :k]
            part.sort(axis=1)
            part_s = np.take_along_axis(s, part, axis=1)
            order = np.argsort(-part_s, axis=1, kind="stable")
            ids[lo:lo + QUERY_BLOCK] = np.take_along_axis(part, order, axis=1)
            cos[lo:lo + QUERY_BLOCK] = np.take_along_axis(part_s, order,
                                                          axis=1)
        return ids, cos


def to_scores(cos) -> list:
    """`_score` of a cosine, as the program's REST surface gives it."""
    return ((1.0 + np.asarray(cos, dtype=np.float64)) / 2.0).tolist()


def control_answers(rows, queries: np.ndarray, k: int):
    """(answers, int4_scan_recall_at_k): the rows the stated int8 scan
    ranks first, each scored in int4; and, for the record, the share of
    the exact float32 top k that an int4 scan's own choice would hold."""
    ids, _ = Int8Rows(rows, 127).scan(queries, k)
    int4 = Int8Rows(rows, 7)
    cos = int4.cosines(queries, [row.tolist() for row in ids])
    answers = [(row.tolist(), to_scores(c)) for row, c in zip(ids, cos)]
    scan_ids, _ = int4.scan(queries, k)
    want, _ = rows.topk(queries, k)
    held = sum(len(set(g.tolist()) & set(w.tolist()))
               for g, w in zip(scan_ids, want))
    return answers, held / float(want.size)
