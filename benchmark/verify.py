"""The comparison that decides `correct`: the answers the timed path gave,
against the plain float32 reference over the same rows.

Numbers compared for a set of answers (each with a limit of its own, in the
configuration's file under `limits`; the readings they were set from are in
PERF.md, section 2):

    recall_at_k        share of the reference's top-k ids that were served
                       (the configuration states the floor)
    score_rms_err      root mean square, over every served hit, of
                       `_score` - (1 + cos(query, that row)) / 2, the cosine
                       computed as the configuration states it (rows and
                       query in bfloat16, summed in float32): what is left is
                       the order of the sum. The control (the reference
                       computed in int8) has to fail it
    filter_violations  served hits whose row does not satisfy the filter
    unanswered         requests that failed, never answered or were malformed
    host_mirror_searches   searches the program answered from its host
                       mirror instead of the device (delta over the window)
"""

from __future__ import annotations

import json
from typing import List, Optional, Sequence

import numpy as np

from benchmark.data import int8_round


def parse_hits(raw: bytes, status: int):
    """(ids, scores) of one `_search` answer; None where it is no sound
    answer (HTTP error, failed shard, not JSON)."""
    if status != 200:
        return None
    try:
        resp = json.loads(raw)
        if resp["_shards"].get("failed"):
            return None
        hits = resp["hits"]["hits"]
        return ([int(h["_id"]) for h in hits],
                [float(h["_score"]) for h in hits])
    except (ValueError, KeyError, TypeError):
        return None


def pick_sample(n: int, size: int, seed: int,
                always: Sequence[int] = ()) -> List[int]:
    """`size` of n positions drawn from the seed, `always` among them."""
    if n <= size:
        return list(range(n))
    rng = np.random.default_rng([int(seed), 4])
    chosen = set(int(i) for i in always)
    for i in rng.permutation(n):
        if len(chosen) >= size:
            break
        chosen.add(int(i))
    return sorted(chosen)


def compare_answers(rows, queries: np.ndarray, answers: list, k: int,
                    tags: Optional[np.ndarray] = None,
                    filter_field: Optional[str] = None) -> dict:
    """`answers[i]` is (ids, scores) for `queries[i]`, or None."""
    want_ids, _ = rows.topk(queries, k, tags, filter_field)
    # served document ids -> row positions; an id no row has is a violation
    served = [rows.positions(a[0]) if a else [] for a in answers]
    known = [[p for p in pos if p >= 0] for pos in served]
    inter = sum(len(set(g) & set(w.tolist()))
                for g, w in zip(known, want_ids))
    cos = rows.cosines(queries, known)
    sq, n_hits, violations = 0.0, 0, 0
    for i, a in enumerate(answers):
        if not a:
            continue
        keep = [j for j, p in enumerate(served[i]) if p >= 0]
        violations += len(served[i]) - len(keep)
        if filter_field is not None and keep:
            field = rows.fields[filter_field][np.asarray(known[i])]
            violations += int((field != tags[i]).sum())
        err = (np.asarray(a[1], dtype=np.float64)[keep]
               - (1.0 + cos[i].astype(np.float64)) / 2.0)
        sq += float((err * err).sum())
        n_hits += len(keep)
    return {"recall_at_k": inter / float(want_ids.size),
            "score_rms_err": (sq / n_hits) ** 0.5 if n_hits else float("inf"),
            "filter_violations": violations,
            "unanswered": sum(1 for a in answers if not a)}


def control_answers(rows, queries: np.ndarray, k: int,
                    tags: Optional[np.ndarray] = None,
                    filter_field: Optional[str] = None) -> list:
    """The control: the reference in the program's place, computed in int8
    (the nearest precision below the configuration's bf16): rows and
    queries both rounded to int8 with a scale each, as an s8 x s8 kernel
    would hold them."""
    ids, cos = rows.topk(int8_round(queries), k, tags, filter_field,
                         unit=rows.int8_unit())
    return [(i.tolist(), ((1.0 + c.astype(np.float64)) / 2.0).tolist())
            for i, c in zip(ids, cos)]


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit. `limits[name]` is {"limit", "must"}
    with must one of `>=`, `<=`, `==`."""
    out = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        lim = limits[name]
        must, limit = lim["must"], lim["limit"]
        ok = {">=": value >= limit, "<=": value <= limit,
              "==": value == limit}[must]
        out[name] = {"value": value, "limit": limit, "must": must,
                     "ok": bool(ok)}
    return out
