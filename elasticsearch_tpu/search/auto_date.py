"""`auto_date_histogram` as Elasticsearch 7.x answers it: the one
statement that the host walker (`search/aggregations.py`), its partial
reduce (`search/agg_partials.py`) and the device engine
(`search/agg_plan.py`) follow.

Roundings, from the finest; each has its inner intervals and the unit
its `interval` prints:

    index  rounding  inner intervals           unit
    0      second    1, 5, 10, 30              s
    1      minute    1, 5, 10, 30              m
    2      hour      1, 3, 12                  h
    3      day       1, 7                      d
    4      month     1, 3                      M
    5      year      1, 5, 10, 20, 50, 100     y

A second, a minute, an hour and a day are fixed lengths in UTC; a month
and a year are the calendar's.

The choice. `n_i` is the number of rounding i's buckets from the
rounding of the matched rows' least key to that of their greatest, the
empty ones between them counted. The rounding is the first whose `n_i`
is at most `buckets` times its largest inner interval; the year where
none is.

The merge. Where `n_i` passes `buckets`, the least inner interval `m`
with ceil(n_i / m) <= `buckets` is taken, and the rounding's buckets
merge m at a time, consecutive, from the first key, each group under
the key of its first bucket. Past 100 x `buckets` years no interval
fits and nothing merges (m = 1).

The answer. Every merged bucket from the first to the last, an empty
one with `doc_count` 0, and `interval` "<m><unit>" (a 21-day range of
rows under 20 buckets: 21 days, merged by 7, three buckets and "7d").
No matched row: no bucket and "1s". Keys and roundings are in UTC: no
`time_zone` is modelled (the device engine takes none but UTC).

A distributed reduce: each shard answers at the rounding its own rows
choose, unmerged, with no empty bucket. The reduce takes the coarsest
rounding of the shards, rounds every bucket's key to it and merges equal
keys; then it chooses (from that rounding up) and merges as above. A
rounding's choice only grows with the span, and the roundings nest, so
that is what the union of the rows chooses.
"""

from __future__ import annotations

import re
from typing import List, Tuple

import numpy as np

# (unit of numpy's datetime64 or a fixed length, inner intervals, unit)
ROUNDINGS = (("s", (1, 5, 10, 30), "s"), ("m", (1, 5, 10, 30), "m"),
             ("h", (1, 3, 12), "h"), ("D", (1, 7), "d"),
             ("M", (1, 3), "M"), ("Y", (1, 5, 10, 20, 50, 100), "y"))
FIXED_MS = (1000, 60_000, 3_600_000, 86_400_000, None, None)

# the key `_partial_spec` sets on a shard's spec: answer at the shard's
# own rounding, unmerged, with no empty bucket
SHARD_KEY = "_shard_rounding"

_INTERVAL = re.compile(r"^(\d+)([smhdMy])$")


def unit(i: int) -> str:
    return ROUNDINGS[i][2]


def interval(i: int, m: int) -> str:
    """What the answer's `interval` prints: "7d"."""
    return f"{m}{unit(i)}"


def parse_interval(text) -> Tuple[int, int]:
    """(rounding index, inner interval) of an `interval` string."""
    got = _INTERVAL.match(str(text))
    if got is None:
        return 0, 1
    return [r[2] for r in ROUNDINGS].index(got.group(2)), int(got.group(1))


def round_down(millis, i: int) -> np.ndarray:
    """Each key (epoch millis, a number or an array) rounded down to
    rounding i, as int64 millis."""
    v = np.asarray(millis, dtype=np.float64)
    fixed = FIXED_MS[i]
    if fixed is not None:
        return (np.floor(v / fixed) * fixed).astype(np.int64)
    t = np.floor(v).astype(np.int64).astype("datetime64[ms]")
    return t.astype(f"datetime64[{ROUNDINGS[i][0]}]").astype(
        "datetime64[ms]").astype(np.int64)


def keys_between(first: int, last: int, i: int) -> np.ndarray:
    """Every key of rounding i from the rounded key `first` to the
    rounded key `last`, both in, as int64 millis."""
    fixed = FIXED_MS[i]
    if fixed is not None:
        return np.arange(int(first), int(last) + 1, fixed, dtype=np.int64)
    u = ROUNDINGS[i][0]
    lo = np.int64(first).astype("datetime64[ms]").astype(f"datetime64[{u}]")
    hi = np.int64(last).astype("datetime64[ms]").astype(f"datetime64[{u}]")
    return np.arange(lo, hi + 1).astype("datetime64[ms]").astype(np.int64)


def count_between(first: int, last: int, i: int) -> int:
    fixed = FIXED_MS[i]
    if fixed is not None:
        return (int(last) - int(first)) // fixed + 1
    u = ROUNDINGS[i][0]
    lo, hi = (np.int64(x).astype("datetime64[ms]").astype(f"datetime64[{u}]")
              for x in (first, last))
    return int((hi - lo).astype(np.int64)) + 1


def choose(least, greatest, buckets: int, start: int = 0
           ) -> Tuple[int, int, int]:
    """(rounding index, inner interval, buckets at that rounding before
    the merge) for matched keys from `least` to `greatest`, trying the
    roundings from `start` on."""
    target = max(1, int(buckets))
    i = start
    for i in range(start, len(ROUNDINGS)):
        n = count_between(round_down(least, i), round_down(greatest, i), i)
        if n <= target * ROUNDINGS[i][1][-1]:
            break
    m = 1
    if n > target:
        m = next((mm for mm in ROUNDINGS[i][1] if -(-n // mm) <= target), 1)
    return i, m, n


def groups(n: int, m: int) -> List[Tuple[int, int]]:
    """[start, end) of each merged bucket over n consecutive ones."""
    return [(g, min(g + m, n)) for g in range(0, n, m)]
