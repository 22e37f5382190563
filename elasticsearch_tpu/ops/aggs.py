"""Device-resident aggregations: columnar field store + segment-reduce kernels.

The analytics half of `_search` (`search/aggregations` is one of the
reference's largest subsystems) served entirely host-side until this
module: `search/aggregations.py` reduced in numpy after a per-doc Python
`get_doc_value` loop, so a terms agg over 100k matching rows cost 100k
interpreter round-trips while the TPU idled. Terms/histogram/range/stats
aggs are segment-reduce shapes — scatter-add over bucket ids — the exact
kernel family `ops/bm25.py` already proves out for impact scoring, so this
module gives doc-value fields the treatment `vectors/store.py` gives
`dense_vector` and `ops/bm25.py` gives text:

* build (at refresh, lazily on first agg use like `LexicalShard`): each
  aggregated field becomes an `AggColumn` — an f64 value column + presence
  mask over the reader's live rows (padded to a pow-2 row bucket so the
  compiled shapes survive refreshes), plus, for terms aggs, a global
  ordinal column (int32 ord per row over the sorted-unique value set),
  plus, where every value is an integer and the span fits, the column's
  32-bit form `k32` ((v - least) / unit as int32, -1 for no value); a
  `scaled_float`'s values are first taken to their stored longs (its
  lattice, `AggColumn.k_scale`).
  Per-segment extractions cache by segment fingerprint, so append-only
  refreshes re-extract only delta segments (copy-on-write rebuild — an
  in-flight search keeps the previous column's arrays). Each array goes
  to the device on its first use: a column that runs the 32-bit programs
  never uploads its f64 pair.

* search: ONE dispatch per (bucket-source, metric) pair computes the fused
  filter→aggregate: the query's matched rows arrive as a boolean mask over
  the row bucket, bucket ids derive in-kernel from the resident key column
  (ordinals for terms, a table of bounds or an affine floor for
  histogram/date_histogram, bound comparisons for range), and the counts /
  sums / mins / maxs of every bucket come back as a board.

* exactness, in one of two arithmetics, chosen from the COLUMNS alone:
  the 32-bit programs (`aggs.n32_counts`, `aggs.n32_metric`: int32 counts,
  an integer sum in limbs of at most 8 bits that no accumulator can
  overflow at the row bucket, ids by integer comparison with an int32
  table the host maps from its own key math; the host widens the boards
  to int64 / f64) where the key and value columns have their `k32`; else
  the x64 programs (`aggs.tree_counts`, `aggs.tree_metric`, `aggs.range_*`,
  traced and executed under the dispatcher's scoped x64 flag: int64
  counts, f64 sums, emulated on a TPU and 12 to 60 times slower there).
  Host parity for sums is guaranteed only for *integral* columns (every
  value integer-valued, sum of |values| < 2^53 — dates, longs, counts),
  where any accumulation order reproduces numpy's pairwise sum
  bit-for-bit; a `scaled_float` on its lattice sums its stored longs
  exactly in the 32-bit program, divided by the factor once on the host
  (the correctly rounded double of the exact decimal; the walker's f64
  sum of the quotients may differ in its last bits); `search/agg_plan.py`
  routes sum-bearing aggs on other columns to the host path.
  min/max/counts are order-insensitive and run on device for any numeric
  column.

* mesh: columns past the `parallel/policy.py` row floor keep a row-sharded
  device copy; the `aggs.mesh_*` twins reduce each shard's row range
  locally inside one shard_map program and merge boards with
  psum/pmin/pmax — exact for the integral-sum contract above, so the
  per-shard device partials merge like every other mesh kernel.

Kernel keys (`ops/dispatch.py`, strict closed grid; 14 of them: counts and
metric in each arithmetic, range counts and metric, the HLL board, and
their mesh twins): rows pad to the pow-2 row bucket fixed at column build;
`n_buckets` rounds up AGG_B_LADDER; warmup pre-compiles the interactive
rungs at column build.
"""

from __future__ import annotations

import logging
import math
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu import telemetry
from elasticsearch_tpu.ops import dispatch
from elasticsearch_tpu.vectors.filter_mask import RowLocator

logger = logging.getLogger("elasticsearch_tpu.aggs")

# bucket-count ladder: terms cardinality / histogram span rounds UP so one
# compiled program serves a band of bucket counts; beyond the last rung the
# plan falls back to the host path (search.max_buckets territory anyway)
AGG_B_LADDER = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192,
                16384, 32768, 65536)

# sums of integer-valued f64 are exact (== numpy's pairwise sum in any
# accumulation order) while |sum| stays under 2^53
_EXACT_INT = float(1 << 53)

# warmup rungs: small terms/histogram dashboards; the persistent cache and
# steady traffic fill the tail
WARMUP_AGG_BUCKETS = (8, 64)

# ladder-top warmup clamp: a single pathological high-cardinality field
# must not AOT-compile the giant rungs at column build — those compile on
# first use (and persist) instead of burning warmup time for every column
WARMUP_MAX_ORD_B = 4096

# HLL register geometry — MUST mirror search/agg_partials.py (_HLL_P /
# _HLL_M) so device register boards pack into host-identical `$p` states
HLL_P = 12
HLL_M = 1 << HLL_P

# composite sub-agg trees: per-level bucket counts ride the same ladder;
# the flat board is the PRODUCT of the levels, so trees cap on total
# lanes (HLL boards are HLL_M registers per lane and cap much lower)
TREE_MAX_DEPTH = 3
TREE_MAX_LANES = 65536
HLL_MAX_LANES = 256

# per-level kernel-arg arity for the composite tree kernels: level args
# flatten in level order, each level contributing (row-shaped..., then
# replicated params...) — see _split_level_args; "ords" and "bounds" are
# the 32-bit programs' levels: (ords) and (k32, lower bounds int32[k + 1])
_LEVEL_ROW = {"ord": 1, "hist": 2, "cal": 2, "ords": 1, "bounds": 1}
_LEVEL_REPL = {"ord": 1, "hist": 1, "cal": 2, "ords": 0, "bounds": 1}


def bucket_count(n: int) -> Optional[int]:
    """Round a bucket count up the AGG_B_LADDER; None = off the grid
    (the caller must fall back to the host path)."""
    n = max(int(n), 1)
    for b in AGG_B_LADDER:
        if b >= n:
            return b
    return None


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def in_b_grid(b: int) -> bool:
    return b in AGG_B_LADDER


# ---------------------------------------------------------------------------
# x64 kernels (traced under scoped x64 — see ops/dispatch.py _Kernel.x64):
# what a column without its 32-bit form runs
# ---------------------------------------------------------------------------


def _metric_boards(tgt, ok, v_eff, n_buckets: int):
    import jax.numpy as jnp
    one = jnp.where(ok, jnp.int64(1), jnp.int64(0))
    cnt = jnp.zeros(n_buckets + 1, dtype=jnp.int64).at[tgt].add(one)
    s = jnp.zeros(n_buckets + 1, dtype=jnp.float64).at[tgt].add(
        jnp.where(ok, v_eff, 0.0))
    mn = jnp.full(n_buckets + 1, jnp.inf, dtype=jnp.float64).at[tgt].min(
        jnp.where(ok, v_eff, jnp.inf))
    mx = jnp.full(n_buckets + 1, -jnp.inf, dtype=jnp.float64).at[tgt].max(
        jnp.where(ok, v_eff, -jnp.inf))
    return cnt, s, mn, mx


def _metric_eff(vals, present, mparams):
    """Apply the metric field's `missing` substitute: mparams f64[2] =
    (flag, value)."""
    import jax.numpy as jnp
    use_missing = mparams[0] > 0.0
    p_eff = present | use_missing
    v_eff = jnp.where(present, vals, mparams[1])
    return v_eff, p_eff


def _hist_ids(keys, kpresent, hparams, n_buckets: int):
    """Bucket ids from the resident key column: hparams f64[6] =
    (interval, offset, base, div, kflag, kmissing). `div` pre-divides
    (date_nanos → millis); `base` rebases floor((v-off)/interval) so ids
    land in [0, B). All f64 — bitwise-identical to the host's numpy key
    math."""
    import jax.numpy as jnp
    interval, offset, base, div = (hparams[0], hparams[1], hparams[2],
                                   hparams[3])
    p_eff = kpresent | (hparams[4] > 0.0)
    v = jnp.where(kpresent, keys / div, hparams[5])
    m = jnp.floor((v - offset) / interval)
    ids = (m - base).astype(jnp.int32)
    ok = p_eff & (ids >= 0) & (ids < n_buckets)
    return jnp.where(ok, ids, n_buckets), ok


def _range_members(keys, kpresent, mask, bounds, rparams):
    """[B, R] membership: bounds f64[B, 2] (lo, hi) with -inf/+inf for
    open ends and (+inf, +inf) pad rows; rparams f64[2] applies the key
    field's `missing` substitute. A row may belong to several overlapping
    ranges — exactly the host semantics."""
    import jax.numpy as jnp
    p_eff = kpresent | (rparams[0] > 0.0)
    v = jnp.where(kpresent, keys, rparams[1])
    ok = mask & p_eff
    return ((v[None, :] >= bounds[:, 0:1]) & (v[None, :] < bounds[:, 1:2])
            & ok[None, :])


def _agg_range_counts(keys, kpresent, mask, bounds, rparams):
    import jax.numpy as jnp
    m = _range_members(keys, kpresent, mask, bounds, rparams)
    return m.astype(jnp.int64).sum(axis=1)


def _agg_range_metric(keys, kpresent, mask, bounds, rparams, mparams, vals,
                      present):
    import jax.numpy as jnp
    m = _range_members(keys, kpresent, mask, bounds, rparams)
    v_eff, p_eff = _metric_eff(vals, present, mparams)
    mm = m & p_eff[None, :]
    cnt = mm.astype(jnp.int64).sum(axis=1)
    s = jnp.where(mm, v_eff[None, :], 0.0).sum(axis=1)
    mn = jnp.where(mm, v_eff[None, :], jnp.inf).min(axis=1)
    mx = jnp.where(mm, v_eff[None, :], -jnp.inf).max(axis=1)
    return cnt, s, mn, mx


# ------------------------------------------------- calendar / tree / HLL ---

def _cal_ids(keys, kpresent, cbounds, cparams, n_buckets: int):
    """Bucket ids for calendar-interval date_histograms from a
    precomputed sorted boundary table: cbounds f64[B] holds the
    `_calendar_floor` outputs over the offset-shifted millis domain
    (+inf pads past the real span), cparams f64[2] = (div, offset).
    One searchsorted pass — no wall-clock arithmetic in traced code.
    Rows first truncate exactly like the host's `int(v - offset)`
    (toward zero, not floor)."""
    import jax.numpy as jnp
    shifted = jnp.trunc(keys / cparams[0] - cparams[1])
    idx = jnp.searchsorted(cbounds, shifted, side="right") - 1
    ids = idx.astype(jnp.int32)
    ok = kpresent & (ids >= 0) & (ids < n_buckets)
    return jnp.where(ok, ids, 0), ok


def _tree_targets(mask, levels, n_buckets, flat_args):
    """Composite bucket ids over a chain of bucket levels: per level the
    id derives like the single-level kernels, the composite folds as
    `cid = cid * k_level + id`. A row is ok only if EVERY level resolves
    (the global trash lane catches the rest). Level arg layout:
    ord → (ords, oparams f64[1]: missing-lane flag; under 0: absent keys
    count in the trash lane), hist → (keys,
    kpresent, hparams), cal → (keys, kpresent, cbounds, cparams).
    Returns (tgt, ok, total) with tgt == total for not-ok rows."""
    import jax.numpy as jnp
    cid = jnp.zeros(mask.shape, dtype=jnp.int32)
    ok = mask
    trashed = jnp.zeros(mask.shape, dtype=bool)
    total = 1
    i = 0
    for kind, k in zip(levels, n_buckets):
        if kind == "ord":
            ords, op = flat_args[i], flat_args[i + 1]
            i += 2
            absent = ords < 0
            # with a `missing` param the level's last lane IS the missing
            # bucket (k was sized for it); otherwise absent rows drop out.
            # A flag under 0 (a terms with no level under it) keeps them,
            # in the board's last lane: that node's `missing` bucket
            ids = jnp.where(absent, jnp.int32(k - 1), ords)
            lok = (~absent) | (op[0] != 0.0)
            trashed = trashed | (absent & (op[0] < 0.0))
        elif kind == "hist":
            keys, kp, hp = flat_args[i], flat_args[i + 1], flat_args[i + 2]
            i += 3
            tgt_l, lok = _hist_ids(keys, kp, hp, k)
            ids = jnp.where(lok, tgt_l, 0).astype(jnp.int32)
        else:  # "cal"
            keys, kp, cb, cp = (flat_args[i], flat_args[i + 1],
                                flat_args[i + 2], flat_args[i + 3])
            i += 4
            ids, lok = _cal_ids(keys, kp, cb, cp, k)
        cid = cid * k + jnp.where(lok, ids, 0)
        ok = ok & lok
        total *= k
    return jnp.where(ok & ~trashed, cid, total), ok, total


def _agg_tree_counts(mask, *level_args, levels, n_buckets):
    """Composite doc counts: int64[prod(n_buckets) + 1]; the last lane is
    the global trash (pad rows + rows failing any level)."""
    import jax.numpy as jnp
    tgt, ok, total = _tree_targets(mask, levels, n_buckets, level_args)
    return jnp.zeros(total + 1, dtype=jnp.int64).at[tgt].add(
        jnp.where(ok, jnp.int64(1), jnp.int64(0)))


def _agg_tree_metric(mask, mparams, vals, present, *level_args, levels,
                     n_buckets):
    """Per-composite-bucket metric boards (count/sum/min/max)."""
    tgt, ok, total = _tree_targets(mask, levels, n_buckets, level_args)
    v_eff, p_eff = _metric_eff(vals, present, mparams)
    return _metric_boards(tgt, ok & p_eff, v_eff, total)


def _agg_hll_board(mask, hidx, hrho, *level_args, levels, n_buckets):
    """Per-composite-bucket HLL register board: int32[total+1, HLL_M],
    max-merged per (bucket, register). hidx/hrho are the precomputed
    per-row register index and rank (rho == 0 marks an absent value, so
    absent rows never raise a register). levels may be empty: the
    top-level cardinality board with every matched row in lane 0."""
    import jax.numpy as jnp
    tgt, ok, total = _tree_targets(mask, levels, n_buckets, level_args)
    rho = jnp.where(ok, hrho, 0)
    board = jnp.zeros((total + 1, HLL_M), dtype=jnp.int32)
    return board.at[tgt, hidx].max(rho)


# ---------------------------------------------------- 32-bit programs ----
#
# What a column of whole numbers that spans under 2^31 units runs instead
# of the x64 programs above (64-bit integers and floats are
# emulated on a TPU: PERF.md section 6, PR 36). The key and the value are
# the column's `k32` (`AggColumn`: (v - vmin) / unit as int32, -1 where
# the row holds no value), a level's bucket ids come from an int32 table
# of lower bounds in that rebased domain (the host's own key math, mapped
# with exact integer arithmetic), counts are int32, and a sum is split
# into limbs narrow enough that no accumulator can overflow at the row
# bucket (`limb_bits`); the host widens the boards to what the assembly
# reads (`search/agg_plan.py` `_widen_*`).
#
# Lanes: a level of k buckets has k + 1 lanes, lane 0 for the rows whose
# key is ABSENT and lane 1 + i for bucket i, so a table that starts with
# -1 derives both; the flat board is the product of the levels' lanes,
# the first level most significant. A row outside the request's mask
# adds 0 wherever it lands. The host folds a level's lane 0 into its
# `missing` bucket or drops it.

I32_MAX = (1 << 31) - 1

# the largest row bucket whose counts an int32 holds with a limb left
N32_MAX_ROWS = 1 << 30

# One-hot product: rows a tile (a tile's f32 sums stay exact under 2^24:
# `_grid_n32` holds tile * (2^limb_bits - 1) to it; 512 to 8,192 rows read
# the same on the chip), and the lanes a column past which ordinals are
# scattered instead. Read in `board_form` alone.
ONEHOT_TILE = 2048
ONEHOT_LANES_A_COLUMN = 8192

# Lanes of a one-hot board up to which its min and max are reduced
# tile by tile inside the product, past which they scatter: for one
# level of bounds, and for ordinals or a chain of levels. Read in
# `extrema_form` alone. The extrema alone on the chip at 2^18 rows
# (`tools/extrema_timing.py`; PERF.md section 6), tiled against
# scattered, ms: one level of bounds 0.69 / 5.6 at 513 lanes, 2.9 / 7.2
# at 2,049, 12.3 / 17.9 at 8,193, 24.0 / 32.1 at 16,385, 142.8 / 116.9 at
# 65,537 (its scatter first counts the bounds for the rows' lanes, which
# grows with them too); ordinals 0.44 / 4.8 at 513, 1.4 / 3.7 at 2,049,
# 2.9 / 3.6 at 4,097, 5.5 / 3.6 at 8,193 (their lane is free, so the
# scatter stays flat); a chain of ordinals 2.2 / 3.7 at 4,096, 4.3 / 3.7
# at 8,192, one of bounds and ordinals 4.6 / 4.0 at 8,192. Each limit is
# the widest width read at which the tiled route won; 2^19 rows read
# twice as long on both routes, so the row bucket drops out.
EXTREMA_TILED_BOUNDS = 16385
EXTREMA_TILED_LANES = 4097
EXTREMA_FORMS = ("reduced", "tiled", "scattered")

N32_KERNELS = frozenset({"aggs.n32_counts", "aggs.n32_metric"})


def limb_bits(r_pad: int) -> int:
    """Bits of one limb of a sum at this row bucket: r_pad rows of
    (2^bits - 1) stay under 2^31 in an int32 accumulator (on the mesh
    the psum is bounded by the whole bucket, so the GLOBAL one is
    passed), and at most 8, what a bf16 operand holds exactly."""
    return max(0, min(8, 31 - (int(r_pad) - 1).bit_length()))


def n_limbs_for(k_max: int, bits: int) -> int:
    """Limbs of `bits` bits that hold every value of 0..k_max."""
    return max(1, -(-max(int(k_max), 1).bit_length() // bits))


def board_form(lanes: int, kind: str, cols: int = 1) -> str:
    """How a 32-bit board is filled, from its shape alone: 'onehot' (a
    product of the widest level's one-hot with the other levels' and the
    columns, tile after tile) or 'scatter' (int32 `.at[].add`, a column
    after the other). `lanes` and `kind` are the widest level's, `cols`
    what a row adds (1 for counts, 1 + the limbs for a sum). As the chip
    read them at 2^19 rows (PERF.md section 6, PR 36; both forms take
    twice as long at 2^20, so the row bucket drops out): the product 3.4
    ms at 2,049 lanes, 4.9 at 8,193, 8.0 at 16,385, 30.4 at 65,537,
    hardly more with four columns than with one; a scatter 5.2 ms a
    column whatever the lanes, ONCE IT HAS THE LANES: ordinals are
    theirs, a table has to be searched, which costs what the product
    costs. So only ordinals scatter, past ONEHOT_LANES_A_COLUMN lanes a
    column."""
    if kind == "ords" and lanes > ONEHOT_LANES_A_COLUMN * cols:
        return "scatter"
    return "onehot"


def extrema_form(levels, lanes, form: str) -> str:
    """How a 32-bit board's min and max are taken, from its shape alone
    (`levels` the kinds, `lanes` each level's lanes): 'reduced' (one
    lane: a masked reduction over every row), 'tiled' (a masked
    reduction over each tile's rows inside the one-hot product, the
    tile's lane one-hot [T, total] beside the product's) or 'scattered'
    (int32 `.at[].min/max` into the rows' lanes). The tile's reduction
    costs rows x lanes. A scatter serialises its updates, so it costs
    the same at one lane as at 513, ONCE IT HAS THE LANES: ordinals are
    theirs, a table has to be searched, which costs rows x bounds too.
    So one level of bounds is reduced up to EXTREMA_TILED_BOUNDS lanes,
    ordinals and chains up to EXTREMA_TILED_LANES."""
    total = math.prod(lanes)
    if total == 1:
        return "reduced"
    top = EXTREMA_TILED_BOUNDS if tuple(levels) == ("bounds",) \
        else EXTREMA_TILED_LANES
    if form == "onehot" and total <= top:
        return "tiled"
    return "scattered"


def _split_n32_levels(levels, level_args):
    """[(kind, row array, table | None)] from the flat per-level args:
    'ords' -> (ords,), 'bounds' -> (k32, lo)."""
    out = []
    i = 0
    for kind in levels:
        n = _LEVEL_ROW[kind] + _LEVEL_REPL[kind]
        out.append((kind,) + tuple(level_args[i:i + n]) + (None,) * (2 - n))
        i += n
    return out


def _n32_lane(kind, rows, table):
    """A level's lane of every row: ord + 1, or the last bound at or
    under the key (lane 0 where the key is -1, absent) by counting the
    bounds at or under it: a bisection's gathers read 45 ms at 2^19 rows
    and 2,049 bounds on the chip, the fused comparisons 2."""
    import jax.numpy as jnp
    if kind == "ords":
        return rows + 1
    return (jnp.searchsorted(table, rows, side="right",
                             method="compare_all") - 1).astype(jnp.int32)


def _n32_columns(mask, mk32, mmiss, bits: int, n_limbs: int):
    """What a row adds: [ok] for counts, [ok, limb 0, ...] for a metric
    (ok = matched and a value, the field's `missing` substitute put in),
    each int32 [R]; and the value for min / max."""
    import jax.numpy as jnp
    if mk32 is None:
        return [mask.astype(jnp.int32)], None, mask
    v = jnp.where(mk32 >= 0, mk32, mmiss)
    ok = mask & (v >= 0)
    cols = [ok.astype(jnp.int32)]
    for j in range(n_limbs):
        limb = (v >> (bits * j)) & ((1 << bits) - 1)
        cols.append(jnp.where(ok, limb, 0))
    return cols, v, ok


def _n32_scatter(lane, total: int, cols):
    import jax.numpy as jnp
    return jnp.stack([jnp.zeros(total, jnp.int32).at[lane].add(c)
                      for c in cols])


def _n32_onehot(lvls, lanes, cols, tile: int, v=None, extrema=()):
    """[len(cols), prod(lanes)] int32 by products: the widest level m is
    the one-hot side A [T, lanes_m] (for a table the THERMOMETER
    key >= lo, whose board is the difference of neighbouring lanes: one
    comparison an element, not two), the other side W [C, T] is the
    one-hot of the other levels' composite lane times the columns. 0/1
    and limbs of at most 8 bits are exact in bf16, a tile's sums exact in
    f32 (under 2^24), tiles add in int32. With `extrema` ('min', 'max')
    and the value `v`, also each lane's min / max of `v` over the rows
    `cols[0]` marks, reduced over a tile's rows and carried from tile to
    tile ('tiled' in `extrema_form`): returns (board, {part: [total]})."""
    import jax
    import jax.numpy as jnp
    m = max(range(len(lanes)), key=lambda j: lanes[j]) if lanes else None
    minor = [j for j in range(len(lanes)) if j != m]
    n_minor = 1
    for j in minor:
        n_minor *= lanes[j]
    n_major = lanes[m] if m is not None else 1
    r = cols[0].shape[0]
    t = min(tile, r)
    n_tiles = r // t
    xs = {"cols": jnp.stack(cols).reshape(len(cols), n_tiles, t)
          .swapaxes(0, 1),
          "rows": [lv[1].reshape(n_tiles, t) for lv in lvls]}
    if extrema:
        xs["v"] = v.reshape(n_tiles, t)

    def body(acc, x):
        acc = dict(acc)
        w = x["cols"].astype(jnp.bfloat16)                    # [c, T]
        if n_minor > 1:
            mlane = jnp.zeros(t, jnp.int32)
            for j in minor:
                kind, _rows, table = lvls[j]
                mlane = mlane * lanes[j] + _n32_lane(
                    kind, x["rows"][j], table)
            oh = (jnp.arange(n_minor, dtype=jnp.int32)[:, None]
                  == mlane[None, :]).astype(jnp.bfloat16)     # [minor, T]
            w = (oh[:, None, :] * w[None, :, :]).reshape(-1, t)
        hot = None
        if m is None:
            a = jnp.ones((t, 1), jnp.bfloat16)
        elif lvls[m][0] == "ords":
            hot = (x["rows"][m] + 1)[:, None] == jnp.arange(
                n_major, dtype=jnp.int32)[None, :]
            a = hot.astype(jnp.bfloat16)
        else:
            hot = x["rows"][m][:, None] >= lvls[m][2][None, :]
            a = hot.astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            w, a, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [C, major]
        acc["g"] = acc["g"] + part.astype(jnp.int32)
        if extrema:
            if n_minor > 1:
                lane = jnp.zeros(t, jnp.int32)
                for (kind, _rows, table), n, rows in zip(lvls, lanes,
                                                          x["rows"]):
                    lane = lane * n + _n32_lane(kind, rows, table)
                hot = lane[:, None] == jnp.arange(
                    n_minor * n_major, dtype=jnp.int32)[None, :]
            elif lvls[m][0] == "bounds":
                # the thermometer's last lane at or under the key
                hot = hot & ~jnp.concatenate(
                    [hot[:, 1:], jnp.zeros((t, 1), bool)], axis=1)
            # the tile's rows with a value, in their lane [T, lanes]
            hot = hot & (x["cols"][0] != 0)[:, None]
            vt = x["v"][:, None]
            if "min" in extrema:
                acc["min"] = jnp.minimum(acc["min"], jnp.min(
                    jnp.where(hot, vt, I32_MAX), axis=0))
            if "max" in extrema:
                acc["max"] = jnp.maximum(acc["max"], jnp.max(
                    jnp.where(hot, vt, -1), axis=0))
        return acc, None

    acc0 = {"g": jnp.zeros((n_minor * len(cols), n_major), jnp.int32)}
    if "min" in extrema:
        acc0["min"] = jnp.full(n_minor * n_major, I32_MAX, jnp.int32)
    if "max" in extrema:
        acc0["max"] = jnp.full(n_minor * n_major, -1, jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, xs)
    g = acc.pop("g")
    if m is not None and lvls[m][0] == "bounds":
        # thermometer: lane b holds the rows AT OR ABOVE bound b
        g = g - jnp.concatenate(
            [g[:, 1:], jnp.zeros((g.shape[0], 1), jnp.int32)], axis=1)
    # [minor, cols, major] -> [cols, levels in order]
    pre = 1
    for j in minor:
        if m is not None and j < m:
            pre *= lanes[j]
    g = g.reshape(pre, n_minor // pre, len(cols), n_major)
    return g.transpose(2, 0, 3, 1).reshape(len(cols), -1), acc


def _n32_boards(mask, mk32, mmiss, level_args, levels, n_buckets, parts,
                bits, n_limbs, form):
    import jax.numpy as jnp
    lvls = _split_n32_levels(levels, level_args)
    lanes = tuple(int(k) + 1 for k in n_buckets)
    total = 1
    for n in lanes:
        total *= n
    want_sum = mk32 is not None and "sum" in parts
    cols, v, ok = _n32_columns(mask, mk32, mmiss, bits,
                               n_limbs if want_sum else 0)
    extrema = [p for p in ("min", "max") if mk32 is not None and p in parts]
    route = extrema_form(levels, lanes, form) if extrema else None
    lane = None
    if form == "scatter" or route == "scattered":
        lane = jnp.zeros(mask.shape, jnp.int32)
        for (kind, rows, table), n in zip(lvls, lanes):
            lane = lane * n + _n32_lane(kind, rows, table)
    tiled = {}
    if form == "scatter":
        board = _n32_scatter(lane, total, cols)
    else:
        board, tiled = _n32_onehot(
            lvls, lanes, cols, ONEHOT_TILE, v,
            tuple(extrema) if route == "tiled" else ())
    if mk32 is None:
        return board[0]
    # an empty lane's min is I32_MAX, its max -1, on every route
    out = [board]
    for p in extrema:
        if p in tiled:
            out.append(tiled[p])
        elif route == "reduced":
            out.append((jnp.min(jnp.where(ok, v, I32_MAX)) if p == "min"
                        else jnp.max(jnp.where(ok, v, -1))).reshape(1))
        elif p == "min":
            out.append(jnp.full(total, I32_MAX, jnp.int32).at[lane].min(
                jnp.where(ok, v, I32_MAX)))
        else:
            out.append(jnp.full(total, -1, jnp.int32).at[lane].max(
                jnp.where(ok, v, -1)))
    return tuple(out)


def _n32_pack(boards):
    """ONE array a program: a read is a round trip to the device."""
    import jax.numpy as jnp
    return jnp.concatenate([boards[0]] + [b[None, :] for b in boards[1:]])


def _agg_n32_counts(mask, *level_args, levels, n_buckets, form):
    """Doc counts, int32 [prod(k + 1)]."""
    return _n32_boards(mask, None, None, level_args, levels, n_buckets,
                       (), 0, 0, form)


def _agg_n32_metric(mask, mk32, mmiss, *level_args, levels, n_buckets,
                    parts, limb_bits, n_limbs, form):
    """A metric field's boards over the same lanes, ONE int32 array
    [1 + n_limbs + extrema, L]: row 0 the count of rows with a value,
    rows 1.. the sum's limbs (where `parts` names 'sum'), then the min,
    then the max (where named), values in the field's rebased domain
    (an empty lane's min is I32_MAX, its max -1). `mmiss` int32 [] is
    the field's `missing` substitute there, -1 for none."""
    return _n32_pack(_n32_boards(
        mask, mk32, mmiss, level_args, levels, n_buckets, parts,
        limb_bits, n_limbs, form))


# ----------------------------------------------------------------- mesh ----

def _mesh_reduce(local_fn, mesh, row_args, repl_args, n_boards,
                 merges=None):
    """Run a board-producing local reduce per shard over row-sharded
    columns and merge boards with psum/pmin/pmax (exact under the
    integral-sum contract). Boards are (cnt int64[, sum f64, min f64,
    max f64]): index 0 and 1 merge by sum, 2 by min, 3 by max — unless
    `merges` names a per-board rule ('sum' | 'min' | 'max') explicitly
    (the HLL register board merges by max)."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.parallel import mesh as mesh_lib
    from elasticsearch_tpu.parallel.sharded_knn import shard_map

    axis = mesh_lib.SHARD_AXIS
    row_spec = jax.sharding.PartitionSpec(axis)
    repl = jax.sharding.PartitionSpec()

    def body(*args):
        boards = local_fn(*args)
        if not isinstance(boards, tuple):
            boards = (boards,)
        merged = []
        for i, b in enumerate(boards):
            rule = merges[i] if merges is not None else (
                "min" if i == 2 else "max" if i == 3 else "sum")
            if rule == "min":
                merged.append(jax.lax.pmin(b, axis))
            elif rule == "max":
                merged.append(jax.lax.pmax(b, axis))
            else:
                merged.append(jax.lax.psum(b, axis))
        return merged[0] if n_boards == 1 else tuple(merged)

    in_specs = tuple([row_spec] * len(row_args) + [repl] * len(repl_args))
    out_specs = repl if n_boards == 1 else tuple([repl] * n_boards)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return fn(*row_args, *repl_args)


# Every row-shaped array (key column, presence, mask, metric columns)
# shards over the row axis; small per-query params/bounds replicate. Each
# shard reduces its own row range into a full [B+1] board, then the boards
# merge in-program (psum for counts/sums, pmin/pmax for extrema).

def _agg_mesh_range_counts(keys, kpresent, mask, bounds, rparams, mesh=None):
    return _mesh_reduce(
        _agg_range_counts, mesh, (keys, kpresent, mask), (bounds, rparams),
        1)


def _agg_mesh_range_metric(keys, kpresent, mask, vals, present, bounds,
                           rparams, mparams, mesh=None):
    return _mesh_reduce(
        lambda k, kp, m, v, p, b, rp, mp: _agg_range_metric(
            k, kp, m, b, rp, mp, v, p),
        mesh, (keys, kpresent, mask, vals, present),
        (bounds, rparams, mparams), 4)


def _split_level_args(levels, level_args):
    """Split the flat per-level args into (row-shaped, replicated) tuples
    for shard_map in_specs, plus a rebuild() that restores the interleaved
    layout `_tree_targets` expects inside the mesh body."""
    rows: list = []
    repls: list = []
    i = 0
    for kind in levels:
        nr, np_ = _LEVEL_ROW[kind], _LEVEL_REPL[kind]
        rows.extend(level_args[i:i + nr])
        repls.extend(level_args[i + nr:i + nr + np_])
        i += nr + np_

    def rebuild(row_args, repl_args):
        out: list = []
        ri = pi = 0
        for kind in levels:
            nr, np_ = _LEVEL_ROW[kind], _LEVEL_REPL[kind]
            out.extend(row_args[ri:ri + nr])
            ri += nr
            out.extend(repl_args[pi:pi + np_])
            pi += np_
        return tuple(out)

    return tuple(rows), tuple(repls), rebuild


def _agg_mesh_tree_counts(mask, *level_args, levels, n_buckets, mesh=None):
    rows, repls, rebuild = _split_level_args(levels, level_args)
    nr = len(rows)

    def local(m, *args):
        la = rebuild(args[:nr], args[nr:])
        return _agg_tree_counts(m, *la, levels=levels, n_buckets=n_buckets)

    return _mesh_reduce(local, mesh, (mask,) + rows, repls, 1)


def _agg_mesh_tree_metric(mask, mparams, vals, present, *level_args,
                          levels, n_buckets, mesh=None):
    rows, repls, rebuild = _split_level_args(levels, level_args)
    nr = len(rows)

    def local(m, v, p, *args):
        la = rebuild(args[:nr], args[nr:-1])
        return _agg_tree_metric(m, args[-1], v, p, *la, levels=levels,
                                n_buckets=n_buckets)

    return _mesh_reduce(local, mesh, (mask, vals, present) + rows,
                        repls + (mparams,), 4)


def _agg_mesh_hll_board(mask, hidx, hrho, *level_args, levels, n_buckets,
                        mesh=None):
    """HLL register boards merge per (bucket, register) by MAX across the
    shard axis — the only board family whose cross-shard merge is not the
    positional default."""
    rows, repls, rebuild = _split_level_args(levels, level_args)
    nr = len(rows)

    def local(m, hi, hr, *args):
        la = rebuild(args[:nr], args[nr:])
        return _agg_hll_board(m, hi, hr, *la, levels=levels,
                              n_buckets=n_buckets)

    return _mesh_reduce(local, mesh, (mask, hidx, hrho) + rows, repls, 1,
                        merges=("max",))


def _agg_mesh_n32_counts(mask, *level_args, levels, n_buckets, form,
                         mesh=None):
    rows, repls, rebuild = _split_level_args(levels, level_args)
    nr = len(rows)

    def local(m, *args):
        return _agg_n32_counts(m, *rebuild(args[:nr], args[nr:]),
                               levels=levels, n_buckets=n_buckets,
                               form=form)

    return _mesh_reduce(local, mesh, (mask,) + rows, repls, 1)


def _agg_mesh_n32_metric(mask, mk32, mmiss, *level_args, levels, n_buckets,
                         parts, limb_bits, n_limbs, form, mesh=None):
    """The shards' int32 boards merge by psum / pmin / pmax: `limb_bits`
    is the GLOBAL row bucket's, so the psum cannot overflow either."""
    rows, repls, rebuild = _split_level_args(levels, level_args)
    nr = len(rows)

    def local(m, v, *args):
        return _n32_boards(
            m, v, args[-1], rebuild(args[:nr], args[nr:-1]), levels,
            n_buckets, parts, limb_bits, n_limbs, form)

    merges = ("sum",) + tuple(p for p in ("min", "max") if p in parts)
    merged = _mesh_reduce(local, mesh, (mask, mk32) + rows,
                          repls + (mmiss,), len(merges), merges=merges)
    return _n32_pack(merged if isinstance(merged, tuple) else (merged,))


# ------------------------------------------------------------ grid checks --

def _row_bucket_ok(r: int) -> bool:
    return r >= 1 and (r & (r - 1)) == 0


def _grid_range(statics, sigs) -> bool:
    r = sigs[0][0][0]
    # bounds [B, 2] rides the 4th positional array arg
    b = None
    for s in sigs:
        if s and s[0] != "py" and len(s[0]) == 2 and s[0][1] == 2:
            b = s[0][0]
            break
    return _row_bucket_ok(int(r)) and (b is None or in_b_grid(int(b)))


def _tree_lanes(statics):
    """(ladder_ok, total lanes) for a tuple-valued n_buckets static."""
    total = 1
    for k in statics["n_buckets"]:
        if not in_b_grid(int(k)):
            return False, 0
        total *= int(k)
    return True, total


def _grid_tree(statics, sigs) -> bool:
    r = sigs[0][0][0]
    nb = tuple(statics["n_buckets"])
    ok, total = _tree_lanes(statics)
    return (_row_bucket_ok(int(r)) and ok
            and 1 <= len(nb) <= TREE_MAX_DEPTH + 1
            and total <= TREE_MAX_LANES)


def _grid_hll(statics, sigs) -> bool:
    r = sigs[0][0][0]
    nb = tuple(statics["n_buckets"])
    ok, total = _tree_lanes(statics)
    return (_row_bucket_ok(int(r)) and ok and len(nb) <= TREE_MAX_DEPTH
            and total <= HLL_MAX_LANES)


def _grid_n32(statics, sigs) -> bool:
    """The 32-bit programs' grid, and the bound that makes their
    accumulators exact: rows of the (global) bucket times the largest
    limb under 2^31, a tile's under 2^24."""
    r = int(sigs[0][0][0])
    nb = tuple(int(k) for k in statics["n_buckets"])
    total = 1
    for k in nb:
        total *= k
    ok = (_row_bucket_ok(r) and r <= N32_MAX_ROWS
          and len(nb) == len(statics["levels"]) <= TREE_MAX_DEPTH + 1
          and all(in_b_grid(k) for k in nb) and total <= TREE_MAX_LANES
          and statics["form"] in ("onehot", "scatter"))
    if ok and "sum" in statics.get("parts", ()):
        top = (1 << int(statics["limb_bits"])) - 1
        ok = (1 <= statics["limb_bits"] <= 8
              and 1 <= statics["n_limbs"] * statics["limb_bits"] <= 38
              and r * top < (1 << 31)
              and min(ONEHOT_TILE, r) * top < (1 << 24))
    return ok


def _register():
    reg = dispatch.DISPATCH.register
    n32 = ("levels", "n_buckets", "form")
    n32m = n32 + ("parts", "limb_bits", "n_limbs")
    reg("aggs.n32_counts", _agg_n32_counts, static_argnames=n32,
        grid_check=_grid_n32)
    reg("aggs.n32_metric", _agg_n32_metric, static_argnames=n32m,
        grid_check=_grid_n32)
    reg("aggs.mesh_n32_counts", _agg_mesh_n32_counts,
        static_argnames=n32 + ("mesh",), grid_check=_grid_n32)
    reg("aggs.mesh_n32_metric", _agg_mesh_n32_metric,
        static_argnames=n32m + ("mesh",), grid_check=_grid_n32)
    reg("aggs.range_counts", _agg_range_counts,
        grid_check=_grid_range, x64=True)
    reg("aggs.range_metric", _agg_range_metric,
        grid_check=_grid_range, x64=True)
    reg("aggs.mesh_range_counts", _agg_mesh_range_counts,
        static_argnames=("mesh",), grid_check=_grid_range, x64=True)
    reg("aggs.mesh_range_metric", _agg_mesh_range_metric,
        static_argnames=("mesh",), grid_check=_grid_range, x64=True)
    reg("aggs.tree_counts", _agg_tree_counts,
        static_argnames=("levels", "n_buckets"), grid_check=_grid_tree,
        x64=True)
    reg("aggs.tree_metric", _agg_tree_metric,
        static_argnames=("levels", "n_buckets"), grid_check=_grid_tree,
        x64=True)
    reg("aggs.hll_board", _agg_hll_board,
        static_argnames=("levels", "n_buckets"), grid_check=_grid_hll,
        x64=True)
    reg("aggs.mesh_tree_counts", _agg_mesh_tree_counts,
        static_argnames=("levels", "n_buckets", "mesh"),
        grid_check=_grid_tree, x64=True)
    reg("aggs.mesh_tree_metric", _agg_mesh_tree_metric,
        static_argnames=("levels", "n_buckets", "mesh"),
        grid_check=_grid_tree, x64=True)
    reg("aggs.mesh_hll_board", _agg_mesh_hll_board,
        static_argnames=("levels", "n_buckets", "mesh"),
        grid_check=_grid_hll, x64=True)


_register()


# ---------------------------------------------------------------------------
# columnar field store
# ---------------------------------------------------------------------------


# per-segment doc-values extraction lives in the shared segment block
# store (`elasticsearch_tpu/columnar/`): `ValuesBlock` is the exact
# shape the retired `_SegmentColumn` held, extracted once per (segment,
# field, live-set) and shared with every other consumer — this module's
# private `_seg_cache` is gone (tpulint TPU011 keeps it from growing
# back)


class AggColumn:
    """One field's columnar agg data over a reader snapshot, padded to the
    store's pow-2 row bucket. Each array is uploaded on its first use
    (`device`: the f64 pair under the scoped x64 flag so f64 survives),
    row-sharded when the serving policy routes this corpus to the mesh.

    `k32` is the 32-bit resident form, there where every value is an
    integer and the span fits: (v - k_base) / k_unit
    as int32, -1 where the row holds no value; `k_unit` is the gcd of
    the values' distances from the least (1000 for an `epoch_second`
    date held in millis), `k_max` the largest of them. A column that
    has it runs the 32-bit programs and its f64 pair stays on the host.
    A `scaled_float`'s column is held on its LATTICE (`k_scale`, the
    field's factor, above 1): its values are the stored longs v x
    k_scale, so `k_base` and `k_unit` count in 1 / k_scale and a value
    is (k_base + k32 x k_unit) / k_scale."""

    __slots__ = ("field", "version", "n_rows", "r_pad", "vals", "present",
                 "numeric", "integral_exact", "multi_valued", "ords_built",
                 "ords", "ord_keys", "vmin", "vmax",
                 "k32", "k_base", "k_unit", "k_max", "k_scale",
                 "hll_built", "hll_idx", "hll_rho", "_dev")

    def __init__(self, field: str):
        self.field = field
        self.version: tuple = None
        self.n_rows = 0
        self.r_pad = 1
        self.vals = np.full(1, np.nan, dtype=np.float64)
        self.present = np.zeros(1, dtype=bool)
        self.numeric = False
        self.integral_exact = False
        self.multi_valued = False
        self.ords_built = False
        self.ords: Optional[np.ndarray] = None    # int32[r_pad], -1 absent
        self.ord_keys: List[Any] = []             # ord -> raw key value
        self.vmin = None
        self.vmax = None
        self.k32: Optional[np.ndarray] = None     # int32[r_pad], -1 absent
        self.k_base = 0
        self.k_unit = 1
        self.k_max = 0
        self.k_scale = 1
        self.hll_built = False
        self.hll_idx: Optional[np.ndarray] = None  # int32[r_pad] register
        self.hll_rho: Optional[np.ndarray] = None  # int32[r_pad], 0 absent
        self._dev: Dict[tuple, tuple] = {}  # (name, sharded) -> (mesh, arr)

    def build_k32(self, integral: bool, scale: Optional[int] = None
                  ) -> None:
        """The 32-bit form of `vals`, or None where it cannot hold them:
        a value that is no integer (`integral`: every present value is
        one; a KEY needs no more, `integral_exact` is the sums' matter),
        a span of 2^31 - 1 units or more, or rows x span past what the
        host's int64 widening holds. With a `scale` (a `scaled_float`'s
        factor) the values are first taken to their stored longs, where
        each f64 value is exactly the quotient of its long by `scale`."""
        self.k32 = None
        self.k_scale = 1
        pv = self.vals[self.present]
        lattice = scale is not None and scale > 1 and len(pv) > 0
        if lattice:
            longs = np.rint(pv * scale)
            if not (np.isfinite(pv).all()
                    and np.array_equal(longs / scale, pv)):
                return
            pv, integral = longs, True
        if not (integral and len(pv) and self.r_pad <= N32_MAX_ROWS
                and float(np.abs(pv).max()) < _EXACT_INT):
            return
        iv = pv.astype(np.int64)
        base = int(iv.min())
        dist = iv - base
        unit = int(np.gcd.reduce(dist)) or 1
        k_max = int(dist.max()) // unit
        if k_max >= I32_MAX or k_max * unit * self.r_pad >= (1 << 62):
            return
        k32 = np.full(self.r_pad, -1, dtype=np.int32)
        k32[self.present] = dist // unit
        self.k32, self.k_base, self.k_unit, self.k_max = (
            k32, base, unit, k_max)
        self.k_scale = int(scale) if lattice else 1

    def to_k32(self, value) -> Optional[int]:
        """A value (a `missing` substitute) in the rebased domain, or
        None where it is not on the column's lattice or past int32."""
        try:
            f = float(value)
        except (TypeError, ValueError):
            return None
        if self.k32 is None or not math.isfinite(f):
            return None
        if self.k_scale != 1:
            long = float(np.rint(f * self.k_scale))
            if long / self.k_scale != f:
                return None
            f = long
        if not f.is_integer() or abs(f) >= _EXACT_INT:
            return None
        k, rem = divmod(int(f) - self.k_base, self.k_unit)
        return k if rem == 0 and 0 <= k < I32_MAX else None

    # ------------------------------------------------------------- device
    def device(self, name: str, mesh=None):
        """One of this column's arrays resident on the device ('vals',
        'present', 'ords', 'k32', 'hll_idx', 'hll_rho'), uploaded on
        first use; with a mesh, sharded by rows (r_pad must divide by
        the shard count; the caller checks)."""
        slot = (name, mesh is not None)
        got = self._dev.get(slot)
        if got is not None and got[0] is mesh:
            return got[1]
        import jax
        import jax.numpy as jnp
        from elasticsearch_tpu.ops.dispatch import _x64_scope
        host = getattr(self, name)
        with _x64_scope(host.dtype.itemsize == 8):
            arr = jnp.asarray(host)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from elasticsearch_tpu.parallel import mesh as mesh_lib
                arr = jax.device_put(
                    arr, NamedSharding(mesh, P(mesh_lib.SHARD_AXIS)))
        self._dev[slot] = (mesh, arr)
        return arr


class StoreSnapshot:
    """Immutable per-reader row-space description: built once per segment
    composition and handed to the whole compute pass, so a concurrent
    refresh-resync (which advances the store to a NEWER reader) can never
    swap the row map out from under an in-flight search's mask. With it
    the map's `RowLocator` (`vectors/filter_mask.py`), which turns a
    request's rows into positions without a search of the map."""

    __slots__ = ("version", "row_map", "n_rows", "r_pad", "locator")

    def __init__(self, version, row_map):
        self.version = version
        self.row_map = row_map
        self.n_rows = len(row_map)
        self.r_pad = _pow2(max(self.n_rows, 1))
        self.locator = RowLocator(row_map)

    def filter_mask(self, rows: np.ndarray) -> np.ndarray:
        """Matched-row mask over the padded row bucket — the `filter` half
        of the fused plan (rows are engine global rows, in any order;
        one the map does not hold is dropped). Written in time by the
        matched rows, in the form the input allows: a slice where `rows`
        IS one run of the map (its ends as many positions apart as it
        is long, and equal to the map between them: a time range over
        an index written in time order, a match-all), else `True`
        scattered at the locator's positions; only a map the locator
        cannot hold (`form == "search"`) is searched whole."""
        mask = np.zeros(self.r_pad, dtype=bool)
        n = len(rows)
        if not n:
            return mask
        loc = self.locator
        if not loc.exact:
            mask[: self.n_rows] = np.isin(self.row_map, rows)
            return mask
        ends = loc.positions(rows[[0, -1]])
        if len(ends) == 2 and ends[1] - ends[0] == n - 1 \
                and np.array_equal(rows, self.row_map[ends[0]:ends[1] + 1]):
            mask[ends[0]:ends[1] + 1] = True
        else:
            mask[loc.positions(rows)] = True
        return mask


class AggFieldStore:
    """Per-index columnar agg store over the combined reader: one
    AggColumn per touched field, rebuilt copy-on-write when the segment
    composition changes. Mirrors `ops/bm25.LexicalShard`'s lazy-sync
    contract — most refreshes never serve an agg, so columns build on
    first agg use and re-extract only delta segments after that."""

    def __init__(self, warmup: Optional[bool] = None, scale_of=None):
        self._columns: Dict[str, AggColumn] = {}
        # field -> a scaled_float's factor, else None (`AggColumn.k_scale`)
        self.scale_of = scale_of
        self._lock = threading.Lock()
        self._snap: Optional[StoreSnapshot] = None
        self.warmup = warmup
        self.stats = {"rebuilds": 0, "columns": 0, "bytes": 0}
        # per-field columnar composition summary of the LAST column
        # (re)build — the `columnar` annotation `profile.aggs` carries
        self.columnar_refresh: Dict[str, dict] = {}
        self._zero_ords: Dict[Any, Any] = {}

    @staticmethod
    def _fingerprint(reader) -> tuple:
        return tuple((v.segment.seg_id, v.segment.num_docs,
                      int(v.live.sum())) for v in reader.views)

    def snapshot(self, reader) -> StoreSnapshot:
        """The (cached) immutable row-space snapshot for this reader."""
        version = self._fingerprint(reader)
        with self._lock:
            if self._snap is not None and self._snap.version == version:
                return self._snap
        snap = StoreSnapshot(version, reader.live_global_rows())
        with self._lock:
            cur = self._snap
            if cur is not None and cur.version == version:
                return cur  # raced with an identical build: share it
            self._snap = snap
        return snap

    def fields(self) -> List[str]:
        with self._lock:
            return sorted(self._columns)

    def column(self, reader, field: str, want_ords: bool = False,
               snap: Optional[StoreSnapshot] = None,
               want_hll: bool = False) -> AggColumn:
        """The field's column for this reader snapshot, building or
        delta-rebuilding as needed. The returned column is consistent
        with `snap` (same version/row bucket) by construction."""
        if snap is None:
            snap = self.snapshot(reader)
        with self._lock:
            col = self._columns.get(field)
            if col is not None and col.version == snap.version \
                    and (not want_ords or col.ords_built) \
                    and (not want_hll or col.hll_built):
                return col
            col = self._build(reader, snap, field,
                              want_ords
                              or (col is not None and col.ords_built),
                              want_hll
                              or (col is not None and col.hll_built))
            self._columns[field] = col
            self.stats["rebuilds"] += 1
            self.stats["columns"] = len(self._columns)
            self.stats["bytes"] = sum(
                c.vals.nbytes + c.present.nbytes
                + (c.ords.nbytes if c.ords is not None else 0)
                + (c.k32.nbytes if c.k32 is not None else 0)
                + (c.hll_idx.nbytes + c.hll_rho.nbytes
                   if c.hll_idx is not None else 0)
                for c in self._columns.values())
            return col

    def _build(self, reader, snap: StoreSnapshot, field: str,
               want_ords: bool, want_hll: bool = False) -> AggColumn:
        from elasticsearch_tpu import columnar
        col = AggColumn(field)
        col.version = snap.version
        col.n_rows = snap.n_rows
        col.r_pad = snap.r_pad
        vals = np.full(snap.r_pad, np.nan, dtype=np.float64)
        present = np.zeros(snap.r_pad, dtype=bool)
        obj_parts: List[np.ndarray] = []
        off = 0
        multi = False
        n_cached = n_extracted = 0
        want_objs = want_ords or want_hll
        for view in reader.views:
            n_live = int(view.live.sum())
            # shared block-store read: append-only refreshes find every
            # pre-existing segment's block cached and extract only the
            # delta segments (one block per (segment, field, live-set),
            # shared with every consumer)
            sc, was_cached = columnar.STORE.values_block(
                view, field, want_objs)
            if was_cached:
                n_cached += 1
            else:
                n_extracted += 1
            vals[off:off + n_live] = sc.vals
            present[off:off + n_live] = sc.present
            if sc.objs is not None:
                obj_parts.append(sc.objs)
            elif want_objs:
                obj_parts.append(np.empty(n_live, dtype=object))
            multi = multi or sc.multi_valued
            off += n_live
        mode = columnar.STORE.note_composition(
            field, "values", n_cached, n_extracted)
        self.columnar_refresh[field] = {
            "blocks": n_cached + n_extracted, "cached": n_cached,
            "extracted": n_extracted, "mode": mode}
        col.vals = vals
        col.present = present
        col.multi_valued = multi
        col.ords_built = bool(want_ords)
        # the f64 column IS the numeric_values view: string/geo values are
        # simply absent from it, which matches the host loop's skip
        col.numeric = True
        pv = vals[present]
        if len(pv):
            col.vmin = float(pv.min())
            col.vmax = float(pv.max())
            integral = bool(np.isfinite(pv).all()
                            and np.all(pv == np.floor(pv)))
            col.integral_exact = bool(
                integral and float(np.abs(pv).sum()) < _EXACT_INT)
            col.build_k32(integral, self.scale_of(field)
                          if self.scale_of is not None else None)
            if col.k_scale != 1:
                telemetry.metrics.counter("aggs.columns.lattice").inc()
        else:
            col.integral_exact = True  # empty sums are trivially exact
        if want_ords and not multi:
            # global ordinals over the raw doc values (raw objects, not the
            # f64 view — terms keys keep int/str/bool identity)
            ords = np.full(snap.r_pad, -1, dtype=np.int32)
            keys: List[Any] = []
            index: Dict[Any, int] = {}
            if obj_parts:
                objs = np.concatenate(obj_parts)
                for i in range(off):
                    v = objs[i]
                    if v is None:
                        continue
                    k = tuple(v) if isinstance(v, (list, tuple)) else v
                    o = index.get(k)
                    if o is None:
                        o = index[k] = len(keys)
                        keys.append(v)
                    ords[i] = o
            col.ords = ords
            col.ord_keys = keys
        # like ords_built, hll_built marks the REQUEST satisfied even for
        # multi-valued columns (arrays stay None; the plan falls back on
        # multi_valued before touching them) so the cache check holds
        col.hll_built = bool(want_hll)
        if want_hll and not multi:
            # per-row HLL register columns over the same hash the host's
            # partial walker uses — so device register boards pack into
            # `$p` states any shard's host partial merges with exactly
            from elasticsearch_tpu.search.agg_partials import _hll_hash
            from elasticsearch_tpu.search.aggregations import _hashable
            hidx = np.zeros(snap.r_pad, dtype=np.int32)
            hrho = np.zeros(snap.r_pad, dtype=np.int32)
            if obj_parts:
                objs = np.concatenate(obj_parts)
                for i in range(off):
                    v = objs[i]
                    if v is None:
                        continue
                    h = _hll_hash(_hashable(v))
                    hidx[i] = h & (HLL_M - 1)
                    hrho[i] = (64 - HLL_P) - (h >> HLL_P).bit_length() + 1
            col.hll_idx = hidx
            col.hll_rho = hrho
        return col

    # ------------------------------------------------------------- warmup
    def warmup_entries(self, col: AggColumn, mesh=None) -> list:
        """Dispatch warmup grid for one freshly-built column (shape-only
        specs — no data materialized): the 32-bit programs where the
        column has its `k32`, the x64 ones where it has not."""
        import jax
        r = col.r_pad
        f64 = jax.ShapeDtypeStruct((r,), np.dtype(np.float64))
        b1 = jax.ShapeDtypeStruct((r,), np.dtype(bool))
        i32 = jax.ShapeDtypeStruct((r,), np.dtype(np.int32))
        hp = jax.ShapeDtypeStruct((6,), np.dtype(np.float64))
        mp = jax.ShapeDtypeStruct((2,), np.dtype(np.float64))
        op = jax.ShapeDtypeStruct((1,), np.dtype(np.float64))
        mm = jax.ShapeDtypeStruct((), np.dtype(np.int32))
        narrow = col.k32 is not None
        entries = []

        bits = limb_bits(r)
        n_limbs = n_limbs_for(col.k_max, bits)

        def n32(levels, ks, level_specs, metric):
            st = {"levels": levels, "n_buckets": ks}
            entries.append(("aggs.n32_counts", (b1,) + level_specs, dict(
                st, form=board_form(ks[0] + 1, levels[0]))))
            if metric:
                entries.append((
                    "aggs.n32_metric", (b1, i32, mm) + level_specs,
                    dict(st, parts=("sum",), limb_bits=bits,
                         n_limbs=n_limbs, form=board_form(
                             ks[0] + 1, levels[0], 1 + n_limbs))))

        rungs = set(WARMUP_AGG_BUCKETS)
        if col.ords is not None and col.ord_keys:
            b_ord = bucket_count(len(col.ord_keys))
            if b_ord is not None:
                # clamp: one pathological high-cardinality field must not
                # AOT-compile the giant rungs for every column build
                rungs.add(min(b_ord, WARMUP_MAX_ORD_B))
        if narrow:
            # a whole-match metric of this field (no level)
            entries.append((
                "aggs.n32_metric", (b1, i32, mm),
                {"levels": (), "n_buckets": (), "form": "onehot",
                 "parts": ("sum",), "limb_bits": bits,
                 "n_limbs": n_limbs}))
        for b in sorted(rungs):
            if col.ords is not None:
                n32(("ords",), (b,), (i32,), narrow)
                if not narrow:
                    entries.append(("aggs.tree_metric",
                                    (b1, mp, f64, b1, i32, op),
                                    {"levels": ("ord",),
                                     "n_buckets": (b,)}))
            if narrow:
                lo = jax.ShapeDtypeStruct((b + 1,), np.dtype(np.int32))
                n32(("bounds",), (b,), (i32, lo), True)
            elif col.numeric:
                cb = jax.ShapeDtypeStruct((b,), np.dtype(np.float64))
                for kind, level in (("hist", (f64, b1, hp)),
                                    ("cal", (f64, b1, cb, mp))):
                    st = {"levels": (kind,), "n_buckets": (b,)}
                    entries.append(("aggs.tree_counts", (b1,) + level, st))
                    entries.append(("aggs.tree_metric",
                                    (b1, mp, f64, b1) + level, st))
        if col.numeric:
            bounds = jax.ShapeDtypeStruct((AGG_B_LADDER[0], 2),
                                          np.dtype(np.float64))
            entries.append(("aggs.range_counts", (f64, b1, b1, bounds, mp),
                            {}))
            entries.append(("aggs.range_metric",
                            (f64, b1, b1, bounds, mp, mp, f64, b1), {}))
        if col.hll_built and col.hll_idx is not None:
            entries.append(("aggs.hll_board", (b1, i32, i32),
                            {"levels": (), "n_buckets": ()}))
        return entries

    def schedule_warmup(self, col: AggColumn) -> None:
        if not dispatch.warmup_enabled(self.warmup):
            return
        entries = self.warmup_entries(col)
        if entries:
            dispatch.DISPATCH.warmup(entries, background=True)

    def zero_ords(self, r_pad: int, mesh=None):
        """Cached all-zero int32 ordinal column over the row bucket — the
        bucket-id source for whole-match metric reduces (every row lands
        in lane 0)."""
        key = (r_pad, mesh)
        with self._lock:
            z = self._zero_ords.get(key)
            if z is not None:
                return z
        import jax
        import jax.numpy as jnp
        zeros = jnp.zeros(r_pad, dtype=jnp.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from elasticsearch_tpu.parallel import mesh as mesh_lib
            zeros = jax.device_put(
                zeros, NamedSharding(mesh, P(mesh_lib.SHARD_AXIS)))
        with self._lock:
            if len(self._zero_ords) > 8:
                self._zero_ords.clear()
            self._zero_ords[key] = zeros
        return zeros

    @staticmethod
    def mesh_ready(snap: StoreSnapshot, mesh) -> bool:
        """The aggs mesh kernels shard the row bucket evenly; a row bucket
        smaller than the shard axis can't."""
        if mesh is None:
            return False
        from elasticsearch_tpu.parallel import mesh as mesh_lib
        s = int(mesh.shape[mesh_lib.SHARD_AXIS])
        return snap.r_pad % s == 0 and snap.r_pad >= s
