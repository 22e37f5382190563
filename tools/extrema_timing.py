"""Time a 32-bit metric board's min and max on each route `extrema_form`
can pick, on the device JAX finds, to set `ops/aggs.EXTREMA_TILED_LANES`.

A shape is `whole` (no level: one lane) or its levels joined by `,`, each
`kind:lanes`: `bounds:513` is one level of 512 bounds, `ords:4097` one of
4,096 ordinals, `ords:512,ords:8` a chain of 4,096 lanes. For each row
bucket and shape it times the metric program with `parts=("min",
"max")` under each route, forced, and the same program with no extremum
(the count's product alone), whose time it subtracts. Every route's
boards must be equal, or the script fails.

    python tools/extrema_timing.py [--rows 18 19] [--shapes whole bounds:513 ...]

The last line of its output is the table as JSON; `--out FILE` writes it
there too.
"""

import argparse
import functools
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from elasticsearch_tpu.ops import aggs as aggs_ops  # noqa: E402

SHAPES = ["whole", "bounds:129", "bounds:513", "bounds:2049", "bounds:8193"]


def _ms(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1000.0)
    return statistics.median(times), np.asarray(out)


def _levels(shape, r, rng):
    """(kinds, ks, device args) of a shape's levels at r rows."""
    kinds, ks, args = [], [], []
    for level in ([] if shape == "whole" else shape.split(",")):
        kind, lanes = level.split(":")
        k = int(lanes) - 1
        if kind == "bounds":
            bounds = np.concatenate([[-1], np.sort(rng.choice(
                np.arange(1 << 20), k, replace=False))]).astype(np.int32)
            key = rng.integers(-1, 1 << 20, r).astype(np.int32)
            args += [jax.device_put(key), jax.device_put(bounds)]
        elif kind == "ords":
            args.append(jax.device_put(
                rng.integers(-1, k, r).astype(np.int32)))
        else:
            raise SystemExit(f"unknown level kind {kind!r} in {shape!r}")
        kinds.append(kind)
        ks.append(k)
    return tuple(kinds), tuple(ks), args


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[18, 19],
                    help="row buckets, as powers of two")
    ap.add_argument("--shapes", nargs="+", default=SHAPES)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=44)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    rng = np.random.default_rng(a.seed)
    table = []
    for p in a.rows:
        r = 1 << p
        mask = jax.device_put(rng.random(r) < 0.7)
        v = jax.device_put(rng.integers(-1, 1 << 20, r).astype(np.int32))
        for shape in a.shapes:
            levels, ks, level_args = _levels(shape, r, rng)
            args = (mask, v, np.int32(-1), *level_args)
            lanes = [k + 1 for k in ks]
            total = math.prod(lanes)
            routes = ("reduced", "scattered") if total == 1 \
                else ("tiled", "scattered")
            row = {"rows": r, "shape": shape, "lanes": total,
                   "picked": aggs_ops.extrema_form(levels, lanes, "onehot")}
            base = jax.jit(functools.partial(
                aggs_ops._agg_n32_metric, levels=levels, n_buckets=ks,
                parts=(), limb_bits=0, n_limbs=0, form="onehot"))
            row["count_ms"], _ = _ms(base, args, a.reps)
            boards = []
            for route in routes:
                real = aggs_ops.extrema_form
                aggs_ops.extrema_form = lambda *_a, route=route: route
                try:
                    fn = jax.jit(functools.partial(
                        aggs_ops._agg_n32_metric, levels=levels,
                        n_buckets=ks, parts=("min", "max"), limb_bits=0,
                        n_limbs=0, form="onehot")).lower(*args).compile()
                finally:
                    aggs_ops.extrema_form = real
                ms, got = _ms(fn, args, a.reps)
                row[route + "_ms"] = ms
                row[route + "_extrema_ms"] = ms - row["count_ms"]
                boards.append(got)
            if not all(np.array_equal(boards[0], b) for b in boards[1:]):
                raise SystemExit(f"routes disagree at {r} rows, {shape}")
            print(json.dumps(row), flush=True)
            table.append(row)
    result = {"device": dev.device_kind, "platform": dev.platform,
              "reps": a.reps, "table": table}
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
