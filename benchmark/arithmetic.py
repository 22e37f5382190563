"""The arithmetic of the end-to-end metrics and the spreads.

A latency is counted from when the request was DUE, so a generator that
stalls raises the tail. A request that failed or never answered is a
latency of infinity: it is missing from no percentile. A rate is all the
work completed inside the window over all the window's seconds.
"""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over ALL values (q in (0, 100])."""
    if not values:
        raise ValueError("no sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies_ms(due: Sequence[float], done: Sequence[Optional[float]],
                 ok: Sequence[bool]) -> list:
    """Milliseconds from due to done; infinity where the request failed."""
    return [(d1 - d0) * 1000.0 if good and d1 is not None else INF
            for d0, d1, good in zip(due, done, ok)]


def lateness_ms(due: Sequence[float], sent: Sequence[float]) -> list:
    """How late the generator sent each request, in milliseconds."""
    return [max(0.0, (s - d) * 1000.0) for d, s in zip(due, sent)]


def rate_in_window(done: Sequence[Optional[float]], weight: Sequence[float],
                   ok: Sequence[bool], t0: float, seconds: float) -> float:
    """Work whose answer arrived inside [t0, t0 + seconds], over seconds."""
    total = sum(w for d, w, good in zip(done, weight, ok)
                if good and d is not None and t0 <= d <= t0 + seconds)
    return total / seconds


def end_to_end(spec: dict, sample, ok: Sequence[bool], setup_s: float):
    """An end-to-end metric by the statistic its file names:

    setup_seconds       process start to the first timed request
    latency_percentile  `q`-th percentile over ALL requests of the window,
                        each timed from when it was due
    rate_in_window      answers (`"of": "requests"`) or their weights
                        (`"of": "weight"`, the documents of a `_bulk`) that
                        arrived inside the window, over its seconds
    """
    stat = spec["statistic"]
    if stat == "setup_seconds":
        return setup_s
    if stat == "latency_percentile":
        return percentile(latencies_ms(sample.due, sample.done, ok),
                          spec["q"])
    if stat == "rate_in_window":
        weight = sample.weight if spec["of"] == "weight" else [1.0] * len(ok)
        return rate_in_window(sample.done, weight, ok, sample.t0,
                              sample.seconds)
    raise ValueError(f"unknown end-to-end statistic {stat!r}")


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median (the contract's)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def lookup(tree: Optional[dict], path: str):
    """`a/b/c` into nested dicts; None where a step is missing."""
    node = tree
    for step in path.split("/"):
        if not isinstance(node, dict) or step not in node:
            return None
        node = node[step]
    return node


def delta(before: dict, after: dict, paths: Sequence[str]) -> Optional[float]:
    """Sum over `paths` of after - before; None where a path is missing."""
    total = 0.0
    for p in paths:
        a, b = lookup(after, p), lookup(before, p)
        if a is None:
            return None
        total += a - (b or 0)
    return total
