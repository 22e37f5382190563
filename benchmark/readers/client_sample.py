"""A statistic of the load generator's own sample of the window.

    lateness_p95_ms   95th percentile of send time less due time
    stall_max_ms      the longest time in the window with no answer at all
"""

from benchmark import arithmetic


def read(spec: dict, ctx: dict):
    s = ctx["sample"]
    if not s.index:
        return None
    if spec["statistic"] == "lateness_p95_ms":
        return arithmetic.percentile(
            arithmetic.lateness_ms(s.due, s.sent), 95)
    if spec["statistic"] == "stall_max_ms":
        done = sorted(d for d in s.done if d is not None)
        edges = [s.t0] + done + [s.t0 + s.seconds]
        return max(b - a for a, b in zip(edges, edges[1:])) * 1000.0
    raise ValueError(f"unknown statistic {spec['statistic']!r}")
