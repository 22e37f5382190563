"""Sum over `paths` of a `_nodes/stats` counter's change over the window."""

from benchmark import arithmetic


def read(spec: dict, ctx: dict):
    return arithmetic.delta(ctx["before"], ctx["after"], spec["paths"])
