"""A `_nodes/stats` counter's change over the window, over another's, or
over the window's seconds (`"per": "second"`). Nothing where the
denominator did not move."""

from benchmark import arithmetic


def read(spec: dict, ctx: dict):
    top = arithmetic.delta(ctx["before"], ctx["after"], spec["paths"])
    if spec.get("per") == "second":
        bottom = ctx["seconds"]
    else:
        bottom = arithmetic.delta(ctx["before"], ctx["after"], spec["over"])
    if top is None or not bottom:
        return None
    return top / bottom * spec.get("scale", 1.0)
