"""A count kept by `benchmark/serve.py` in the child (`counter`, e.g. the
collector's pause nanoseconds): its change over the window, times `scale`.
With `"per": "second"`, over the window's seconds; with
`"since_last_read": true`, not its change but its value as read after the
window (a maximum that the child starts anew at each read, the last of them
just before the window)."""


def read(spec: dict, ctx: dict):
    value = ctx["counters_after"].get(spec["counter"])
    if value is None:
        return None
    if not spec.get("since_last_read"):
        value -= ctx["counters_before"].get(spec["counter"], 0)
    if spec.get("per") == "second":
        value /= ctx["seconds"]
    return value * spec.get("scale", 1.0)
