"""A kNN roofline share from the profiler's trace: the least time the chip
could take for the traced window's searches (`benchmark/roofline.py`), over
ALL device-busy time of that window. The cell sends one kind of request, so
no kernel names are needed. The searches are the REQUESTS the batcher
handed to the device (`indices/knn/scheduler/requests`; the program's
`indices/knn/searches` counts dispatched batches), from `_nodes/stats` read
just inside the traced window, so they are never over-counted."""

from benchmark import arithmetic, roofline


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or ctx["platform"] != "tpu":
        return None         # a CPU rehearsal has no chip to take a share of
    searches = arithmetic.delta(ctx["trace_before"], ctx["trace_after"],
                                spec["searches"])
    cfg = ctx["config"]
    return roofline.share_percent(
        int(searches or 0), ctx["rows"], cfg["dims"], cfg["device_dtype"],
        roofline.peaks_for(ctx["device_kind"]), trace["busy_s"])
