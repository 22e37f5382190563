"""An aggregation roofline share from the profiler's trace: the least time
the chip could take for the requests answered inside the traced seconds
(`benchmark/roofline_aggs.py`: each one's matched rows, read off its own
answer, times the bytes a row of the fields its aggregations read holds
at the mapping's widths), over ALL device-busy time of those seconds.

Only requests SENT inside the traced seconds and answered are counted
(one sent before them did part of its device work untraced), so the work
is never over-counted. A request's aggregations are those of the kind's
own body for its place in the stream (`aggs_reference.body`: panel i % 4;
the bounds of its range do not matter here). None on a CPU rehearsal
and where the trace holds nothing."""

import json

from benchmark import roofline, roofline_aggs
from benchmark.kinds import aggs_reference as reference


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or ctx["platform"] != "tpu":
        return None         # a CPU rehearsal has no chip to take a share of
    s = ctx["sample"]
    start = s.t0 + max(0.0, s.seconds - float(spec["traced_seconds"]))
    props = ctx["config"]["index"]["mappings"]["properties"]
    width = {}
    for name, span in reference.PANELS:
        aggs = reference.body(name, 0 if span else None)["aggs"]
        width[name] = roofline_aggs.row_bytes(aggs, props)
    work = []
    for i, sent, done, status, raw in zip(s.index, s.sent, s.done,
                                          s.status, s.raw):
        if done is None or status != 200 or sent < start:
            continue
        try:
            answer = json.loads(raw)["aggregations"]
        except (ValueError, KeyError, TypeError):
            continue
        panel = reference.PANELS[i % len(reference.PANELS)][0]
        work.append((roofline_aggs.matched_rows(answer), width[panel]))
    return roofline_aggs.share_percent(
        work, roofline.peaks_for(ctx["device_kind"]), trace["busy_s"])
