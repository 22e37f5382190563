"""`nyc-taxis-dash`'s aggregation roofline share from the profiler's
trace: the least time the chip could take for the requests answered
inside the traced seconds, over ALL device-busy time of those seconds
(`benchmark/roofline_aggs.py`'s floor and share).

A request's floor is its matched rows, read off its own answer
(`roofline_aggs.matched_rows`), times 4 bytes for each field its
aggregations read: the three columns the operations read are int32 a
row on the device (a stamp in seconds, an amount as its stored long),
and a `scaled_float` has no width in the mapping's table. So
`distance_amount_agg` reads 8 bytes a row (trip_distance, total_amount),
the two date operations 4 (dropoff_datetime). Only requests SENT inside
the traced seconds and answered are counted. None on a CPU rehearsal and
where the trace holds nothing."""

import json

from benchmark import roofline, roofline_aggs
from benchmark.kinds import taxi_aggs_reference as reference

FIELD_BYTES = 4


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s") or ctx["platform"] != "tpu":
        return None         # a CPU rehearsal has no chip to take a share of
    s = ctx["sample"]
    start = s.t0 + max(0.0, s.seconds - float(spec["traced_seconds"]))
    width = {}
    for panel in reference.PANELS:
        dated = panel != "distance_amount_agg"
        aggs = reference.body(panel, 0 if dated else None,
                              1 if dated else None, 1)["aggs"]
        width[panel] = FIELD_BYTES * len(roofline_aggs.fields_read(aggs))
    work = []
    for i, sent, done, status, raw in zip(s.index, s.sent, s.done,
                                          s.status, s.raw):
        if done is None or status != 200 or sent < start:
            continue
        try:
            answer = json.loads(raw)["aggregations"]
        except (ValueError, KeyError, TypeError):
            continue
        panel = reference.PANELS[i % len(reference.PANELS)]
        work.append((roofline_aggs.matched_rows(answer), width[panel]))
    return roofline_aggs.share_percent(
        work, roofline.peaks_for(ctx["device_kind"]), trace["busy_s"])
