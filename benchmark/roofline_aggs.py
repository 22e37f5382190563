"""The least time the chip could take for the aggregation work of a window.

    least = sum over requests of matched_rows * row_bytes / peak_bytes_per_s

`matched_rows` is what the request's ANSWER says it counted (the sum of a
bucket aggregation's `doc_count`s, plus a terms' `sum_other_doc_count`);
`row_bytes` is the bytes one row of the fields its aggregations read holds
at the MAPPING's widths (`date` 8, `integer` 4, ...), each field once a
request however many nodes read it. The `range` that selects the rows is
free: a column sorted by time finds it by bisection.

A floor under ANY implementation: whatever kernel, accumulator width or
mask answers, it reads every matched row's aggregated fields at least
once. So the share cannot pass 100 %, and a PR that rewrites the kernels
(split-precision accumulators, a gather of the matched run) cannot make
the count stale. Aggregation is a scatter-add: no multiply floor exists.
"""

from __future__ import annotations

# bytes a doc value of a mapped type holds (Lucene's numeric doc values'
# widths: a date is a long of millis, an ip 16 bytes, a boolean a byte)
TYPE_BYTES = {"date": 8, "date_nanos": 8, "long": 8, "double": 8,
              "integer": 4, "float": 4, "short": 2, "half_float": 2,
              "byte": 1, "boolean": 1, "ip": 16}


def fields_read(aggs: dict) -> set:
    """Every `field` an aggregation tree names, at any depth."""
    out = set()
    for spec in aggs.values():
        for kind, body in spec.items():
            if kind in ("aggs", "aggregations"):
                out |= fields_read(body)
            elif isinstance(body, dict) and isinstance(body.get("field"),
                                                       str):
                out.add(body["field"])
    return out


def row_bytes(aggs: dict, properties: dict) -> int:
    """Bytes one matched row of the request's aggregated fields holds.
    A field of a type with no width here is an error, never a default."""
    return sum(TYPE_BYTES[properties[f]["type"]] for f in fields_read(aggs))


def matched_rows(answer_aggs: dict) -> int:
    """Rows the answer says it aggregated: the most any of its top-level
    bucket aggregations counted (each saw the same rows; a row without a
    value of the field is in none of its buckets). 0 where the answer has
    no bucket aggregation."""
    most = 0
    for node in answer_aggs.values():
        if isinstance(node, dict) and isinstance(node.get("buckets"), list):
            n = sum(int(b.get("doc_count", 0)) for b in node["buckets"])
            most = max(most, n + int(node.get("sum_other_doc_count", 0)))
    return most


def least_seconds(requests, peaks: dict) -> float:
    """`requests`: (matched_rows, row_bytes) pairs."""
    return sum(float(m) * b for m, b in requests) / peaks["bytes_per_s"]


def share_percent(requests, peaks: dict, busy_s: float):
    """Least time over device-busy time, in percent; None (never 0) where
    there is nothing to read."""
    requests = list(requests)
    if not requests or not busy_s or busy_s <= 0:
        return None
    return 100.0 * least_seconds(requests, peaks) / busy_s
