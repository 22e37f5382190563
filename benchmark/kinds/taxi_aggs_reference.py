"""The plain reference of `nyc-taxis-dash`, in numpy alone: nothing here
imports `elasticsearch_tpu` or JAX.

The deployment (Elastic's Rally track `nyc_taxis`): taxi rides of 2015,
each a document of about twenty fields, and the track's three
aggregation operations as `size: 0` requests.

    rows, requests    made from the seed (`RideCorpus`, `RideRows`): block
                      b of the corpus is a function of (seed, b, rows in
                      all), request i of (seed, i) alone
    PANELS            the three operations; request i is panel i % 3.
                      `distance_amount_agg` is the track's body verbatim;
                      the other two take the track's `range` on
                      `dropoff_datetime` in `dd/MM/yyyy` (`lte` rounded up
                      to the day's end) from a whole day of 2015 drawn
                      from the seed, as many days wide as the traffic
                      file's `window_days` gives (request i: its
                      [(i // 3) % len] entry; 21 is the track's own)
    rows.body(req)    the `_search` body as it is sent (`auto_buckets`,
                      the `buckets` of `auto_date_histogram`, from the
                      traffic file too)
    answer(req)       what a correct server answers
    differs(got, want)  the comparison (below)

`auto_date_histogram`, as Elasticsearch 7.x answers it (written from
that statement alone, not from the program): roundings second (inner
intervals 1, 5, 10, 30; unit "s"), minute (1, 5, 10, 30; "m"), hour (1,
3, 12; "h"), day (1, 7; "d"), month (1, 3; "M"), year (1, 5, 10, 20, 50,
100; "y"). From the matched rows' least and greatest keys, n is the
count of a rounding's buckets between their roundings, empty ones
included; the first rounding with n <= buckets x its largest inner
interval is taken; where n passes `buckets`, the least inner interval m
with ceil(n / m) <= buckets; buckets merge m at a time from the first,
under the first one's key, every one from the first to the last, empty
ones too; `interval` is "<m><unit>".

What is stated is EXACT: keys, `key_as_string` (the field's own
`yyyy-MM-dd HH:mm:ss`), `doc_count`, bucket order and presence,
`interval`, `hits.total`, and a `stats`' `count`, `min` and `max` (the
stored long over the factor). `sum` and `avg` lie within 1e-12 of the
exact int64 sum of cents over 100 (Elasticsearch sums doubles with
compensation; a float32 accumulator misses by about 1e-7). The control
(`answer(..., sum_dtype=np.float32)`): every `sum` accumulated in
float32, row after row.
"""

from __future__ import annotations

import datetime
from typing import Optional, Sequence, Tuple

import numpy as np

DAY_MS = 86_400_000
TRACK_TOTAL_HITS = 10_000
REQUEST_CHUNK = 1024
SUM_REL = 1e-12
PANELS = ("distance_amount_agg", "autohisto_agg", "date_histogram_agg")

_FIXED_MS = {"s": 1000, "m": 60_000, "h": 3_600_000, "d": DAY_MS}
ROUNDINGS = (("s", (1, 5, 10, 30)), ("m", (1, 5, 10, 30)),
             ("h", (1, 3, 12)), ("d", (1, 7)), ("M", (1, 3)),
             ("y", (1, 5, 10, 20, 50, 100)))

# the track's 22 fields, each a column of a ride (`bulk_body`)
_VENDOR = ("CMT", "VTS", "DDS")


def stamp(ms: int) -> str:
    """`yyyy-MM-dd HH:mm:ss` of epoch millis (UTC): the field's format."""
    return datetime.datetime.fromtimestamp(
        ms / 1000, datetime.timezone.utc).strftime("%Y-%m-%d %H:%M:%S")


def ddmmyyyy(ms: int) -> str:
    return datetime.datetime.fromtimestamp(
        ms / 1000, datetime.timezone.utc).strftime("%d/%m/%Y")


def _cents(v: int) -> str:
    """A whole number of cents as the JSON number of dollars."""
    sign = "-" if v < 0 else ""
    return "%s%d.%02d" % (sign, abs(v) // 100, abs(v) % 100)


class RideCorpus:
    """The rides of one run. `data.rides` in the configuration's file
    holds the generator's parameters (its `assumed` list states them);
    the traffic file (`mix`) the windows' widths and the auto histogram's
    target buckets."""

    dims = 0                    # no vector: `setup.load_rows` prints it

    def __init__(self, seed: int, config: dict, n_rows: int, mix: dict):
        self.seed = int(seed)
        self.n_rows = int(n_rows)
        self.widths = tuple(int(w) for w in mix["window_days"])
        self.auto_buckets = int(mix["auto_buckets"])
        self.block_docs = int(config["data"]["block_docs"])
        r = config["data"]["rides"]
        self.t0_ms = int(r["span_start_s"]) * 1000
        self.days = int(r["span_days"])
        self.r = r
        # an hour's weight: a daily cycle (the night is quiet)
        h = np.arange(self.days * 24)
        weight = 1.0 + float(r["daily_amplitude"]) * np.sin(
            2 * np.pi * ((h % 24) - 10) / 24.0)
        self._hour_cdf = np.concatenate([[0.0],
                                         np.cumsum(weight / weight.sum())])
        self._blocks = {}

    def _pickups(self, rng, lo: int, n: int) -> np.ndarray:
        """Rows lo..lo+n in pickup order: row j is the ((j + u) / rows)-
        quantile of the hourly density, u uniform; epoch millis of whole
        seconds."""
        q = (np.arange(lo, lo + n) + rng.random(n)) / float(self.n_rows)
        hour = np.clip(np.searchsorted(self._hour_cdf, q, side="right") - 1,
                       0, len(self._hour_cdf) - 2)
        inside = (q - self._hour_cdf[hour]) / (self._hour_cdf[hour + 1]
                                               - self._hour_cdf[hour])
        sec = np.clip((inside * 3600).astype(np.int64), 0, 3599)
        return self.t0_ms + (hour * 3600 + sec) * 1000

    def block(self, b: int) -> dict:
        got = self._blocks.get(b)
        if got is not None:
            return got
        r = self.r
        rng = np.random.default_rng([self.seed, 1, b])
        n = self.block_docs
        pickup = self._pickups(rng, b * n, n)
        minutes = np.clip(np.exp(np.log(float(r["minutes_median"]))
                                 + float(r["minutes_sigma"])
                                 * rng.standard_normal(n)), 1 / 60, 180)
        dropoff = pickup + np.rint(minutes * 60).astype(np.int64) * 1000
        miles = np.exp(np.log(float(r["miles_median"]))
                       + float(r["miles_sigma"]) * rng.standard_normal(n))
        miles[rng.random(n) < float(r["zero_share"])] = 0.0
        tail = rng.random(n) < float(r["tail_share"])
        miles[tail] = 50 + 250 * rng.random(int(tail.sum()))
        distance = np.rint(miles * 100).astype(np.int64)      # cents
        fare = 250 + np.rint(2.5 * distance + 30 * minutes).astype(np.int64)
        extra = np.where(rng.random(n) < 0.4, 50, 0)
        extra[rng.random(n) < 0.1] = 100
        mta, surcharge = 50, 30
        payment = np.where(rng.random(n) < 0.6, 1, 2)           # card, cash
        tip = np.where(payment == 1, np.rint(
            fare * (0.15 + 0.1 * rng.random(n))).astype(np.int64), 0)
        tolls = np.where(rng.random(n) < float(r["tolls_share"]), 555, 0)
        total = fare + extra + mta + surcharge + tip + tolls
        refund = rng.random(n) < float(r["refund_share"])
        for col in (fare, extra, tip, tolls, total):
            col[refund] = -col[refund]
        got = {"pickup": pickup, "dropoff": dropoff, "distance": distance,
               "total": total, "fare": fare, "extra": extra, "tip": tip,
               "tolls": tolls, "payment": np.where(refund, 3, payment),
               "passengers": rng.integers(1, 7, size=n),
               "vendor": rng.integers(1, 3, size=n),
               "lon": -74.0 + 0.2 * rng.random((n, 2)),
               "lat": 40.6 + 0.25 * rng.random((n, 2)),
               "flag": rng.random(n) < 0.01}
        self._blocks[b] = got
        return got

    def bulk_body(self, b: int, index: str,
                  docs: Optional[int] = None) -> bytes:
        """One `_bulk` body: the first `docs` rides of block b as the
        track's documents (no `_id`), amounts in dollars and cents."""
        blk = self.block(b)
        n = self.block_docs if docs is None else docs
        action = '{"index":{"_index":"%s"}}' % index
        lines = []
        for j in range(n):
            lines.append(action)
            v = int(blk["vendor"][j])
            lines.append(
                '{"vendor_id":"%d","vendor_name":"%s","cab_color":"yellow",'
                '"pickup_datetime":"%s","dropoff_datetime":"%s",'
                '"passenger_count":%d,"trip_distance":%s,'
                '"pickup_location":[%.6f,%.6f],'
                '"dropoff_location":[%.6f,%.6f],"rate_code_id":"1",'
                '"store_and_fwd_flag":"%s","payment_type":"%d",'
                '"fare_amount":%s,"extra":%s,"mta_tax":0.50,'
                '"tip_amount":%s,"tolls_amount":%s,"ehail_fee":0.00,'
                '"improvement_surcharge":0.30,"surcharge":0.00,'
                '"total_amount":%s,"trip_type":"1"}'
                % (v, _VENDOR[v - 1], stamp(int(blk["pickup"][j])),
                   stamp(int(blk["dropoff"][j])),
                   int(blk["passengers"][j]), _cents(int(blk["distance"][j])),
                   blk["lon"][j, 0], blk["lat"][j, 0], blk["lon"][j, 1],
                   blk["lat"][j, 1], "Y" if blk["flag"][j] else "N",
                   int(blk["payment"][j]), _cents(int(blk["fare"][j])),
                   _cents(int(blk["extra"][j])), _cents(int(blk["tip"][j])),
                   _cents(int(blk["tolls"][j])), _cents(int(blk["total"][j]))))
        return ("\n".join(lines) + "\n").encode()

    def rows(self, blocks: Sequence[Tuple[int, int]]) -> "RideRows":
        parts = [(self.block(b), n) for b, n in blocks]
        cols = {k: np.concatenate([p[k][:n] for p, n in parts])
                for k in ("dropoff", "distance", "total")}
        self._blocks.clear()
        return RideRows(self, cols["dropoff"], cols["distance"],
                        cols["total"])


class RideRows:
    """What the index holds, flat: the three columns the panels read
    (dropoff millis, distance and total in cents), the source of requests
    and of the exact answers."""

    def __init__(self, corpus: RideCorpus, dropoff, distance, total):
        self.corpus = corpus
        self.dropoff, self.distance, self.total = dropoff, distance, total
        self._order = np.argsort(dropoff, kind="stable")
        self._sorted = dropoff[self._order]

    def __len__(self) -> int:
        return len(self.dropoff)

    # -- the requests --------------------------------------------------------
    def requests(self, first: int, count: int) -> list:
        """(panel, start day's epoch millis or None, width in days) of
        requests first..first+count of the run's one stream."""
        c = self.corpus
        out = []
        for chunk in range(first // REQUEST_CHUNK,
                           (first + count - 1) // REQUEST_CHUNK + 1):
            u = np.random.default_rng([c.seed, 2, chunk]).random(
                REQUEST_CHUNK)
            lo = max(first, chunk * REQUEST_CHUNK)
            hi = min(first + count, (chunk + 1) * REQUEST_CHUNK)
            for i in range(lo, hi):
                panel = PANELS[i % len(PANELS)]
                if panel == "distance_amount_agg":
                    out.append((panel, None, None))
                    continue
                width = c.widths[(i // len(PANELS)) % len(c.widths)]
                day = int(u[i - chunk * REQUEST_CHUNK]
                          * (c.days - width + 1))
                out.append((panel, c.t0_ms + day * DAY_MS, width))
        return out

    def body(self, panel: str, start: Optional[int],
             width: Optional[int]) -> dict:
        return body(panel, start, width, self.corpus.auto_buckets)

    # -- the plain reference -------------------------------------------------
    def matching(self, panel: str, start: Optional[int],
                 width: Optional[int]) -> np.ndarray:
        if panel == "distance_amount_agg":
            return np.nonzero((self.distance >= 0)
                              & (self.distance < 5000))[0]
        a, b = np.searchsorted(self._sorted,
                               [start, start + width * DAY_MS],
                               side="left")
        return self._order[a:b]

    def matched_rows(self, panel, start, width) -> int:
        return len(self.matching(panel, start, width))

    def answer(self, panel: str, start: Optional[int],
               width: Optional[int], sum_dtype=np.int64) -> dict:
        """A correct server's answer; with `sum_dtype` float32, every
        `sum` accumulated in float32 row after row (the control's)."""
        rows = self.matching(panel, start, width)
        n = len(rows)
        total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                 else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
        if panel == "distance_amount_agg":
            aggs = {"distance_histo": self._distance(rows, sum_dtype)}
        elif panel == "autohisto_agg":
            aggs = {"dropoffs_over_time": self._auto(rows)}
        else:
            aggs = {"dropoffs_over_time": self._daily(rows)}
        return {"total": total, "aggregations": aggs}

    def _distance(self, rows, sum_dtype) -> dict:
        if not len(rows):
            return {"buckets": []}
        key = self.distance[rows] // 100
        amount = self.total[rows]
        first, last = int(key.min()), int(key.max())
        order = np.argsort(key, kind="stable")
        cut = np.searchsorted(key[order], np.arange(first, last + 2))
        buckets = []
        for j, k in enumerate(range(first, last + 1)):
            a = amount[order[cut[j]:cut[j + 1]]]
            c = len(a)
            if not c:
                stats = {"count": 0, "min": None, "max": None, "avg": None,
                         "sum": 0.0}
            else:
                if sum_dtype == np.int64:
                    s = int(a.sum()) / 100
                else:       # row after row, in the dollars a server holds
                    s = float(np.cumsum((a / 100).astype(sum_dtype),
                                        dtype=sum_dtype)[-1])
                stats = {"count": c, "min": int(a.min()) / 100,
                         "max": int(a.max()) / 100, "avg": s / c, "sum": s}
            buckets.append({"key": float(k), "doc_count": c,
                            "total_amount_stats": stats})
        return {"buckets": buckets}

    def _daily(self, rows) -> dict:
        if not len(rows):
            return {"buckets": []}
        day = self.dropoff[rows] // DAY_MS
        first = int(day.min())
        counts = np.bincount(day - first)
        return {"buckets": [
            {"key_as_string": stamp((first + j) * DAY_MS),
             "key": (first + j) * DAY_MS, "doc_count": int(c)}
            for j, c in enumerate(counts)]}

    def _auto(self, rows) -> dict:
        if not len(rows):
            return {"buckets": [], "interval": "1s"}
        keys = self.dropoff[rows]
        target = self.corpus.auto_buckets
        for unit, inner in ROUNDINGS:
            lo, hi = rounded(keys.min(), unit), rounded(keys.max(), unit)
            every = steps(lo, hi, unit)
            if len(every) <= target * inner[-1] or unit == "y":
                break
        n = len(every)
        m = 1
        if n > target:
            m = next((x for x in inner if -(-n // x) <= target), 1)
        lane = np.searchsorted(every, rounded(keys, unit), side="right") - 1
        counts = np.bincount(lane // m, minlength=-(-n // m))
        return {"buckets": [
            {"key_as_string": stamp(int(every[g * m])),
             "key": int(every[g * m]), "doc_count": int(c)}
            for g, c in enumerate(counts)], "interval": f"{m}{unit}"}


def rounded(ms, unit: str):
    """Epoch millis rounded down to a unit (UTC), as int64."""
    if unit in _FIXED_MS:
        return np.asarray(ms, dtype=np.int64) // _FIXED_MS[unit] \
            * _FIXED_MS[unit]
    t = np.asarray(ms, dtype=np.int64).astype("datetime64[ms]")
    return t.astype("datetime64[M]" if unit == "M" else "datetime64[Y]") \
        .astype("datetime64[ms]").astype(np.int64)


def steps(lo: int, hi: int, unit: str) -> np.ndarray:
    """Every rounded key from lo to hi, both in."""
    if unit in _FIXED_MS:
        return np.arange(int(lo), int(hi) + 1, _FIXED_MS[unit],
                         dtype=np.int64)
    u = "M" if unit == "M" else "Y"
    a, b = (np.int64(x).astype("datetime64[ms]").astype(f"datetime64[{u}]")
            for x in (lo, hi))
    return np.arange(a, b + 1).astype("datetime64[ms]").astype(np.int64)


def body(panel: str, start: Optional[int], width: Optional[int],
         auto_buckets: int) -> dict:
    """The `_search` body of one request."""
    if panel == "distance_amount_agg":          # the track's, verbatim
        return {"size": 0,
                "query": {"bool": {"filter": {"range": {"trip_distance": {
                    "lt": 50, "gte": 0}}}}},
                "aggs": {"distance_histo": {
                    "histogram": {"field": "trip_distance", "interval": 1},
                    "aggs": {"total_amount_stats": {"stats": {
                        "field": "total_amount"}}}}}}
    query = {"range": {"dropoff_datetime": {
        "gte": ddmmyyyy(start), "lte": ddmmyyyy(start + (width - 1) * DAY_MS),
        "format": "dd/MM/yyyy"}}}
    if panel == "autohisto_agg":
        agg = {"auto_date_histogram": {"field": "dropoff_datetime",
                                       "buckets": auto_buckets}}
    elif panel == "date_histogram_agg":
        agg = {"date_histogram": {"field": "dropoff_datetime",
                                  "calendar_interval": "day"}}
    else:
        raise ValueError(f"no panel {panel!r}")
    return {"size": 0, "query": query, "aggs": {"dropoffs_over_time": agg}}


def control_answers(rows: RideRows, requests: list) -> list:
    """The control's answers: the reference in the program's place,
    every `sum` in float32."""
    made = {}
    for req in requests:
        if req not in made:
            made[req] = rows.answer(*req, sum_dtype=np.float32)
    return [made[req] for req in requests]


def _close(got, want) -> bool:
    return isinstance(got, (int, float)) and not isinstance(got, bool) \
        and abs(float(got) - want) <= SUM_REL * abs(want)


def _stats_differ(got, want: dict) -> bool:
    if not isinstance(got, dict) or set(got) != set(want):
        return True
    for k in ("count", "min", "max"):
        if got[k] != want[k]:
            return True
    if want["count"] == 0:
        return got["avg"] is not None or got["sum"] != 0
    return not (_close(got["sum"], want["sum"])
                and _close(got["avg"], want["avg"]))


def differs(got: dict, want: dict) -> bool:
    """Does a served answer (`{"total", "aggregations"}`) differ from the
    reference's? Exact in everything but a `stats`' `sum` and `avg`
    (SUM_REL, relative); bucket keys compared as numbers (a histogram's
    key 3.0 equals 3)."""
    if got.get("total") != want["total"]:
        return True
    aggs = got.get("aggregations")
    if not isinstance(aggs, dict) or set(aggs) != set(want["aggregations"]):
        return True
    for name, w in want["aggregations"].items():
        g = aggs[name]
        if not isinstance(g, dict) or set(g) != set(w):
            return True
        if "interval" in w and g["interval"] != w["interval"]:
            return True
        gb, wb = g.get("buckets"), w["buckets"]
        if not isinstance(gb, list) or len(gb) != len(wb):
            return True
        for x, y in zip(gb, wb):
            if not isinstance(x, dict) or set(x) != set(y):
                return True
            for k, v in y.items():
                if k == "total_amount_stats":
                    if _stats_differ(x[k], v):
                        return True
                elif x[k] != v:
                    return True
    return False
