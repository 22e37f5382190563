"""The plain reference of `http-logs-dash`, in numpy alone: nothing here
imports `elasticsearch_tpu` or JAX.

The deployment (Elastic's Rally track `http_logs`): web-server log lines
of five fields (`@timestamp` in epoch seconds, `clientip`, `request`,
`status`, `size`), written in time order as a log is, and a dashboard's
`size: 0` panels over them under a time picker.

    rows, requests    made from the seed (`LogCorpus`, `LogRows`): block b
                      of the corpus is a function of (seed, b, rows in
                      all), request i of (seed, i) alone
    PANELS            the four panels; request i is panel i % 4 at a whole
                      hour `t` drawn uniformly over those that leave the
                      panel's range inside the corpus's span
    body(panel, t)    the `_search` body as it is sent
    answer(panel, t)  what a correct server answers: `hits.total` as the
                      default `track_total_hits` of 10,000 states it, and
                      the aggregations tree: bucket keys in epoch millis,
                      `key_as_string` in `strict_date_optional_time`,
                      `doc_count` and `sum` by `np.bincount` / `np.add.at`
                      in int64 over the matching rows, terms ordered by
                      count then key, every hourly bucket from the first to
                      the last that holds a row (`min_doc_count` 0)

What is stated is EXACT: counts and sums of whole numbers far under 2^53.
The control (`answer(..., sum_dtype=np.float32)`) is the nearest precision
below: every `sum` accumulated in float32, row after row. An hour's sum
may stay under 2^24 and come out right; a week's does not.
"""

from __future__ import annotations

import datetime
from typing import Optional, Sequence, Tuple

import numpy as np

HOUR = 3600
DAY = 24 * HOUR
TRACK_TOTAL_HITS = 10_000       # the default: counted exactly up to here
TERMS_SIZE = 10
REQUEST_CHUNK = 1024

# name -> (width of the `range` on @timestamp in hours; 0 = no query)
PANELS = (("hourly", 0), ("bytes-by-hour", 7 * 24), ("status-in-range", 24),
          ("status-by-hour", 24))

_PATHS = ("/english/images/team_hm_header_%d.gif", "/images/s%d.gif",
          "/english/playing/body%d.html", "/french/news/%d.htm")


def iso(seconds: int) -> str:
    """`strict_date_optional_time` of a whole second, as the program
    renders a `key_as_string` and as a time picker sends a bound."""
    return datetime.datetime.fromtimestamp(
        int(seconds), datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%S.000Z")


class LogCorpus:
    """The log lines of one run. `data.logs` in the configuration's file
    holds the generator's parameters (PERF.md section 4, `assumed`)."""

    dims = 0                    # no vector: `setup.load_rows` prints it

    def __init__(self, seed: int, config: dict, n_rows: int):
        self.seed = int(seed)
        self.n_rows = int(n_rows)
        self.block_docs = int(config["data"]["block_docs"])
        logs = config["data"]["logs"]
        self.t0 = int(logs["span_start_s"])
        self.hours = int(logs["span_days"]) * 24
        self.statuses = np.asarray(logs["status"]["values"], dtype=np.int64)
        w = 1.0 / np.arange(1, len(self.statuses) + 1) ** float(
            logs["status"]["zipf_s"])
        self._status_cdf = np.cumsum(w / w.sum())
        self.size_median = float(logs["size"]["median"])
        self.size_sigma = float(logs["size"]["sigma"])
        self.size_max = int(logs["size"]["max"])
        self.clients = int(logs["clientip"]["addresses"])
        self.paths = int(logs["request"]["paths"])
        self._client_cdf = _zipf_cdf(self.clients)
        self._path_cdf = _zipf_cdf(self.paths)
        # an hour's weight: a daily cycle (1 + a sin) times its day's
        # level; match days are `peak` times as busy. Never an empty day.
        h = np.arange(self.hours)
        cycle = 1.0 + float(logs["daily_amplitude"]) * np.sin(
            2 * np.pi * ((h % 24) - 9) / 24.0)
        level = np.ones(self.hours // 24)
        level[np.asarray(logs["match_days"], dtype=np.int64)] = float(
            logs["match_day_level"])
        weight = cycle * np.repeat(level, 24)
        self._hour_cdf = np.concatenate([[0.0], np.cumsum(weight
                                                          / weight.sum())])
        self._blocks = {}

    def _times(self, rng, lo: int, n: int) -> np.ndarray:
        """Rows lo..lo+n of a log written in time order: row j is the
        ((j + u) / rows)-quantile of the hourly density, u uniform, so
        the stamps ascend and every seed draws other seconds."""
        q = (np.arange(lo, lo + n) + rng.random(n)) / float(self.n_rows)
        hour = np.clip(np.searchsorted(self._hour_cdf, q, side="right") - 1,
                       0, self.hours - 1)
        inside = (q - self._hour_cdf[hour]) / (self._hour_cdf[hour + 1]
                                               - self._hour_cdf[hour])
        sec = np.clip((inside * HOUR).astype(np.int64), 0, HOUR - 1)
        return self.t0 + hour * HOUR + sec

    def block(self, b: int) -> dict:
        got = self._blocks.get(b)
        if got is not None:
            return got
        rng = np.random.default_rng([self.seed, 1, b])
        n = self.block_docs
        ts = self._times(rng, b * n, n)
        status = self.statuses[np.minimum(
            np.searchsorted(self._status_cdf, rng.random(n), side="right"),
            len(self.statuses) - 1)]
        size = np.clip(np.rint(self.size_median * np.exp(
            self.size_sigma * rng.standard_normal(n))), 0,
            self.size_max).astype(np.int64)
        size[status == 304] = 0
        client = np.minimum(np.searchsorted(self._client_cdf, rng.random(n),
                                            side="right"), self.clients - 1)
        path = np.minimum(np.searchsorted(self._path_cdf, rng.random(n),
                                          side="right"), self.paths - 1)
        got = {"ts": ts, "status": status, "size": size, "client": client,
               "path": path}
        self._blocks[b] = got
        return got

    def bulk_body(self, b: int, index: str,
                  docs: Optional[int] = None) -> bytes:
        """One `_bulk` body: the first `docs` lines of block b, as the
        track sends them: no id (append-only), `@timestamp` in epoch
        seconds."""
        blk = self.block(b)
        n = self.block_docs if docs is None else docs
        action = '{"index":{"_index":"%s"}}' % index
        lines = []
        for ts, c, p, st, sz in zip(blk["ts"][:n].tolist(),
                                    blk["client"][:n].tolist(),
                                    blk["path"][:n].tolist(),
                                    blk["status"][:n].tolist(),
                                    blk["size"][:n].tolist()):
            lines.append(action)
            lines.append(
                '{"@timestamp":%d,"clientip":"%d.%d.%d.%d",'
                '"request":"GET %s HTTP/1.0","status":%d,"size":%d}'
                % (ts, 40 + (c >> 16), (c >> 8) & 255, c & 255, 0,
                   _PATHS[p & 3] % p, st, sz))
        return ("\n".join(lines) + "\n").encode()

    def rows(self, blocks: Sequence[Tuple[int, int]]) -> "LogRows":
        parts = [(self.block(b), n) for b, n in blocks]
        cols = {k: np.concatenate([p[k][:n] for p, n in parts])
                for k in ("ts", "status", "size")}
        self._blocks.clear()
        return LogRows(self, cols["ts"], cols["status"], cols["size"])


def _zipf_cdf(count: int) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1, dtype=np.float64)
    return np.cumsum(w / w.sum())


class LogRows:
    """What the index holds, flat: the three columns the panels read,
    the source of requests and of the exact answers."""

    def __init__(self, corpus: LogCorpus, ts, status, size):
        self.corpus = corpus
        self.ts, self.status, self.size = ts, status, size
        # the reference does not lean on the order the rows were made in
        self._order = np.argsort(ts, kind="stable")
        self._sorted = ts[self._order]

    def __len__(self) -> int:
        return len(self.ts)

    # -- the requests --------------------------------------------------------
    def requests(self, first: int, count: int) -> list:
        """(panel, t) of requests first..first+count of the run's one
        stream: panel i % 4; t a whole hour (epoch seconds) drawn
        uniformly over those that leave the panel's range inside the
        span; None for `hourly`, which has no range."""
        c = self.corpus
        out = []
        for chunk in range(first // REQUEST_CHUNK,
                           (first + count - 1) // REQUEST_CHUNK + 1):
            u = np.random.default_rng([c.seed, 2, chunk]).random(
                REQUEST_CHUNK)
            lo = max(first, chunk * REQUEST_CHUNK)
            hi = min(first + count, (chunk + 1) * REQUEST_CHUNK)
            for i in range(lo, hi):
                name, width = PANELS[i % len(PANELS)]
                t = None
                if width:
                    t = c.t0 + HOUR * int(u[i - chunk * REQUEST_CHUNK]
                                          * (c.hours - width + 1))
                out.append((name, t))
        return out

    # -- the plain reference -------------------------------------------------
    def matching(self, lo: Optional[int], hi: Optional[int]) -> np.ndarray:
        """Rows whose stamp lies in [lo, hi) seconds; all where None."""
        if lo is None:
            return self._order
        a, b = np.searchsorted(self._sorted, [lo, hi], side="left")
        return self._order[a:b]

    def _by_hour(self, rows: np.ndarray):
        """(first hour's epoch seconds, counts a whole hour) from the
        first to the last hour that holds one of `rows`."""
        hour = self.ts[rows] // HOUR
        first = int(hour.min())
        return first * HOUR, hour - first, int(hour.max()) - first + 1

    def _terms(self, status: np.ndarray):
        keys, counts = np.unique(status, return_counts=True)
        order = np.lexsort((keys, -counts))[:TERMS_SIZE]
        kept = int(counts[order].sum())
        return {"doc_count_error_upper_bound": 0,
                "sum_other_doc_count": int(counts.sum()) - kept,
                "buckets": [{"key": int(keys[i]),
                             "doc_count": int(counts[i])} for i in order]}

    def panel_rows(self, panel: str, t: Optional[int]) -> np.ndarray:
        """The rows the panel's query matches at the whole hour `t`."""
        width = dict(PANELS)[panel]
        return self.matching(t, None if t is None else t + width * HOUR)

    def answer(self, panel: str, t: Optional[int],
               sum_dtype=np.int64) -> dict:
        """A correct server's answer; with another `sum_dtype`, every
        `sum` accumulated at that width (the control's)."""
        rows = self.panel_rows(panel, t)
        n = len(rows)
        total = ({"value": n, "relation": "eq"} if n <= TRACK_TOTAL_HITS
                 else {"value": TRACK_TOTAL_HITS, "relation": "gte"})
        aggs = {}
        if panel == "status-in-range":
            aggs["by_status"] = self._terms(self.status[rows])
            return {"total": total, "aggregations": aggs}
        buckets = []
        if n:
            start, ids, span = self._by_hour(rows)
            counts = np.bincount(ids, minlength=span)
            buckets = [{"key_as_string": iso(start + h * HOUR),
                        "key": (start + h * HOUR) * 1000,
                        "doc_count": int(counts[h])} for h in range(span)]
            if panel == "bytes-by-hour":
                sums = np.zeros(span, dtype=sum_dtype)
                np.add.at(sums, ids, self.size[rows].astype(sum_dtype))
                for b, s in zip(buckets, sums):
                    b["bytes"] = {"value": int(s)}
            elif panel == "status-by-hour":
                status = self.status[rows]
                by = np.argsort(ids, kind="stable")
                cut = np.searchsorted(ids[by], np.arange(span + 1))
                for h, b in enumerate(buckets):
                    b["by_status"] = self._terms(
                        status[by[cut[h]:cut[h + 1]]])
        aggs["by_hour"] = {"buckets": buckets}
        if panel == "bytes-by-hour":
            size = self.size[rows].astype(sum_dtype)
            # row after row, as an accumulator of that width would
            aggs["total_bytes"] = {"value": int(
                np.cumsum(size, dtype=sum_dtype)[-1] if n else 0)}
        return {"total": total, "aggregations": aggs}

    def matched_rows(self, panel: str, t: Optional[int]) -> int:
        return len(self.panel_rows(panel, t))


def body(panel: str, t: Optional[int]) -> dict:
    """The `_search` body of one panel at the whole hour `t`."""
    width = dict(PANELS)[panel]
    out = {"size": 0}
    if width:
        out["query"] = {"range": {"@timestamp": {
            "gte": iso(t), "lt": iso(t + width * HOUR)}}}
    if panel == "hourly":           # Rally's hourly_agg, verbatim
        out["aggs"] = {"by_hour": {"date_histogram": {
            "field": "@timestamp", "calendar_interval": "hour"}}}
    elif panel == "bytes-by-hour":
        out["aggs"] = {
            "by_hour": {"date_histogram": {"field": "@timestamp",
                                           "fixed_interval": "1h"},
                        "aggs": {"bytes": {"sum": {"field": "size"}}}},
            "total_bytes": {"sum": {"field": "size"}}}
    elif panel == "status-in-range":
        out["aggs"] = {"by_status": {"terms": {"field": "status",
                                               "size": TERMS_SIZE}}}
    elif panel == "status-by-hour":
        out["aggs"] = {"by_hour": {
            "date_histogram": {"field": "@timestamp",
                               "fixed_interval": "1h"},
            "aggs": {"by_status": {"terms": {"field": "status",
                                             "size": TERMS_SIZE}}}}}
    else:
        raise ValueError(f"no panel {panel!r}")
    return out


def control_answers(rows: LogRows, requests: list) -> list:
    """The control's answers to `requests` ((panel, t) pairs): the
    reference in the program's place, every `sum` in float32."""
    made = {}
    for req in requests:
        if req not in made:
            made[req] = rows.answer(*req, sum_dtype=np.float32)
    return [made[req] for req in requests]


def differs(got: dict, want: dict) -> bool:
    """Does a served answer (`{"total", "aggregations"}`) differ from
    the reference's in any bucket's key, `key_as_string`, `doc_count`,
    order, presence, `sum` or `sum_other_doc_count`, or in `hits.total`?
    Plain equality of the two trees; a `sum` of 5.0 equals 5. What a
    server adds beside the stated keys of a histogram (`interval`) is
    not compared."""
    if got.get("total") != want["total"]:
        return True
    aggs = got.get("aggregations")
    if not isinstance(aggs, dict) or set(aggs) != set(want["aggregations"]):
        return True
    for name, w in want["aggregations"].items():
        g = aggs[name]
        if "buckets" in w and "doc_count_error_upper_bound" not in w:
            g = {"buckets": g.get("buckets")} if isinstance(g, dict) else g
        if g != w:
            return True
    return False
