"""Mean per event of the program's telemetry histograms over the window:
the `sum_nanos` changes of `sum_of`, over the `count` change of `count_of`.
Both are exact; the histograms' percentiles are log2 bucket edges, a factor
of two wide, and are never read."""

from benchmark import arithmetic


def read(spec: dict, ctx: dict):
    def hist(stats, name):
        return arithmetic.lookup(stats, "telemetry/histograms/" + name) or {}

    n = (hist(ctx["after"], spec["count_of"]).get("count", 0)
         - hist(ctx["before"], spec["count_of"]).get("count", 0))
    if n <= 0:
        return None
    total = sum(hist(ctx["after"], h).get("sum_nanos", 0)
                - hist(ctx["before"], h).get("sum_nanos", 0)
                for h in spec["sum_of"])
    return total / n * spec["scale"]
