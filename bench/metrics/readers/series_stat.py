"""A statistic (median, mean, p95) of one series the job recorded, times
`params.scale`, optionally over a constant the job gave (slots)."""

import statistics

from benchlib.result import percentile


def read(m, params, ctx):
    xs = m.get("series", {}).get(params["series"])
    if not xs:
        return None
    stat = params["stat"]
    if stat == "median":
        value = statistics.median(xs)
    elif stat == "mean":
        value = statistics.fmean(xs)
    elif stat.startswith("p"):
        value = percentile(xs, float(stat[1:]))
    else:
        raise ValueError(f"series_stat: unknown stat {stat!r}")
    if "per_constant" in params:
        value /= m["constants"][params["per_constant"]]
    return value * params["scale"]
