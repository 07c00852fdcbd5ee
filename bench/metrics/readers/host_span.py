"""A statistic of one of the program's host spans inside the traced window.
`params.span` is the span's whole name (`trlx:engine.step`); `params.stat`:

- `share`: the seconds inside the spans, clipped to the window, over the
  window, in percent;
- `host_ms`: the mean, over the spans that lie wholly inside the window, of a
  span's length minus the time inside it in which an operation ran on the
  device, in ms: what the host adds to the device's work round one call;
- `gap_ms`: the mean time from the end of one such span to the start of the
  next on the same thread, in ms, over the pairs wholly inside the window.

A program without the span (a parent commit) gives nothing to read."""

import bisect
import statistics

from benchlib.files import load_module


def spans_by_thread(trace, name):
    """[[(start_ns, end_ns), ...] per host thread that holds the span]"""
    reduce = load_module("trace/reduce.py")
    threads = []
    for plane in trace["planes"]:
        if plane["name"] != reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans = sorted((s, s + d) for n, s, d in line["events"] if n == name)
            if spans:
                threads.append(spans)
    return threads


def busy_inside(intervals, starts, lo, hi):
    """ns of the sorted disjoint `intervals` (their `starts` beside them)
    that fall inside [lo, hi)."""
    total = 0
    for s, e in intervals[max(bisect.bisect_right(starts, lo) - 1, 0):]:
        if s >= hi:
            break
        total += max(min(e, hi) - max(s, lo), 0)
    return total


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    threads = spans_by_thread(trace, params["span"])
    if not threads:
        return None
    lo, hi = reduce.window_of(trace)
    stat = params["stat"]
    if stat == "share":
        inside = reduce.merge_intervals(reduce.clip([sp for t in threads for sp in t], lo, hi))
        return 100.0 * sum(e - s for s, e in inside) / (hi - lo)
    whole = [[(s, e) for s, e in t if lo <= s and e <= hi] for t in threads]
    if stat == "host_ms":
        intervals = reduce.busy(trace)["intervals"][0]
        starts = [s for s, _ in intervals]
        xs = [(e - s) - busy_inside(intervals, starts, s, e) for t in whole for s, e in t]
    elif stat == "gap_ms":
        xs = [b[0] - a[1] for t in whole for a, b in zip(t, t[1:])]
    else:
        raise ValueError(f"host_span: unknown stat {stat!r}")
    if not xs:
        return None
    ctx.log(f"{params['span']} {stat}: {len(xs)} samples, median "
            f"{statistics.median(xs) / 1e6:.3f} ms, max {max(xs) / 1e6:.3f} ms")
    return statistics.fmean(xs) / 1e6
