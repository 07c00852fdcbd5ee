"""From a profiler trace to numbers: device busy and idle, time by XLA module
and by operation, one kernel's events, and idle gaps by the host span they
fall in.

`load_xplane` reads the `.xplane.pb` the JAX profiler writes into a neutral
structure (plain lists, what the fixture under bench/trace/ holds too):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

Everything else works on that structure, so the arithmetic can be checked by
hand on a small recorded trace (bench/tests/test_trace_reduce.py).

On a TPU the device planes are named `/device:TPU:<n>`; their line
`XLA Ops` holds one event per executed HLO operation (a Pallas kernel is one
such event, named after its custom call), `XLA Modules` one event per
executed program (`jit_<function>(<fingerprint>)`). Host threads are lines of
the plane `/host:CPU`; `jax.profiler.TraceAnnotation` spans appear there
under the name they were given. The benchmark's own spans start `bench:`,
the program's (trlx_tpu/observability/tracing.py `span`) `trlx:`.
"""

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
SPAN_PREFIXES = (SPAN_PREFIX, "trlx:")  # the benchmark's spans, the program's


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, keep_host=lambda name: name.startswith(SPAN_PREFIXES)) -> dict:
    """Device planes whole; of the host plane only the events `keep_host`
    accepts (a host plane holds every Python call)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events if device or keep_host(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _line(plane: dict, name: str):
    return next((ln["events"] for ln in plane["lines"] if ln["name"] == name), [])


def device_planes(trace: dict):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def host_spans(trace: dict):
    """[(name, start_ns, end_ns)] of the benchmark's and the program's spans
    on any host thread."""
    spans = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans += [(n, s, s + d) for n, s, d in line["events"] if n.startswith(SPAN_PREFIXES)]
    return sorted(spans, key=lambda x: x[1])


def merge_intervals(intervals):
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window_of(trace: dict):
    """The traced window in ns: the `bench:window` span where the run put
    one, else first device event to last."""
    for name, s, e in host_spans(trace):
        if name == SPAN_PREFIX + "window":
            return s, e
    starts, ends = [], []
    for plane in device_planes(trace):
        for n, s, d in _line(plane, OPS_LINE):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy(trace: dict, window=None) -> dict:
    """busy_s: seconds in which an operation ran, averaged over the device
    planes; window_s; and the merged busy intervals of each plane."""
    lo, hi = window or window_of(trace)
    per_plane = []
    for plane in device_planes(trace):
        ivs = clip([(s, s + d) for _, s, d in _line(plane, OPS_LINE)], lo, hi)
        per_plane.append(merge_intervals(ivs))
    if not per_plane:
        raise ValueError("the trace holds no device plane")
    busy_ns = [sum(e - s for s, e in ivs) for ivs in per_plane]
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9, "window_s": (hi - lo) / 1e9,
            "intervals": per_plane, "window": (lo, hi)}


def seconds_by_name(trace: dict, line_name: str, window=None) -> dict:
    """Total device seconds of each event name on one line, averaged over
    the device planes, within the window."""
    lo, hi = window or window_of(trace)
    planes = device_planes(trace)
    total = {}
    for plane in planes:
        for n, s, d in _line(plane, line_name):
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                total[n] = total.get(n, 0.0) + part / 1e9 / len(planes)
    return total


# a Pallas kernel is called once a layer, each call an instruction of its own
# (`%paged_decode.24`, `%paged_decode.25`, ...): one row a kernel, under the
# `name=` of its `pallas_call`
KERNEL_CALL = re.compile(r"^(%[A-Za-z_][\w-]*?)\.\d+ =(?= custom-call \[tpu_custom_call\])")


def leaf_seconds_by_name(trace: dict, window=None) -> dict:
    """Seconds by shortened operation name on the first device plane's
    `XLA Ops`, containers left out, so that the parts add up to busy time;
    the calls of one Pallas kernel are one row."""
    lo, hi = window or window_of(trace)
    total = {}
    for n, s, d in leaves(_line(device_planes(trace)[0], OPS_LINE)):
        part = min(s + d, hi) - max(s, lo)
        if part > 0:
            key = KERNEL_CALL.sub(r"\1.* =", short_name(n))
            total[key] = total.get(key, 0.0) + part / 1e9
    return total


def events_matching(trace: dict, line_name: str, match, window=None):
    """[(name, start_ns, duration_ns)] on the first device plane whose name
    `match` accepts, whole events that start inside the window."""
    lo, hi = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    return [(n, s, d) for n, s, d in _line(planes[0], line_name) if lo <= s < hi and match(n)]


def module_at(trace: dict):
    """A function ns -> name of the XLA module running then on the first
    device plane (None between programs)."""
    import bisect

    mods = sorted((s, s + d, n) for n, s, d in _line(device_planes(trace)[0], MODULES_LINE))
    starts = [m[0] for m in mods]

    def at(ns):
        i = bisect.bisect_right(starts, ns) - 1
        return mods[i][2] if i >= 0 and ns < mods[i][1] else None

    return at


def idle_gaps_by_span(trace: dict, window=None, min_gap_ns: int = 20_000) -> dict:
    """Idle seconds of the first device plane by the innermost span, of the
    benchmark's or the program's, that the host was in at the middle of each
    gap (`(no span)` if none)."""
    b = busy(trace, window)
    lo, hi = b["window"]
    ivs = b["intervals"][0]
    edges = [lo] + [x for s, e in ivs for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns]
    spans = [sp for sp in host_spans(trace) if sp[0] != SPAN_PREFIX + "window"]
    total = {}
    for s, e in gaps:
        mid = (s + e) // 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "(no span)"
        total[name] = total.get(name, 0.0) + (e - s) / 1e9
    return total


def short_name(name: str, limit: int = 96) -> str:
    """An `XLA Ops` event is named by its whole HLO instruction; keep the
    result's name and the operation: `%fusion.12 = fusion`, and for a custom
    call its target or kernel name where the text gives one."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:limit]
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    op = m.group(1) if m else ""
    extra = re.search(r'kernel_name[\\"=: ]+([A-Za-z0-9_.\-]+)', rest) or \
        re.search(r'custom_call_target="([^"]+)"', rest)
    label = f"{head} = {op}" + (f" [{extra.group(1)}]" if extra else "")
    return label[:limit]


def leaves(events):
    """The events that contain no other event of the same line (a `while`
    or a `conditional` spans its body's operations, which are events too)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    is_leaf = [True] * len(events)
    stack = []  # indices of open events
    for i in order:
        s = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            is_leaf[stack[-1]] = False
        stack.append(i)
    return [ev for ev, leaf in zip(events, is_leaf) if leaf]


def top(d: dict, n: int = 10):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace: dict) -> dict:
    """What a traced run reports: device busy and window seconds and the
    breakdown lists."""
    b = busy(trace)
    return {
        "busy_s": b["busy_s"], "window_s": b["window_s"],
        "breakdown": {
            "device_ops": top(leaf_seconds_by_name(trace, b["window"])),
            "idle_gaps": top(idle_gaps_by_span(trace, b["window"])),
        },
    }


def dump(trace: dict, path: str, n: int = 40):
    """Planes, lines, event counts and the heaviest event names of each line,
    as JSON: the by-hand look a new reader starts from."""
    import json

    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            total = {}
            for name, _, d in line["events"]:
                c = total.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += d
            heavy = sorted(total.items(), key=lambda kv: -kv[1][1])[:n]
            custom = sorted(((k, v) for k, v in total.items() if "custom" in k),
                            key=lambda kv: -kv[1][1])[:n]
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]), "distinct": len(total),
                        "heaviest": [[k, c, ns / 1e9] for k, (c, ns) in heavy],
                        "custom_calls": [[k, c, ns / 1e9] for k, (c, ns) in custom],
                        "first": line["events"][:5]})
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    # and 40 ms from the middle of the window, names cut short: the stuff a
    # fixture for bench/tests/test_trace_reduce.py is made of
    lo, hi = window_of(trace)
    mid = (lo + hi) // 2
    piece = cut(trace, mid, mid + 40_000_000)
    for plane in piece["planes"]:
        for line in plane["lines"]:
            line["events"] = [[nm[:160], st, du] for nm, st, du in line["events"]]
    with open(path + ".cut.json", "w") as f:
        json.dump(piece, f)


def cut(trace: dict, lo: int, hi: int) -> dict:
    """The events that overlap [lo, hi) ns, whole: how the recorded fixture
    under bench/trace/ was taken from a run's trace."""
    planes = []
    for plane in trace["planes"]:
        lines = [{"name": ln["name"],
                  "events": [ev for ev in ln["events"] if ev[1] < hi and ev[1] + ev[2] > lo]}
                 for ln in plane["lines"]]
        planes.append({"name": plane["name"], "lines": [ln for ln in lines if ln["events"]]})
    return {"planes": planes}
