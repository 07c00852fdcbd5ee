"""The engine's decode pipeline, step by step, from the identity the program
gives each step (`InferenceEngine._dispatch_decode`): the counter span
`trlx:engine.queued seq=N ..` stands directly in front of the step's
`trlx:engine.dispatch` span and `trlx:engine.fetched seq=N` directly behind
the `trlx:engine.fetch` span that waited for its outputs, on one thread. The
device runs the decode programs in the order they were queued, so dispatches
of consecutive `seq` are consecutive `jit_decode*` module events, and the one
offset between the two lists is pinned by what cannot be otherwise: no program
starts before its dispatch began, and no fetch ends before its program has.

`params.stat`, in ms over the steps inside the traced window (a step whose
dispatch, device event or fetch the window's edge cuts is left out of the
samples that need it):

- `slack_ms`: the median of the start of a step's device event minus the end
  of its dispatch span: how long a queued step waits for the device, so how
  far ahead of the chip the host runs (0: the chip starves);
- `host_loop_ms`: the mean of the end of a step's fetch to the end of the
  next dispatch span: the host's own path a step (emit, reclaim, admit, the
  insert's dispatch, the next dispatch), whether or not the device hides it;
- `fetch_late_ms`: the median of the end of a step's fetch minus the end of
  its device event, over the fetches that began before the device finished:
  the runtime's wake-up and the outputs' landing.

Step k's device event ends, the host is `fetch_late` behind it, runs its
loop and dispatches step k+2, which waits `slack` for the device: the three
add up to the time from the end of step k's event to the start of step
k+2's, which a busy device spends on step k+1 and the admissions between.
`host_loop_ms` logs that sum against the trace's own gap and the device's
work in it.

Nothing to read on a program without the `seq=` counter spans (a parent
commit). Refuses (nothing, and the log says why) where the `seq` numbers are
not consecutive or the dispatches and the decode events do not pair in order:
no offset fits (an event is missing, or one is there that nobody of the trace
dispatched) or more than one does. A step queued when the trace ended has no
event and is a pair the edge cut."""

import bisect
import statistics

from benchlib.files import load_module

QUEUED, DISPATCH = "trlx:engine.queued ", "trlx:engine.dispatch"
FETCH, FETCHED = "trlx:engine.fetch", "trlx:engine.fetched "
DECODE_MODULE = "jit_decode"


def seq_of(name: str) -> int:
    return int(dict(kv.split("=", 1) for kv in name.split()[1:])["seq"])


def host_side(spans):
    """({seq: its dispatch span}, {seq: the fetch span that waited for it},
    every dispatch span) from the host spans in start order; a span is
    (start_ns, end_ns)."""
    dispatch, fetch, dispatches = {}, {}, []
    queued = last_fetch = None
    for name, start, end in spans:
        if name.startswith(QUEUED):
            queued = seq_of(name)
        elif name == DISPATCH:
            dispatches.append((start, end))
            if queued is not None:
                dispatch[queued], queued = (start, end), None
        elif name == FETCH:
            last_fetch = (start, end)
        elif name.startswith(FETCHED) and last_fetch is not None:
            fetch[seq_of(name)], last_fetch = last_fetch, None
    return dispatch, fetch, dispatches


def offsets_that_fit(seqs, dispatch, fetch, events):
    """Every o for which dispatch `seqs[i]` <-> `events[i + o]` breaks
    neither order, over the pairs that exist."""
    fits = []
    for o in range(1 - len(seqs), len(events)):
        pairs = [(seq, events[i + o]) for i, seq in enumerate(seqs) if 0 <= i + o < len(events)]
        if all(ev[0] >= dispatch[seq][0] and (seq not in fetch or ev[1] <= fetch[seq][1])
               for seq, ev in pairs):
            fits.append(o)
    return fits


def join(trace, log):
    """{seq: (dispatch span, device event, fetch span or None)} for the
    dispatches that found their `jit_decode*` event, with every dispatch span
    of the trace and its module events; None where there is nothing to join
    or the join is refused."""
    reduce = load_module("trace/reduce.py")
    planes = reduce.device_planes(trace)
    dispatch, fetch, dispatches = host_side(reduce.host_spans(trace))
    if not planes or not dispatch:
        return None
    lo, hi = reduce.window_of(trace)
    modules = sorted((s, s + d, n) for ln in planes[0]["lines"] if ln["name"] == reduce.MODULES_LINE
                     for n, s, d in ln["events"])
    events = [(s, e) for s, e, n in modules if n.startswith(DECODE_MODULE)]
    seqs = sorted(dispatch)
    if seqs != list(range(seqs[0], seqs[0] + len(seqs))):
        log(f"step_pipeline REFUSES: the dispatches' seq numbers are not consecutive "
            f"({len(seqs)} from {seqs[0]} to {seqs[-1]})")
        return None
    fits = offsets_that_fit(seqs, dispatch, fetch, events)
    if len(fits) != 1:
        log(f"step_pipeline REFUSES: {len(fits)} offsets pair {len(seqs)} dispatches with "
            f"{len(events)} {DECODE_MODULE}* events in order ({fits[:4]})")
        return None
    steps = {seq: (dispatch[seq], events[i + fits[0]], fetch.get(seq))
             for i, seq in enumerate(seqs) if 0 <= i + fits[0] < len(events)}

    def inside(span):
        return lo <= span[0] and span[1] <= hi

    # a dispatch at the trace's end whose program the trace ended before, and
    # an event at its start that was dispatched before it began, are pairs
    # that an edge cuts, like those the window's edge cuts: left out, counted
    inside_d = [seq for seq in seqs if inside(dispatch[seq])]
    inside_ev = sum(inside(ev) for ev in events)
    whole = sum(seq in steps and inside(steps[seq][1]) for seq in inside_d)
    ended = sum(seq not in steps for seq in inside_d)
    began = inside_ev - sum(inside(ev) for _, ev, _ in steps.values())
    log(f"step_pipeline: {len(inside_d)} dispatches inside the window, {whole} joined to one "
        f"{DECODE_MODULE}* event whole inside it, {len(inside_d) - whole - ended} to one its edge "
        f"cuts, {ended} queued when the trace ended; {inside_ev} such events inside it, "
        f"{inside_ev - began} joined to one dispatch, {began} dispatched before the trace began; "
        f"seq {seqs[0]}..{seqs[-1]}")
    return {"steps": steps, "dispatches": dispatches, "modules": modules, "window": (lo, hi)}


def next_dispatch(dispatches, starts, ns):
    i = bisect.bisect_left(starts, ns)
    return dispatches[i] if i < len(dispatches) else None


def log_identity(j, log):
    """slack of step k+2 + fetch-late of step k + host loop after step k,
    against the time from the end of step k's device event to the start of
    step k+2's, and what the device ran in it: means over the steps k whose
    next dispatch is step k+2's, all of it inside the window."""
    steps, (lo, hi) = j["steps"], j["window"]
    starts = [s for s, _ in j["dispatches"]]
    rows = []
    for seq, (_, ev, fetch) in steps.items():
        after = steps.get(seq + 2)
        if after is None or fetch is None or ev[1] < lo or after[1][0] > hi \
                or next_dispatch(j["dispatches"], starts, fetch[1]) != after[0]:
            continue
        ran = [(min(e, after[1][0]) - max(s, ev[1]), n.startswith(DECODE_MODULE))
               for s, e, n in j["modules"] if e > ev[1] and s < after[1][0]]
        rows.append((after[1][0] - after[0][1], fetch[1] - ev[1], after[0][1] - fetch[1],
                     after[1][0] - ev[1], sum(ns for ns, decode in ran if decode),
                     sum(ns for ns, decode in ran if not decode)))
    if not rows:
        return
    slack, late, loop, gap, decode, other = (statistics.fmean(col) / 1e6 for col in zip(*rows))
    log(f"step_pipeline identity over {len(rows)} steps: slack {slack:.4f} + fetch-late {late:.4f} "
        f"+ host loop {loop:.4f} = {slack + late + loop:.4f} ms; from the end of step k's device "
        f"event to the start of step k+2's {gap:.4f} ms, in which the device ran {decode:.4f} ms "
        f"of {DECODE_MODULE}* and {other:.4f} ms of other programs")


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    j = join(trace, ctx.log)
    if j is None:
        return None
    lo, hi = j["window"]
    stat = params["stat"]
    steps = j["steps"].values()
    if stat == "slack_ms":
        xs = [ev[0] - d[1] for d, ev, _ in steps if lo <= d[0] and ev[1] <= hi]
    elif stat == "fetch_late_ms":
        xs = [f[1] - ev[1] for _, ev, f in steps
              if f is not None and lo <= ev[0] and f[1] <= hi and f[0] < ev[1]]
    elif stat == "host_loop_ms":
        log_identity(j, ctx.log)
        starts = [s for s, _ in j["dispatches"]]
        pairs = [(f, next_dispatch(j["dispatches"], starts, f[1])) for _, _, f in steps
                 if f is not None and lo <= f[1]]
        xs = [nxt[1] - f[1] for f, nxt in pairs if nxt is not None and nxt[1] <= hi]
    else:
        raise ValueError(f"step_pipeline: unknown stat {stat!r}")
    if len(xs) < 2:
        return None
    ctx.log(f"step_pipeline {stat}: {len(xs)} samples, median {statistics.median(xs) / 1e6:.4f} "
            f"ms, mean {statistics.fmean(xs) / 1e6:.4f}, 5th percentile "
            f"{statistics.quantiles(xs, n=20)[0] / 1e6:.4f}, {sum(x < 100_000 for x in xs)} under 0.1 ms")
    return (statistics.fmean(xs) if stat == "host_loop_ms" else statistics.median(xs)) / 1e6
