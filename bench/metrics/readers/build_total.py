"""What the process spent building programs before it was ready, from the
program's own account of it (`trlx_tpu.observability.compile_ledger`:
every trace to a jaxpr, lowering, backend compile and cache read, heard from
`jax.monitoring`). The program's `tracing.stop()` writes the account into
the trace as counter spans whose NAMES carry the numbers,
`trlx:build.total mark=<name> programs=.. builds=.. trace_s=.. ...`, one a
mark (the totals from the process's start to the moment the program said
"ready") and one `mark=end`. They are written when the session stops, so
they lie after `bench:window` and are looked for anywhere in the trace.

The reading is the sum of the `params.over` keys, times `params.scale`, of
the span whose `mark` is the first of `params.marks` that the trace holds
(a serve cell marks `sched.start`, a PPO cell `train.first_epoch`). A
program that writes no such span (a parent commit) gives nothing to read."""

from benchlib.files import load_module

SPAN = "trlx:build.total "


def totals_by_mark(trace) -> dict:
    """{mark: {key: text}} of the trace's `trlx:build.total` spans"""
    reduce = load_module("trace/reduce.py")
    by_mark = {}
    for name, _, _ in reduce.host_spans(trace):
        if name.startswith(SPAN):
            values = dict(kv.split("=", 1) for kv in name[len(SPAN):].split())
            by_mark[values["mark"]] = values
    return by_mark


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    by_mark = totals_by_mark(trace)
    mark = next((name for name in params["marks"] if name in by_mark), None)
    if mark is None:
        return None
    values = by_mark[mark]
    ctx.log(f"trlx:build.total at {mark} ({values['at_s']} s after the program's import): "
            + " ".join(f"{k}={values[k]}" for k in params["over"]))
    return float(params.get("scale", 1.0)) * sum(float(values[k]) for k in params["over"])
