"""A kernel's share of its roofline, in percent: the least time the chip
could take for the calls the trace holds (bench/roofline.py on the shapes
the job gave, against bench/peaks.json) over the kernel's device time.

Today's Pallas kernels carry no name of their own in the trace: each is an
`XLA Ops` event `%<scope>.N = ... custom-call(...),
custom_call_target="tpu_custom_call"`. So a kernel can be told only where it
is the one Pallas kernel of its program: the events are the custom calls to
`params.target` that start while a module named `params.module*` runs. The
paged decode kernel is the decode program's only one. The steps inside the
traced window are priced by the tokens then resident, one call per layer per
step."""

from benchlib.files import load_module


def read(m, params, ctx):
    calls = m.get("kernel_calls", {}).get(params["kernel"])
    if m.get("trace") is None or not calls:
        return None
    reduce = load_module("trace/reduce.py")
    roofline = load_module("roofline.py")
    trace = m["trace"]
    module_at = reduce.module_at(trace)
    target = f'custom_call_target="{params["target"]}"'
    events = [ev for ev in reduce.events_matching(
        trace, reduce.OPS_LINE, lambda n: " custom-call(" in n and target in n)
        if (module_at(ev[1]) or "").startswith(params["module"])]
    steps = [r for r in calls["steps_resident_tokens"] if r >= 0]
    if not events or not steps:
        return None
    kernel_s = sum(d for _, _, d in events) / 1e9
    per_step, bounds = 0.0, {}
    for resident in steps:
        f, b = getattr(roofline, params["kernel"])(
            resident, m["constants"]["num_slots"], calls["heads"], calls["kv_heads"],
            calls["head_dim"], calls["kv_bytes"])
        s, bound = roofline.least_seconds(f, b, ctx.peaks)
        per_step += s * calls["layers"]
        bounds[bound] = bounds.get(bound, 0) + 1
    # the window's edges cut a step: price the events seen at the mean of the
    # steps wholly inside
    least = per_step / (len(steps) * calls["layers"]) * len(events)
    ctx.log(f"{params['kernel']}: {len(events)} kernel events, {kernel_s:.4f} s on the device, "
            f"least {least:.4f} s, bound by {bounds}")
    return 100.0 * least / kernel_s
