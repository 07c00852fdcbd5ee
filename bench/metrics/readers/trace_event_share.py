"""Device seconds of the `XLA Ops` events whose instruction name starts
with `params.name_prefix` (the Pallas kernels of one layer, under the
`name=` of their calls), over the traced window, in percent. Nothing to
read where the trace holds no such event."""

from benchlib.files import load_module


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    prefix = "%" + params["name_prefix"]
    events = reduce.events_matching(trace, reduce.OPS_LINE, lambda n: n.startswith(prefix))
    if not events:
        return None
    lo, hi = reduce.window_of(trace)
    by_kernel = {}
    for name, _, d in events:
        kernel = name.split(" ", 1)[0].rsplit(".", 1)[0]
        by_kernel[kernel] = by_kernel.get(kernel, 0.0) + d / 1e9
    ctx.log(f"{params['name_prefix']}*: {len(events)} events, seconds by kernel "
            f"{ {k: round(v, 4) for k, v in sorted(by_kernel.items())} } of a {(hi - lo) / 1e9:.3f} s window")
    return 100.0 * sum(by_kernel.values()) / ((hi - lo) / 1e9)
