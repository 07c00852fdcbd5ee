"""A looped stack's decode step against ITS roofline, in percent: the least
time the chip could take for one step (bench/roofline_loop.py `decode_step`
on the configuration file's published sizes, the rows the job serves and the
key positions resident in the traced steps, against bench/peaks.json) over
the device time of one step (the mean of the whole `params.module*` module
events inside the traced window: one event a call of the decode program).

The bound counts every byte a step cannot avoid and nothing else, so a share
over 100% means the count is wrong. A configuration that names no
`total_ut_steps`, or a trace with no such module event, gives nothing to read."""

from benchlib.files import load_module


def read(m, params, ctx):
    trace = m.get("trace")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    calls = m.get("kernel_calls", {}).get("paged_decode") or {}
    steps = [r for r in calls.get("steps_resident_tokens", ()) if r >= 0]
    if trace is None or "total_ut_steps" not in sizes or not steps:
        return None
    reduce = load_module("trace/reduce.py")
    planes = reduce.device_planes(trace)
    if not planes:
        return None
    lo, hi = reduce.window_of(trace)
    modules = [d for n, s, d in next((ln["events"] for ln in planes[0]["lines"] if ln["name"] == reduce.MODULES_LINE), [])
               if n.startswith(params["module"]) and lo <= s and s + d <= hi]
    if not modules:
        return None
    step_s = sum(modules) / len(modules) / 1e9
    resident, rows = sum(steps) / len(steps), m["constants"]["num_slots"]
    precision = ctx.config["precision"]["serve"]
    nbytes_of = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
    flops, nbytes = load_module("roofline_loop.py").decode_step(
        resident, rows, sizes, nbytes_of[precision["weights"]], calls.get("kv_bytes", nbytes_of[precision["kv_cache"]]),
        nbytes_of[precision["compute"]])
    least, bound = load_module("roofline.py").least_seconds(flops, nbytes, ctx.peaks)
    ctx.log(f"{params['module']}*: {len(modules)} whole steps, {1e3 * step_s:.3f} ms each on the device; a step of "
            f"{rows} rows over {resident:.0f} resident positions needs {nbytes / 1e9:.3f} GB and "
            f"{flops / 1e9:.1f} GFLOP: least {1e3 * least:.3f} ms, bound by {bound}")
    return 100.0 * least / step_s
