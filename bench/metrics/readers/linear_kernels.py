"""The kernels of a linear-attention (Kimi delta attention) model in the
device trace, by the `name=` of their calls. `params.what`:

- `decode_roofline`: the `%<params.kernel>.N` events that start while a module
  `params.module*` runs, each one layer's decode step over the live rows, priced
  by bench/roofline_linear.py `kda_decode`: the least time the chip could take
  over their device time, in percent. The live rows are the program's own
  count, the mean `live=` of the `params.span` counter spans inside the traced
  window (`trlx:engine.slot_state`, one in front of every decode dispatch);
  heads and the head width come from the configuration's published keys, the
  state's bytes from its stated precision.

A trace with no such event or no such span (a parent commit, a model of
another kind) gives nothing to read."""

import re

from benchlib.files import load_module

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    if params["what"] != "decode_roofline":
        raise ValueError(f"linear_kernels: unknown params.what {params['what']!r}")
    reduce = load_module("trace/reduce.py")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    precision = ctx.config.get("precision", {}).get("serve", {})
    if "short_conv_kernel_size" not in sizes or "recurrent_state" not in precision:
        return None
    mine = re.compile(r"^%" + re.escape(params["kernel"]) + r"(\.\d+)? = ")
    module_at = reduce.module_at(trace)
    events = [ev for ev in reduce.events_matching(trace, reduce.OPS_LINE, lambda n: bool(mine.match(n)))
              if (module_at(ev[1]) or "").startswith(params["module"])]
    lo, hi = reduce.window_of(trace)
    prefix = params["span"] + " "
    live = [float(dict(kv.split("=", 1) for kv in name[len(prefix):].split())["live"])
            for name, start, _ in reduce.host_spans(trace) if name.startswith(prefix) and lo <= start < hi]
    if not events or not live:
        return None
    rows = sum(live) / len(live)
    heads, dim = int(sizes["num_attention_heads"]), int(sizes["head_dim"])
    flops, nbytes = load_module("roofline_linear.py").kda_decode(
        rows, heads, dim, dim, state_bytes=BYTES[precision["recurrent_state"]])
    seconds, bound = load_module("roofline.py").least_seconds(flops, nbytes, ctx.peaks)
    spent = sum(d for _, _, d in events) / 1e9
    ctx.log(f"{params['kernel']}: {len(events)} events, {spent:.4f} device s; a call over {rows:.1f} live rows "
            f"needs {seconds * 1e6:.1f} us ({bound}-bound), takes {spent / len(events) * 1e6:.1f}")
    return 100.0 * seconds * len(events) / spent
