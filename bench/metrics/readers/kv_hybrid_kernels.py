"""The kernels and the chunked recurrence of a model whose layers are softmax
attention over K/V by head (`gqa_layers`) or Kimi delta attention
(`linear_attn_config`) in the device trace. `params.what`:

- `kda_decode_roofline`: the `%<params.kernel>.N` events that start while a
  module `params.module*` runs, each one linear layer's decode step over the
  live rows (the mean `live=` of the `params.span` counter spans inside the
  traced window), priced by bench/roofline_linear.py `kda_decode` with the
  heads and the head width of `linear_attn_config` and the state's bytes from
  its stated precision: the least time the chip could take over their device
  time, in percent.
- `paged_roofline`: the `%<params.kernel>.N` events in `params.module*`, each
  the ONE call a step makes for a softmax layer (one layer in four caches),
  priced by bench/roofline_window.py `paged_decode_layer` at the positions
  resident (the job's `steps_resident_tokens`, the mean over the traced
  steps), `num_attention_heads` query heads over `num_key_value_heads` K/V
  heads of `head_dim`.
- `linear_prefill_share`: device seconds of the chunked recurrence inside
  `params.module*`, over the traced window, in percent. XLA keeps no name of
  a `jax.named_scope` in an event's name, so the form is told by what only it
  carries: it is a loop (`%while.N = (...) while(...)`) whose carried tuple
  holds a row's recurrent state, `f32[<rows>,<num_heads>,<head_dim>,
  <head_dim>]`; of nested loops that carry it (the scan over a span's chunks
  inside the scan over spans) the outermost counts, with everything inside
  it. A prompt of one span has no outer loop: its pair terms are then left
  out (the cell's prompts are 1,024 to 8,192, a span 1,024).

The sizes come from the configuration's published keys. A trace with no such
event (a parent commit, a model of another kind) gives nothing to read."""

import re

from benchlib.files import load_module

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _events(reduce, trace, match, module):
    """The `XLA Ops` events whose instruction `match` accepts, that start
    inside the traced window while a module `module*` runs."""
    module_at = reduce.module_at(trace)
    return [ev for ev in reduce.events_matching(trace, reduce.OPS_LINE, match)
            if (module_at(ev[1]) or "").startswith(module)]


def _kernel(name):
    mine = re.compile(r"^%" + re.escape(name) + r"(\.\d+)? = ")
    return lambda n: bool(mine.match(n))


def _share(ctx, label, events, seconds, bound):
    spent = sum(d for _, _, d in events) / 1e9
    ctx.log(f"{label}: {len(events)} events, {spent:.4f} device s; a call needs {seconds * 1e6:.1f} us "
            f"({bound}-bound), takes {spent / len(events) * 1e6:.1f}")
    return 100.0 * seconds * len(events) / spent


def read(m, params, ctx):
    trace = m.get("trace")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    if trace is None or "linear_attn_config" not in sizes or "gqa_layers" not in sizes:
        return None
    reduce = load_module("trace/reduce.py")
    roofline = load_module("roofline.py")
    linear = sizes["linear_attn_config"]
    heads, dim = int(linear["num_heads"]), int(linear["head_dim"])
    what = params["what"]

    if what == "kda_decode_roofline":
        events = _events(reduce, trace, _kernel(params["kernel"]), params["module"])
        lo, hi = reduce.window_of(trace)
        prefix = params["span"] + " "
        live = [float(dict(kv.split("=", 1) for kv in name[len(prefix):].split())["live"])
                for name, start, _ in reduce.host_spans(trace) if name.startswith(prefix) and lo <= start < hi]
        if not events or not live:
            return None
        rows = sum(live) / len(live)
        state = BYTES[ctx.config["precision"]["serve"]["recurrent_state"]]
        flops, nbytes = load_module("roofline_linear.py").kda_decode(rows, heads, dim, dim, state_bytes=state)
        return _share(ctx, f"{params['kernel']} over {rows:.1f} live rows, {heads} heads of {dim}", events,
                      *roofline.least_seconds(flops, nbytes, ctx.peaks))

    if what == "paged_roofline":
        calls = m.get("kernel_calls", {}).get("paged_decode")
        steps = [r for r in (calls or {}).get("steps_resident_tokens", ()) if r >= 0]
        events = _events(reduce, trace, _kernel(params["kernel"]), params["module"])
        if not events or not steps:
            return None
        positions, rows = sum(steps) / len(steps), m["constants"]["num_slots"]
        q_heads, kv_heads = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
        flops, nbytes = load_module("roofline_window.py").paged_decode_layer(
            positions, rows, q_heads, kv_heads, int(sizes["head_dim"]), calls["kv_bytes"])
        return _share(ctx, f"{params['kernel']} over {positions:.0f} positions resident, {q_heads} query heads "
                           f"over {kv_heads} K/V heads", events, *roofline.least_seconds(flops, nbytes, ctx.peaks))

    if what != "linear_prefill_share":
        raise ValueError(f"kv_hybrid_kernels: unknown params.what {what!r}")
    state = re.compile(rf"f32\[\d+,{heads},{dim},{dim}\]")  # among the results, in front of ` while(`
    carries = lambda n: n.startswith("%while") and bool(state.search(n.partition(" while(")[0]))
    loops = sorted(_events(reduce, trace, carries, params["module"]), key=lambda ev: ev[1])
    outer, end = [], -1
    for ev in loops:
        if ev[1] >= end:  # not inside the loop before it
            outer.append(ev)
            end = ev[1] + ev[2]
    if not outer:
        return None
    lo, hi = reduce.window_of(trace)
    seconds = sum(min(s + d, hi) - max(s, lo) for _, s, d in outer if s < hi and s + d > lo) / 1e9
    ctx.log(f"chunked recurrence in {params['module']}*: {len(outer)} outermost loops that carry a row's state "
            f"f32[.,{heads},{dim},{dim}] ({len(loops)} with those nested in them), {seconds:.4f} device s of a "
            f"{(hi - lo) / 1e9:.3f} s window")
    return 100.0 * seconds / ((hi - lo) / 1e9)
