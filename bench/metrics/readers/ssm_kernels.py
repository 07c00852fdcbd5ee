"""The kernels and the chunked recurrence of a model whose every layer runs a
Mamba-2 mixer beside softmax attention over K/V by head (the `mamba_*` keys of
its configuration file) in the device trace. `params.what`:

- `ssd_decode_roofline`: the `%<params.kernel>.N` events that start while a
  module `params.module*` runs, each one layer's decode step over the live
  rows (the mean `live=` of the `params.span` counter spans inside the traced
  window), priced by bench/roofline_ssm.py `ssd_decode` with the heads, head
  width, state width and groups of the configuration file and the state's
  bytes from its stated precision: the least time the chip could take over
  their device time, in percent.
- `paged_roofline`: the `%<params.kernel>.N` events in `params.module*`, each
  the call a step makes for ONE layer's attention (every layer caches), priced
  by bench/roofline_window.py `paged_decode_layer` at the positions resident
  (the job's `steps_resident_tokens`, the mean over the traced steps),
  `num_attention_heads` query heads over `num_key_value_heads` K/V heads of
  `head_dim`.
- `ssm_prefill_share`: device seconds of the chunked recurrence inside
  `params.prefill_module*`, over the traced window, in percent. It is plain
  XLA, and XLA keeps no name of a `jax.named_scope` in an event's name, so the
  form is told by what only it carries (as `kv_hybrid_kernels` tells KDA's): a
  loop (`%while.N = (...) while(...)`) whose carried tuple holds a row's
  recurrent state, `f32[<rows>,<groups>,<heads a group>,<d_state>,<d_head>]`
  (the scan over chunks of `ops/ssd.py:ssd_chunked`; `f32[<rows>,<heads>,
  <d_state>,<d_head>]` is read too, should a compiler fold the two head axes).
- `ssm_share`: that and the `%<params.kernel>*` events of the whole window
  together: the share of the device's time in SSD work, decode and prefill.

The readers of events, kernel names and shares are `kv_hybrid_kernels`'s. A
trace with no such event (a parent commit, a model of another kind) gives
nothing to read."""

import re

from benchlib.files import load_module

hybrid = load_module("metrics/readers/kv_hybrid_kernels.py")


def _seconds_in_window(events, lo, hi):
    return sum(min(s + d, hi) - max(s, lo) for _, s, d in events if s < hi and s + d > lo) / 1e9


def read(m, params, ctx):
    trace = m.get("trace")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    if trace is None or "mamba_n_heads" not in sizes:
        return None
    reduce = load_module("trace/reduce.py")
    roofline = load_module("roofline.py")
    heads, d_head, d_state = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_head"]), int(sizes["mamba_d_state"])
    groups = int(sizes["mamba_n_groups"])
    what = params["what"]

    if what == "ssd_decode_roofline":
        events = hybrid._events(reduce, trace, hybrid._kernel(params["kernel"]), params["module"])
        lo, hi = reduce.window_of(trace)
        prefix = params["span"] + " "
        live = [float(dict(kv.split("=", 1) for kv in name[len(prefix):].split())["live"])
                for name, start, _ in reduce.host_spans(trace) if name.startswith(prefix) and lo <= start < hi]
        if not events or not live:
            return None
        rows = sum(live) / len(live)
        state = hybrid.BYTES[ctx.config["precision"]["serve"]["recurrent_state"]]
        flops, nbytes = load_module("roofline_ssm.py").ssd_decode(rows, heads, d_state, d_head, groups,
                                                                  state_bytes=state)
        return hybrid._share(ctx, f"{params['kernel']} over {rows:.1f} live rows, {heads} heads of {d_state} x "
                                  f"{d_head}", events, *roofline.least_seconds(flops, nbytes, ctx.peaks))

    if what == "paged_roofline":
        calls = m.get("kernel_calls", {}).get("paged_decode")
        steps = [r for r in (calls or {}).get("steps_resident_tokens", ()) if r >= 0]
        events = hybrid._events(reduce, trace, hybrid._kernel(params["kernel"]), params["module"])
        if not events or not steps:
            return None
        positions, rows = sum(steps) / len(steps), m["constants"]["num_slots"]
        q_heads, kv_heads = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
        flops, nbytes = load_module("roofline_window.py").paged_decode_layer(
            positions, rows, q_heads, kv_heads, int(sizes["head_dim"]), calls["kv_bytes"])
        return hybrid._share(ctx, f"{params['kernel']} over {positions:.0f} positions resident, {q_heads} query "
                                  f"heads over {kv_heads} K/V heads", events,
                             *roofline.least_seconds(flops, nbytes, ctx.peaks))

    if what not in ("ssm_prefill_share", "ssm_share"):
        raise ValueError(f"ssm_kernels: unknown params.what {what!r}")
    state = re.compile(rf"f32\[\d+,(?:{groups},{heads // groups}|{heads}),{d_state},{d_head}\]")
    carries = lambda n: n.startswith("%while") and bool(state.search(n.partition(" while(")[0]))
    loops = hybrid._events(reduce, trace, carries, params["prefill_module"])
    lo, hi = reduce.window_of(trace)
    seconds = _seconds_in_window(loops, lo, hi)
    said = (f"chunked recurrence in {params['prefill_module']}*: {len(loops)} loops that carry a row's state "
            f"f32[.,{heads} heads,{d_state},{d_head}], {seconds:.4f} device s")
    if what == "ssm_share":
        prefix = "%" + params["kernel"]
        kernels = reduce.events_matching(trace, reduce.OPS_LINE, lambda n: n.startswith(prefix))
        decode = _seconds_in_window(kernels, lo, hi)
        said += f"; {len(kernels)} {params['kernel']}* events, {decode:.4f} device s"
        seconds, loops = seconds + decode, loops + kernels
    if not loops:
        return None
    ctx.log(f"{said} of a {(hi - lo) / 1e9:.3f} s window")
    return 100.0 * seconds / ((hi - lo) / 1e9)
