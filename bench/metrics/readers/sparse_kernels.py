"""The kernels of a stack whose latent layers are banded or choose their
positions by a learned index (dots3-note), in the device trace, by the
`name=` of their calls: `paged_index_scores` and `paged_decode_latent_window`
in a decode step, `sparse_index_scores`, `sparse_latent_fwd` and
`flash_fwd_latent_window` in a prefill. `params.what` says which number:

- `index_roofline`: the `%paged_index_scores.N` events that start while a
  module `params.module*` runs, priced by bench/roofline_sparse.py
  `paged_index_scores` at the positions resident (the job's
  `steps_resident_tokens`, the mean over the traced steps): the least time
  the chip could take over their device time, in percent.
- `window_roofline`: the same for `%paged_decode_latent_window.N`, priced by
  `paged_decode_latent_window` over the positions inside the band.
- `share`: device seconds of the events named by `params.kernels` (a Pallas
  call's `name=`), over the traced window, in percent. (The choice itself and a
  decode step's gather of the chosen latents are XLA operations: the trace's
  instruction text keeps no `op_name`, so their `jax.named_scope`s cannot be
  read and they are in neither share: PERF.md section 7.)

The sizes come from the configuration's published keys. A trace with no such
event (a parent commit, a model of another kind) gives nothing to read."""

import re

from benchlib.files import load_module


def _events(reduce, trace, kernel):
    mine = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? = ")
    return reduce.events_matching(trace, reduce.OPS_LINE, lambda n: bool(mine.match(n)))


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    if "index_topk" not in sizes:
        return None

    if params["what"] == "share":
        lo, hi = reduce.window_of(trace)
        seconds = {k: sum(d for _, _, d in _events(reduce, trace, k)) / 1e9 for k in params["kernels"]}
        if not any(seconds.values()):
            return None
        ctx.log(f"{params['kernels']}: device seconds { {k: round(v, 4) for k, v in seconds.items()} } "
                f"of a {(hi - lo) / 1e9:.3f} s window")
        return 100.0 * sum(seconds.values()) / ((hi - lo) / 1e9)

    roofline, sparse = load_module("roofline.py"), load_module("roofline_sparse.py")
    calls = m.get("kernel_calls", {}).get("paged_decode")
    steps = [r for r in (calls or {}).get("steps_resident_tokens", ()) if r >= 0]
    module_at = reduce.module_at(trace)
    events = [ev for ev in _events(reduce, trace, params["kernel"])
              if (module_at(ev[1]) or "").startswith(params["module"])]
    if not events or not steps:
        return None
    positions, rows = sum(steps) / len(steps), m["constants"]["num_slots"]
    if params["what"] == "index_roofline":
        flops, nbytes = sparse.paged_index_scores(positions, rows, sizes["index_n_heads"], sizes["index_head_dim"],
                                                  calls["kv_bytes"])
    elif params["what"] == "window_roofline":
        values = sizes["swa_kv_lora_rank"]
        flops, nbytes = sparse.paged_decode_latent_window(
            positions, rows, sizes["sliding_window_size"], sizes["swa_num_attention_heads"],
            values + sizes["swa_qk_rope_head_dim"], values, calls["kv_bytes"])
    else:
        raise ValueError(f"sparse_kernels: unknown params.what {params['what']!r}")
    seconds, bound = roofline.least_seconds(flops, nbytes, ctx.peaks)
    spent = sum(d for _, _, d in events) / 1e9
    ctx.log(f"{params['kernel']}: {len(events)} events, {spent:.4f} device s; a call over "
            f"{positions:.0f} positions resident needs {seconds * 1e6:.1f} us ({bound}-bound), "
            f"takes {spent / len(events) * 1e6:.1f}")
    return 100.0 * seconds * len(events) / spent
