"""The two kernels of a latent-attention (MLA) model in the device trace, by
the `name=` of their calls: `paged_decode_latent` (the absorbed decode over
the paged latent arena) and `flash_fwd_latent` (the prefill's fused forward
over decompressed keys and values, value heads narrower than query/key
heads). `params.what` says which number:

- `paged_roofline`: the `%<params.kernel>.N` events that start while a module
  `params.module*` runs, each priced by bench/roofline_latent.py
  `paged_decode_latent` at the positions resident (the job's
  `steps_resident_tokens`, the mean over the traced steps): the least time
  the chip could take over their device time, in percent.
- `flash_roofline`: the `%<params.kernel>.N` events, each priced from its own
  instruction (its first result is the output, `[rows x heads, sequence,
  value width]`) by `flash_fwd_latent`; the widths have to be the
  configuration's, or the run fails saying so.
- `share`: device seconds of all `params.kernels` over the traced window, in
  percent.

The sizes come from the configuration's published keys. A trace with no such
event (a parent commit, a model of another kind) gives nothing to read."""

import re

from benchlib.files import load_module

BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _events(reduce, trace, kernel):
    mine = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? = ")
    return reduce.events_matching(trace, reduce.OPS_LINE, lambda n: bool(mine.match(n)))


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    roofline, latent = load_module("roofline.py"), load_module("roofline_latent.py")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    if "kv_lora_rank" not in sizes:
        return None
    heads, values = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    width = values + sizes["qk_rope_head_dim"]

    if params["what"] == "share":
        lo, hi = reduce.window_of(trace)
        seconds = {k: sum(d for _, _, d in _events(reduce, trace, k)) / 1e9 for k in params["kernels"]}
        if not any(seconds.values()):
            return None
        ctx.log(f"latent attention kernels, device seconds { {k: round(v, 4) for k, v in seconds.items()} } "
                f"of a {(hi - lo) / 1e9:.3f} s window")
        return 100.0 * sum(seconds.values()) / ((hi - lo) / 1e9)

    if params["what"] == "paged_roofline":
        calls = m.get("kernel_calls", {}).get("paged_decode")
        steps = [r for r in (calls or {}).get("steps_resident_tokens", ()) if r >= 0]
        module_at = reduce.module_at(trace)
        events = [ev for ev in _events(reduce, trace, params["kernel"])
                  if (module_at(ev[1]) or "").startswith(params["module"])]
        if not events or not steps:
            return None
        positions, rows = sum(steps) / len(steps), m["constants"]["num_slots"]
        flops, nbytes = latent.paged_decode_latent(positions, rows, heads, width, values, calls["kv_bytes"])
        seconds, bound = roofline.least_seconds(flops, nbytes, ctx.peaks)
        spent = sum(d for _, _, d in events) / 1e9
        ctx.log(f"{params['kernel']}: {len(events)} events, {spent:.4f} device s; a call over "
                f"{positions:.0f} positions resident needs {seconds * 1e6:.1f} us ({bound}-bound), "
                f"takes {spent / len(events) * 1e6:.1f}")
        return 100.0 * seconds * len(events) / spent

    if params["what"] != "flash_roofline":
        raise ValueError(f"latent_kernels: unknown params.what {params['what']!r}")
    qk_dim, v_dim = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    least, kernel_s, seen = 0.0, 0.0, {}
    for text, _, d in _events(reduce, trace, params["kernel"]):
        shape = re.search(r"= \(?(\w+)\[(\d+),(\d+),(\d+)\]", text)
        if shape is None:
            raise ValueError(f"latent_kernels: no [rows x heads, sequence, width] result in {text[:120]!r}")
        dtype, (rows_heads, t, hv) = shape.group(1), map(int, shape.group(2, 3, 4))
        if hv != v_dim or rows_heads % heads:
            raise ValueError(f"latent_kernels: {text[:80]!r} is [{rows_heads}, {t}, {hv}], which does not fit "
                             f"{heads} heads with values of {v_dim}")
        seconds = roofline.least_seconds(*latent.flash_fwd_latent(rows_heads, t, qk_dim, v_dim, BYTES[dtype]),
                                         ctx.peaks)[0]
        least += seconds
        kernel_s += d / 1e9
        n, spent, floor = seen.get((rows_heads, t), (0, 0.0, 0.0))
        seen[(rows_heads, t)] = (n + 1, spent + d / 1e9, floor + seconds)
    if not seen:
        return None
    ctx.log(f"{params['kernel']} by (rows x heads, sequence): (events, device s, least s) "
            f"{ {k: (n, round(a, 4), round(b, 4)) for k, (n, a, b) in sorted(seen.items())} }")
    return 100.0 * least / kernel_s
