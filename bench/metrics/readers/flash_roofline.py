"""The flash-attention forward kernels' share of their roofline, in percent:
the least time the chip could take for the calls the trace holds over their
device time.

The kernels carry names (`name=` on each `pl.pallas_call`), so their events
on `XLA Ops` are `%flash_fwd.N = ...` and `%flash_fwd_lse.N = ...`
(`params.kernel_prefix`), whichever program runs them: the sampler's
prefill, the scorer, the train step. Each event is named by its whole
instruction, whose first result is the attention output `[rows x heads,
sequence, head width]`: the calls are counted, and each priced by
bench/roofline.py `flash_fwd` (causal, keys as long as queries), from that
shape. What the configuration and the cell's recipe say of a call (heads and
hidden width, under whichever of `params.heads_keys` / `params.hidden_keys`
the published configuration uses; `recipe.train.seq_length`, or the
program's default where the recipe leaves it) is the check: an event whose
shape does not fit them is not the kernel the metric means, and the run
fails saying so."""

import re

from benchlib.files import load_module, merge

BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def expected_call(params, ctx):
    """Heads, head width and the longest sequence of a flash-attention call,
    from the configuration as it is run and the cell's recipe."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    heads = next(sizes[k] for k in params["heads_keys"] if k in sizes)
    hidden = next(sizes[k] for k in params["hidden_keys"] if k in sizes)
    recipe = merge(ctx.cell["recipe"], ctx.cell.get("rehearse_recipe") if ctx.rehearse else None)
    seq_length = recipe.get("train", {}).get("seq_length")
    if seq_length is None:  # the recipe overrides the program's default, as in jobs/ppo.py
        from trlx_tpu.data.default_configs import default_ppo_config

        seq_length = default_ppo_config().train.seq_length
    return {"heads": heads, "head_dim": hidden // heads, "seq_length": seq_length}


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    roofline = load_module("roofline.py")
    prefix = "%" + params["kernel_prefix"]
    events = reduce.events_matching(
        trace, reduce.OPS_LINE, lambda n: n.startswith(prefix) and " custom-call(" in n)
    if not events:
        return None
    want = expected_call(params, ctx)
    least, kernel_s, by_shape = 0.0, 0.0, {}
    for name, _, d in events:
        shape = re.search(r"= \(?(\w+)\[(\d+),(\d+),(\d+)\]", name)
        if shape is None:
            raise ValueError(f"flash_roofline: no [rows x heads, sequence, width] result in {name[:120]!r}")
        dtype, (rows_heads, t, hd) = shape.group(1), map(int, shape.group(2, 3, 4))
        if hd != want["head_dim"] or rows_heads % want["heads"] or t > want["seq_length"]:
            raise ValueError(f"flash_roofline: {name[:80]!r} is [{rows_heads}, {t}, {hd}], which "
                             f"does not fit the configuration and recipe {want}")
        flops, nbytes = roofline.flash_fwd(1, t, rows_heads, hd, BYTES[dtype])
        least += roofline.least_seconds(flops, nbytes, ctx.peaks)[0]
        kernel_s += d / 1e9
        by_shape[(rows_heads, t)] = by_shape.get((rows_heads, t), 0) + 1
    ctx.log(f"{params['kernel_prefix']}*: {len(events)} kernel events by (rows x heads, sequence) "
            f"{dict(sorted(by_shape.items()))}, {kernel_s:.4f} s on the device, least {least:.4f} s")
    return 100.0 * least / kernel_s
