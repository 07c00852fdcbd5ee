"""The forward grouped-product kernel's share of its roofline, in percent:
the least time the chip could take for the calls the trace holds over their
device time.

The expert layer's kernels carry names (`trlx_tpu/ops/moe.py`): the events
of the forward one on `XLA Ops` are `%moe_gmm.N = bf16[rows, d_out] ...`
(`params.kernel`; `moe_gmm_dlhs` and `moe_tgmm`, the backward's, are not
read here). An event's first result says how many dispatch rows the call's
buffer holds and how wide its output is; the other width, the experts and
the experts a token are the configuration file's. The buffer's rows are
static, 4 a position; the rows a call really multiplies are not in the
trace, so each call is priced at the rows expected of it
(`roofline_moe.expected_rows`): its positions' real share, from the cell's
traffic and recipe (a call over whole sequences of `seq_length`: mean prompt
+ new tokens of `seq_length`; the sampler's prefill over the padded prompt
width: mean prompt of it; a decode step: every row), times the share of
experts held. A share over 100% means the count is wrong, not the kernel
fast."""

import re

from benchlib import traffic
from benchlib.files import load_module, merge

BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def real_token_share(positions: int, ctx) -> float:
    """Of a call's `positions`, the share that are real tokens."""
    import numpy as np

    recipe = merge(ctx.cell["recipe"], ctx.cell.get("rehearse_recipe") if ctx.rehearse else None)
    mix = merge(ctx.traffic, ctx.traffic.get("rehearse") if ctx.rehearse else None)
    prompts = traffic.lengths(mix["prompt_len"], int(mix["pool"]), np.random.default_rng(0))
    width, new = int(mix["prompt_len"]["max"]), int(recipe["method"]["gen_kwargs"]["max_new_tokens"])
    if positions % (width + new) == 0:  # score, train: prompt + response, to the sequence's end
        return float(prompts.mean() + new) / (width + new)
    if positions % width == 0:  # the sampler's prefill
        return float(prompts.mean()) / width
    return 1.0  # a decode step: one position a row, rows run to max_new_tokens


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    roofline, moe = load_module("roofline.py"), load_module("roofline_moe.py")
    mine = re.compile(r"^%" + re.escape(params["kernel"]) + r"(\.\d+)? = ")
    events = reduce.events_matching(trace, reduce.OPS_LINE, lambda n: bool(mine.match(n)))
    if not events:
        return None
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    widths = {sizes[params["hidden_key"]], sizes[params["expert_width_key"]]}
    held, top_k = sizes[params["experts_held_key"]], sizes[params["top_k_key"]]
    experts = (ctx.config["rehearse_sizes"].get("router_width", held) if ctx.rehearse
               else ctx.config["published"][params["experts_held_key"]])
    least, kernel_s, by_rows = 0.0, 0.0, {}
    for name, _, d in events:
        shape = re.search(r"= \(?(\w+)\[(\d+),(\d+)\]", name)
        if shape is None or int(shape.group(3)) not in widths:
            raise ValueError(f"moe_roofline: {name[:100]!r} is no [rows, width] product of widths {widths}")
        dtype, static_rows, d_out = shape.group(1), int(shape.group(2)), int(shape.group(3))
        (d_in,) = widths - {d_out} or {d_out}
        rows = moe.expected_rows(static_rows, experts, held, real_token_share(static_rows // top_k, ctx))
        flops, nbytes = moe.grouped_matmul(rows, d_in, d_out, held, BYTES[dtype])
        least += roofline.least_seconds(flops, nbytes, ctx.peaks)[0]
        kernel_s += d / 1e9
        by_rows[static_rows] = by_rows.get(static_rows, 0) + 1
    ctx.log(f"{params['kernel']}: {len(events)} kernel events by static rows {dict(sorted(by_rows.items()))}, "
            f"{kernel_s:.4f} s on the device, least {least:.4f} s")
    return 100.0 * least / kernel_s
