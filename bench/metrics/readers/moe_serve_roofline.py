"""The forward grouped-product kernel's share of its roofline in SERVING, in
percent: the least time the chip could take for the calls the trace holds
over their device time (the accepted `moe_roofline` prices a PPO recipe's
calls, by rows expected under a share of the experts held).

Here every expert is held, so every assignment of a real token is a real
row. The events on `XLA Ops` are `%moe_gmm.N = bf16[rows, d_out] ...`
(`params.kernel`); an event's first result says how many dispatch rows the
call's buffer holds (padded to the kernel's row tile) and how wide its output
is. A call inside a module `params.decode_module*` is a decode step's: its
real rows are the live rows x the experts a token (the job's `num_slots`; a
backlog keeps every slot busy), and its bytes are priced at the experts the
step MET, not at all that are held, so that a perfect kernel reads 100: the
mean of `experts_met` in the program's `params.met_span` counters inside the
traced window, or, where the program writes none, what a router that favours
none is expected to meet (`experts_met_expected`). Every other call is a
prefill block's: its real rows are the buffer's times the share of the
window's admitted positions that were prompt (`prompt_tokens` over
`padded_tokens` of `params.insert_span`; a block's own share is not in the
trace, so the window's is applied to each, exact in the sum where the calls
are compute-bound), its bytes at the experts that many tokens are expected to
meet. Operations and bytes are bench/roofline_moe.py's `grouped_matmul`. A
share over 100% means the count is wrong, not the kernel fast."""

import re

from benchlib.files import load_module

BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def experts_met_expected(tokens: float, experts: int, top_k: int) -> float:
    """Experts with at least one row when `tokens` tokens each choose `top_k`
    different ones of `experts` with equal chances."""
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def counter_sums(reduce, trace, span: str, keys):
    """The sums of `keys` over the counter spans `span` inside the traced window, and their number."""
    lo, hi = reduce.window_of(trace)
    sums, n = dict.fromkeys(keys, 0.0), 0
    for name, start, _ in reduce.host_spans(trace):
        if name.startswith(span + " ") and lo <= start < hi:
            values = dict(kv.split("=", 1) for kv in name[len(span) + 1:].split())
            if all(k in values for k in keys):
                n += 1
                for k in keys:
                    sums[k] += float(values[k])
    return sums, n


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
    experts, top_k = sizes[params["experts_key"]], sizes[params["top_k_key"]]
    step_rows = m["constants"]["num_slots"] * top_k
    met, n_met = counter_sums(reduce, trace, params["met_span"], ("experts_met",))
    step_met = met["experts_met"] / n_met if n_met else experts_met_expected(step_rows / top_k, experts, top_k)
    admitted, n_admitted = counter_sums(reduce, trace, params["insert_span"], ("prompt_tokens", "padded_tokens"))
    prompt_share = admitted["prompt_tokens"] / admitted["padded_tokens"] if admitted["padded_tokens"] else 1.0
    module_at = reduce.module_at(trace)
    least, kernel_s, seen = 0.0, 0.0, {}
    for name, start, d in events:
        shape = re.search(r"= \(?(\w+)\[(\d+),(\d+)\]", name)
        if shape is None or int(shape.group(3)) not in widths:
            raise ValueError(f"moe_serve_roofline: {name[:100]!r} is no [rows, width] product of widths {widths}")
        dtype, static_rows, d_out = shape.group(1), int(shape.group(2)), int(shape.group(3))
        (d_in,) = widths - {d_out} or {d_out}
        if (module_at(start) or "").startswith(params["decode_module"]):
            kind, rows, held = "decode", min(step_rows, static_rows), step_met
        else:
            kind, rows = "prefill", static_rows * prompt_share
            held = experts_met_expected(rows / top_k, experts, top_k)
        flops, nbytes = moe.grouped_matmul(rows, d_in, d_out, held, BYTES[dtype])
        seconds, bound = roofline.least_seconds(flops, nbytes, ctx.peaks)
        least += seconds
        kernel_s += d / 1e9
        n, spent, floor = seen.get((kind, static_rows, d_out, bound), (0, 0.0, 0.0))
        seen[(kind, static_rows, d_out, bound)] = (n + 1, spent + d / 1e9, floor + seconds)
    ctx.log(f"{params['kernel']} in serving: decode steps priced at {step_rows} rows over {step_met:.2f} experts met "
            f"({n_met} `{params['met_span']}` spans; {experts} held), prefill blocks at {100 * prompt_share:.1f}% of "
            f"their rows ({n_admitted} `{params['insert_span']}` spans); by (kind, buffer rows, output width, bound): "
            f"(events, device s, least s) { {k: (n, round(a, 4), round(b, 4)) for k, (n, a, b) in sorted(seen.items())} }")
    return 100.0 * least / kernel_s
