"""Device time of the XLA modules whose name starts with
`params.module_prefix` (the programs `jit_<function>`), inside the traced
window; with `params.op`, of the leaf operations of that kind (`copy`) that
ran inside them instead. `params.per`:

- `window`: over the traced window, in percent;
- `module_event`: over the number of those module events (one a call of the
  program: a decode step), in ms; events cut by the window's edges are left
  out, with the operations inside them."""

import bisect
import re

from benchlib.files import load_module


def operation(name: str) -> str:
    """The HLO operation of an `XLA Ops` event, which is named by its whole
    instruction: `%copy.7 = bf16[8,128]{1,0:T(8,128)} copy(%p)` -> `copy`."""
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + name.partition(" = ")[2])
    return m.group(1) if m else ""


def read(m, params, ctx):
    trace = m.get("trace")
    if trace is None:
        return None
    reduce = load_module("trace/reduce.py")
    planes = reduce.device_planes(trace)
    if not planes:
        return None
    lo, hi = reduce.window_of(trace)
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    modules = sorted((s, s + d) for n, s, d in lines.get(reduce.MODULES_LINE, [])
                     if n.startswith(params["module_prefix"]) and lo <= s and s + d <= hi)
    if not modules:
        return None
    if "op" in params:
        starts = [s for s, _ in modules]

        def inside_a_module(ns):
            i = bisect.bisect_right(starts, ns) - 1
            return i >= 0 and ns < modules[i][1]

        ops = [(n, s, d) for n, s, d in reduce.leaves(lines.get(reduce.OPS_LINE, []))
               if inside_a_module(s) and operation(n) == params["op"]]
        seconds = sum(d for _, _, d in ops) / 1e9
        ctx.log(f"{params['module_prefix']}: {len(ops)} `{params['op']}` operations in "
                f"{len(modules)} module events, {seconds:.4f} s on the device")
    else:
        seconds = sum(e - s for s, e in modules) / 1e9
    if params["per"] == "window":
        return 100.0 * seconds / ((hi - lo) / 1e9)
    if params["per"] == "module_event":
        return 1e3 * seconds / len(modules)
    raise ValueError(f"trace_module_ops: unknown per {params['per']!r}")
