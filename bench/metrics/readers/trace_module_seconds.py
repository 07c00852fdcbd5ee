"""Device seconds of the XLA modules whose name starts with
`params.module_prefix`, per traced cycle."""

from benchlib.files import load_module


def read(m, params, ctx):
    if m.get("trace") is None:
        return None
    reduce = load_module("trace/reduce.py")
    by_name = reduce.seconds_by_name(m["trace"], reduce.MODULES_LINE)
    hits = {n: s for n, s in by_name.items() if n.startswith(params["module_prefix"])}
    if not hits:
        return None
    return sum(hits.values()) / max(int(m.get("cycles_traced", 1)), 1)
