"""1 - (union of the intervals in which an operation ran on the device) /
(the traced window), in percent, averaged over the chips used."""

from benchlib.files import load_module


def read(m, params, ctx):
    if m.get("trace") is None:
        return None
    b = load_module("trace/reduce.py").busy(m["trace"])
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
