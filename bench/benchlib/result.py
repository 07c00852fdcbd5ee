"""The result line and the small statistics the jobs share."""

import json
import math
import sys


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation, all digits."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Checks:
    """Every number compared beside its limit, printed as it is taken;
    `ok` is the conjunction."""

    def __init__(self):
        self.ok = True
        self.lines = []

    def at_most(self, what: str, value: float, limit: float):
        good = bool(value <= limit) and math.isfinite(value)
        self._row(what, value, f"<= {limit}", good)

    def equal(self, what: str, value, want):
        self._row(what, value, f"== {want}", value == want)

    def true(self, what: str, cond: bool):
        self._row(what, bool(cond), "is true", bool(cond))

    def _row(self, what, value, limit, good):
        self.ok = self.ok and good
        self.lines.append(f"[bench] check {what}: {value} (limit {limit}) "
                          f"{'ok' if good else 'NOT CORRECT'}")
        print(self.lines[-1], flush=True)

    def to_stderr(self):
        """Every row again, as the last lines of standard error: of a run that
        is not correct the driver's record keeps the end of that."""
        print("\n".join(self.lines), file=sys.stderr, flush=True)


def print_result(correct, attempted, failed, metrics, units, device, breakdown=None):
    """The one JSON object, last on standard output."""
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
