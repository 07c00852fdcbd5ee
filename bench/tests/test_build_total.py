"""bench/metrics/readers/build_total.py over a trace made by hand: the
program's `trlx:build.total` counter spans (two marks and the `end`), the
five metric files that read them, and a parent's trace that holds none.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_build_total.py -q
"""

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

from benchlib.files import load_json, load_module  # noqa: E402

reader = load_module("metrics/readers/build_total.py")
MS = 1_000_000  # ns
METRICS = ("build.trace_lower_s", "build.backend_s", "build.cache_read_s", "build.cache_misses",
           "build.programs")


def total(mark, builds, trace_s, lower_s, backend_s, misses, read_s, at_s):
    return (f"trlx:build.total mark={mark} programs={builds - 1} builds={builds} trace_s={trace_s} "
            f"lower_s={lower_s} backend_s={backend_s} cache_hits={builds - misses} "
            f"cache_misses={misses} cache_read_s={read_s} saved_s=1e-05 at_s={at_s}")


def trace_with(*names):
    """A traced window of 100 ms on one device, and the given empty spans
    AFTER it, where `tracing.stop()` writes them."""
    spans = [["bench:window", 0, 100 * MS]] + [[n, (101 + i) * MS, 0] for i, n in enumerate(names)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [["%fusion.1 = fusion()", 5 * MS, 10 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": spans}]},
    ]}


# the process was ready at sched.start, trained (which no serve cell does) later, stopped at 200 s
SPANS = [
    total("sched.start", 30, 41.5, 12.25, 6.5, 0, 5.75, 80.0),
    total("train.first_epoch", 40, 50.0, 15.0, 9.0, 2, 6.0, 120.0),
    total("end", 44, 51.0, 15.5, 9.5, 3, 6.25, 200.0),
    "trlx:build.program name=decode builds=1 trace_s=2.8 lower_s=1.0 backend_s=1.1 first_at_s=20.0 last_at_s=24.9",
]
BOTH = trace_with(*SPANS)


def read(trace, over, marks=("sched.start", "train.first_epoch"), **params):
    lines = []
    got = reader.read({"trace": trace}, {"marks": list(marks), "over": over, **params},
                      types.SimpleNamespace(log=lines.append))
    return got, lines


def test_the_first_mark_of_the_list_that_the_trace_holds_is_read():
    assert read(BOTH, ["trace_s", "lower_s"])[0] == pytest.approx(41.5 + 12.25)
    # a PPO cell's trace holds no `sched.start`
    ppo = trace_with(*SPANS[1:])
    assert read(ppo, ["trace_s", "lower_s"])[0] == pytest.approx(50.0 + 15.0)
    assert read(BOTH, ["backend_s"], marks=("end",))[0] == pytest.approx(9.5)
    got, lines = read(BOTH, ["builds"], scale=0.5)
    assert got == pytest.approx(15.0)
    assert lines == ["trlx:build.total at sched.start (80.0 s after the program's import): builds=30"]


def test_a_trace_without_the_span_or_without_the_marks_gives_nothing_to_read():
    assert read(trace_with("trlx:engine.step"), ["backend_s"])[0] is None
    assert read(BOTH, ["backend_s"], marks=("server.ready",))[0] is None
    assert reader.read({}, {"marks": ["end"], "over": ["backend_s"]}, None) is None


@pytest.mark.parametrize("name,want", zip(METRICS, (53.75, 6.5, 5.75, 0.0, 30.0)))
def test_the_metric_files_read_the_marks_in_every_cell(name, want):
    spec = load_json(f"metrics/{name}.json")
    assert spec["reader"] == "build_total" and spec["moves"] == "setup_s"
    assert spec["params"]["marks"] == ["sched.start", "train.first_epoch"]
    got = reader.read({"trace": BOTH}, spec["params"], types.SimpleNamespace(log=lambda msg: None))
    assert got == pytest.approx(want)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == \
        {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
