"""bench/trace/reduce.py on a trace small enough to reduce by hand, and on
the recorded fixture against an independent brute-force count.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from benchlib.files import load_module  # noqa: E402

reduce = load_module("trace/reduce.py")

# One device, one host thread, times in ns. By hand:
#   window 0..1000; ops busy on [100,300) U [250,400) U [600,700) = [100,400) U [600,700)
#   -> busy 400 ns, idle 600 ns (60%); gaps [0,100) [400,600) [700,1000)
#   kernel `paged_attn.3` ran 150 + 100 = 250 ns
#   module jit_decode covers [90,410), jit_insert [590,710)
#   host spans: bench:window [0,1000), bench:engine.step [80,450), bench:admit [450,720)
#   gap mid-points 50 -> (no span), 500 -> bench:admit, 850 -> (no span)
HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 100, 200], ["paged_attn.3", 250, 150],
                                       ["paged_attn.3", 600, 100]]},
        {"name": "XLA Modules", "events": [["jit_decode(123)", 90, 320],
                                           ["jit_insert(456)", 590, 120]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench:window", 0, 1000], ["bench:engine.step", 80, 370],
                                      ["bench:admit", 450, 270]]}]},
]}


def test_busy_idle_by_hand():
    b = reduce.busy(HAND)
    assert b["window"] == (0, 1000)
    assert b["busy_s"] == pytest.approx(400e-9)
    assert b["window_s"] == pytest.approx(1000e-9)
    assert b["intervals"][0] == [[100, 400], [600, 700]]


def test_kernel_and_module_time_by_hand():
    ops = reduce.seconds_by_name(HAND, reduce.OPS_LINE)
    assert ops["paged_attn.3"] == pytest.approx(250e-9)
    assert ops["fusion.1"] == pytest.approx(200e-9)
    mods = reduce.seconds_by_name(HAND, reduce.MODULES_LINE)
    assert mods["jit_decode(123)"] == pytest.approx(320e-9)
    events = reduce.events_matching(HAND, reduce.OPS_LINE, lambda n: "paged" in n)
    assert [(s, d) for _, s, d in events] == [(250, 150), (600, 100)]
    at = reduce.module_at(HAND)
    assert at(250).startswith("jit_decode") and at(600).startswith("jit_insert") and at(500) is None


def test_idle_gaps_by_hand():
    gaps = reduce.idle_gaps_by_span(HAND, min_gap_ns=1)
    assert gaps == {"(no span)": pytest.approx(400e-9), "bench:admit": pytest.approx(200e-9)}
    s = reduce.summary(HAND)
    assert s["breakdown"]["device_ops"][0] == ["paged_attn.3", pytest.approx(250e-9)]


def test_containers_and_names():
    """A `while` spans its body: the leaves add up, the container is left
    out; a long HLO name keeps its result and operation."""
    events = [["%while.1 = (s32[]) while(s32[] %x), body=%b", 0, 100],
              ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 30],
              ['%flash.3 = bf16[8]{0} custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"', 50, 40]]
    assert [e[0][:8] for e in reduce.leaves(events)] == ["%fusion.", "%flash.3"]
    assert reduce.short_name(events[1][0]) == "%fusion.2 = fusion"
    assert reduce.short_name(events[2][0]) == "%flash.3 = custom-call [tpu_custom_call]"


def test_window_clips_events():
    b = reduce.busy(HAND, window=(200, 650))
    assert b["busy_s"] == pytest.approx((200 + 50) * 1e-9)


FIXTURE = os.path.join(BENCH, "trace", "fixture_v5e_serve.json")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded fixture")
def test_recorded_fixture_against_brute_force():
    """A few milliseconds cut from a traced run of a serve cell on the v5e
    (bench/trace/README in the fixture's `note`). Busy time, the paged
    kernel's time and the idle seconds by span are recomputed here one
    nanosecond at a time, with nothing shared with reduce.py."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    trace = fx["trace"]
    lo, hi = reduce.window_of(trace)
    ops = next(ln["events"] for p in trace["planes"] if p["name"].startswith("/device:TPU:")
               for ln in p["lines"] if ln["name"] == "XLA Ops")
    grid = np.zeros(hi - lo, bool)
    for _, s, d in ops:
        grid[max(s - lo, 0):max(min(s + d, hi) - lo, 0)] = True
    b = reduce.busy(trace)
    assert b["busy_s"] * 1e9 == pytest.approx(int(grid.sum()), abs=1)
    kernel_ns = sum(d for n, s, d in ops if fx["kernel_match"] in n and lo <= s < hi)
    events = reduce.events_matching(trace, reduce.OPS_LINE, lambda n: fx["kernel_match"] in n)
    assert sum(d for _, _, d in events) == kernel_ns and kernel_ns > 0
    # idle by span: label every idle nanosecond's gap by its mid-point
    gaps = reduce.idle_gaps_by_span(trace, min_gap_ns=1)
    assert sum(gaps.values()) * 1e9 == pytest.approx(int((~grid).sum()), abs=len(gaps) + 1)
    assert fx["expect_span"] in gaps
