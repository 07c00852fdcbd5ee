"""bench/metrics/readers/step_pipeline.py on a trace built by hand
(bench/trace/fixture_step_pipeline.json), and the data-file metrics that read
the same trace through readers that were there.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_step_pipeline.py -q

The fixture, in ms (one device, the scheduler's thread, the window 10.5..62):

    seq  queued+dispatch   device event   fetch span      fetched
    100  (before the trace)   0 .. 10      1.5 .. 10.3
    101   1.0 ..  1.5        10 .. 20     12.4 .. 20.2
    102  12.0 .. 12.4        20 .. 30     30.02 .. 30.1   (began after the device had finished)
    103  23.2 .. 23.7        30 .. 40     33.0 .. 40.4
         an admission: `trlx:sched.insert` at 30.95, jit_insert 40 .. 44
    104  32.6 .. 33.0        44 .. 54     43.5 .. 54.3
    105  43.0 .. 43.5        54 .. 64     57.4 .. 64.2    (both cut by the window's end)
    106  57.0 .. 57.4        64 .. 74     (after the trace)

By hand: slack = device start - dispatch end: 7.6 (102), 6.3 (103), 11.0 (104),
median 7.6; host loop = fetch end to the next dispatch's end: 20.2 -> 23.7,
30.1 -> 33.0, 40.4 -> 43.5, 54.3 -> 57.4 = 3.5, 2.9, 3.1, 3.1, mean 3.15;
fetch-late = fetch end - device end over the fetches that waited: 0.4 (103),
0.3 (104), median 0.35 (102 did not wait; 101's event began before the
window). For k = 102: slack(104) 11.0 + late 0.1 + loop 2.9 = 14.0 = 44 - 30,
in which the device ran step 103 (10) and the admission (4).
"""

import copy
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH)) if p not in sys.path]

from benchlib.files import load_json, load_module  # noqa: E402

reader = load_module("metrics/readers/step_pipeline.py")
FIXTURE = os.path.join(BENCH, "trace", "fixture_step_pipeline.json")


@pytest.fixture
def trace():
    with open(FIXTURE) as f:
        return json.load(f)


def read(trace, stat):
    """(the reading, the reader's log lines)"""
    lines = []
    ctx = types.SimpleNamespace(log=lines.append)
    return reader.read({"trace": trace}, {"stat": stat}, ctx), lines


def host_line(trace):
    return next(ln for p in trace["planes"] if p["name"] == "/host:CPU"
                for ln in p["lines"] if ln["name"] == "scheduler")


def device_line(trace, name):
    return next(ln for ln in trace["planes"][0]["lines"] if ln["name"] == name)


@pytest.mark.parametrize("stat,want", [("slack_ms", 7.6), ("host_loop_ms", 3.15),
                                       ("fetch_late_ms", 0.35)])
def test_known_intervals(trace, stat, want):
    got, lines = read(trace, stat)
    assert got == pytest.approx(want, abs=1e-9)
    joined = next(ln for ln in lines if "dispatches inside the window" in ln)
    # dispatches 102..106 and events 102..104 lie inside the window; the
    # pairs of 105 and 106 are cut by its end
    assert joined == ("step_pipeline: 5 dispatches inside the window, 3 joined to one jit_decode* "
                      "event whole inside it, 2 to one its edge cuts, 0 queued when the trace ended; "
                      "3 such events inside it, 3 joined to one dispatch, 0 dispatched before the "
                      "trace began; seq 101..106")


def test_the_three_add_up_to_the_devices_gap(trace):
    _, lines = read(trace, "host_loop_ms")
    identity = next(ln for ln in lines if "identity" in ln)
    # k = 101: 6.3 + 0.2 + 3.5 = 10 (step 102 alone); k = 102: 11.0 + 0.1 +
    # 2.9 = 14 (step 103 and the admission); k = 103's step 105 ends past the
    # window but begins inside it: 10.5 + 0.4 + 3.1 = 14 (the admission, step 104)
    assert "over 3 steps" in identity
    assert "slack 9.2667 + fetch-late 0.2333 + host loop 3.1667 = 12.6667 ms" in identity
    assert "12.6667 ms, in which the device ran 10.0000 ms of jit_decode* and 2.6667 ms of other" in identity


def test_a_parent_without_the_counter_spans_gives_nothing(trace):
    line = host_line(trace)
    line["events"] = [ev for ev in line["events"] if "seq=" not in ev[0]]
    for stat in ("slack_ms", "host_loop_ms", "fetch_late_ms"):
        assert read(trace, stat) == (None, [])
    assert reader.read({"trace": None}, {"stat": "slack_ms"}, None) is None


def test_a_window_edge_drops_the_pair_not_the_reading(trace):
    feeder = next(ln for p in trace["planes"] for ln in p["lines"] if ln["name"] == "feeder")
    feeder["events"] = [["bench:window", 10_500_000, 39_500_000]]  # ends at 50: cuts step 104's event
    got, lines = read(trace, "slack_ms")
    assert got == pytest.approx((7.6 + 6.3) / 2)
    assert any("4 dispatches inside the window, 2 joined" in ln and "2 such events inside it" in ln
               for ln in lines)
    assert read(trace, "fetch_late_ms")[0] is None  # one sample left (103): no median of one
    assert read(trace, "host_loop_ms")[0] == pytest.approx((3.5 + 2.9 + 3.1) / 3)


def test_a_step_queued_when_the_trace_ended_is_a_cut_pair(trace):
    """The device's side of the trace ends behind step 103 (a long admission
    stood in front of step 104 when the profiler stopped): the dispatches of
    104..106 lie inside the window and have no event, which drops those
    pairs and not the reading."""
    modules = device_line(trace, "XLA Modules")
    modules["events"] = [ev for ev in modules["events"] if ev[1] < 44_000_000]
    got, lines = read(trace, "slack_ms")
    assert got == pytest.approx((7.6 + 6.3) / 2)
    assert any("5 dispatches inside the window, 2 joined" in ln and "3 queued when the trace ended" in ln
               for ln in lines)
    # of the steps that were joined (101..103); step 104's loop goes with its pair
    assert read(trace, "host_loop_ms")[0] == pytest.approx((3.5 + 2.9 + 3.1) / 3)


@pytest.mark.parametrize("case", ["event_missing", "event_of_nobody", "dispatch_unnumbered",
                                  "two_offsets_fit"])
def test_counts_that_do_not_match_refuse(trace, case):
    modules, host = device_line(trace, "XLA Modules"), host_line(trace)
    if case == "event_missing":  # step 103's program is not in the device's line
        modules["events"] = [ev for ev in modules["events"] if ev[1] != 30_000_000]
    elif case == "event_of_nobody":  # a decode program between steps 103 and 104 that no dispatch of the trace queued
        modules["events"] = [["jit_decode(7)" if ev[0].startswith("jit_insert") else ev[0], ev[1], ev[2]]
                             for ev in modules["events"]]
    elif case == "dispatch_unnumbered":  # a dispatch nobody numbered: the seq numbers have a hole
        host["events"] = [ev for ev in host["events"] if "queued seq=103" not in ev[0]]
    else:  # one dispatch, and nothing that pins it to one of two later events
        host["events"] = [ev for ev in host["events"]
                          if not (ev[0].startswith("trlx:engine.") and ev[1] > 2_000_000)]
    for stat in ("slack_ms", "host_loop_ms", "fetch_late_ms"):
        got, lines = read(trace, stat)
        assert got is None
        assert len(lines) == 1 and "step_pipeline REFUSES" in lines[0]


def test_an_unknown_stat_raises(trace):
    with pytest.raises(ValueError, match="unknown stat"):
        read(trace, "p99")


@pytest.mark.parametrize("name,want", [
    ("engine.dispatch_slack_ms.batch", 7.6), ("engine.host_loop_ms.batch", 3.15),
    ("engine.fetch_late_ms.batch", 0.35),
    # the whole `jit_decode*` events inside the window: 102, 103, 104
    ("engine.decode_device_ms.batch", 10.0), ("engine.insert_device_ms.batch", 4.0),
    ("sched.insert_rows.batch", 1.0), ("sched.prefill_padding_share.batch", 100 * 28 / 128)])
def test_the_metric_files_read_the_fixture(trace, name, want):
    """Each new metric through its own file, as `bench/run.py` reads it, and
    listed for the three serve cells in BENCHMARK.json under its layer."""
    spec = load_json(f"metrics/{name}.json")
    ctx = types.SimpleNamespace(log=lambda msg: None)
    got = load_module(f"metrics/readers/{spec['reader']}.py").read({"trace": trace}, spec["params"], ctx)
    assert got == pytest.approx(want)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"] if e["name"] == name)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} \
        == {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert entry["workloads"] == ["pythia-1.4b.rollout-batch", "laguna-xs.2.rollout-code",
                                  "openpangu-ultra-moe-718b.rollout-longctx"]


def test_idle_gaps_keep_one_row_a_span_name(trace):
    """The counter spans carry numbers in their names and are empty: no idle
    gap of the device is charged to one, so `breakdown.idle_gaps` keeps one
    row a span name however many steps a trace holds."""
    reduce = load_module("trace/reduce.py")
    ops = device_line(trace, "XLA Ops")
    # open three gaps where the host stands in a counter span's moment, in a
    # dispatch and in a fetch
    ops["events"] = [ev for ev in ops["events"] if ev[1] not in (20_000_000, 30_000_000, 54_000_000)]
    gaps = reduce.idle_gaps_by_span(copy.deepcopy(trace))
    assert not any("seq=" in name or "calls=" in name for name in gaps)
    assert set(gaps) <= {"trlx:engine.step", "trlx:engine.dispatch", "trlx:engine.fetch",
                         "trlx:sched.insert_batch", "trlx:engine.insert", "(no span)"}
