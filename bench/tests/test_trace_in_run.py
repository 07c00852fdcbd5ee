"""`--trace 2` and the readers of the program's own spans.

- `host_span` and `trace_module_ops` on a trace small enough to read by
  hand: a step span of 10 ms with 8 ms of device work inside reads 2 ms;
- idle gaps are charged to the innermost span, be it the benchmark's
  (`bench:`) or the program's (`trlx:`);
- a traced run lists the cell file's metrics and then what `BENCHMARK.json`
  lists for the cell, and every one of them has its file and its reader;
- the rehearsal of `--trace 2` walks both jobs, and no span is put on from
  outside: the host spans of its trace are the program's, the traced window
  and the three of the `ppo` job's own cycle, which `--trace 0` has too.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_trace_in_run.py -q
"""

import ast
import json
import os
import re
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from benchlib.files import load_json, load_module  # noqa: E402

reduce = load_module("trace/reduce.py")
host_span = load_module("metrics/readers/host_span.py")
module_ops = load_module("metrics/readers/trace_module_ops.py")
CTX = types.SimpleNamespace(log=lambda msg: None)
MS = 1_000_000  # ns

# One device, the scheduler's thread and the thread that feeds, times in ms.
# By hand:
#   window [0,100). Three steps of the engine: [5,15) [20,30) [40,52), and one
#   cut by the window's end, [95,105).
#   step 1: decode program [6,14) busy all through: 10 - 8 = 2 ms of host
#   step 2: decode program [21,29) -> 2 ms
#   step 3: behind an admission; the prefill [33,41) overlaps it by 1 ms,
#           decode [41,51): busy inside [40,52) is 11 ms -> 1 ms of host
#   -> host_ms = (2 + 2 + 1) / 3; gaps between whole steps: 5 and 10 -> 7.5 ms
#   prefill program `jit_insert` [33,41): 8 of 100 ms -> 8 %
#   copies inside the whole decode programs [6,14) [21,29) [41,51): 1 + 1 + 2
#   ms over 3 steps (the copy at [34,36) is the prefill's, the one at [97,99)
#   sits in a decode program the window cuts) -> 4/3 ms a step
#   reward spans [60,61) and [70,72), on another thread [71,73): union 4 ms -> 4 %
#   the device is busy on [6,14) [21,29) [33,51) [96,99): 37 ms; the idle gaps and
#   the innermost span round the middle of each:
#     [0,6)   mid 3    -> no span                        6 ms
#     [14,21) mid 17.5 -> trlx:sched.emit (inside bench:engine.loop)  7 ms
#     [29,33) mid 31   -> trlx:sched.admit (inside bench:engine.loop) 4 ms
#     [51,96) mid 73.5 -> bench:check_outputs           45 ms
#     [99,100) mid 99.5 -> trlx:engine.step              1 ms
STEP = "trlx:engine.step"
COPY = "%copy.{} = bf16[1280,16,32,128]{{3,2,1,0:T(8,128)(2,1)}} copy(bf16[1280,16,32,128]{{3,2,1,0}} %p)"
FUSION = "%fusion.{} = bf16[64,2048]{{1,0:T(8,128)(2,1)}} fusion(bf16[64,2048]{{1,0}} %x), kind=kLoop"


def _ms(events):
    return [[n, s * MS, d * MS] for n, s, d in events]


HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": _ms([
            [FUSION.format(1), 6, 7], [COPY.format(1), 13, 1],
            [FUSION.format(2), 21, 7], [COPY.format(2), 28, 1],
            [FUSION.format(3), 33, 1], [COPY.format(3), 34, 2], [FUSION.format(4), 36, 5],
            [FUSION.format(5), 41, 8], [COPY.format(4), 49, 2],
            [FUSION.format(6), 96, 1], [COPY.format(5), 97, 2]])},
        {"name": "XLA Modules", "events": _ms([
            ["jit_decode(1)", 6, 8], ["jit_decode(1)", 21, 8], ["jit_insert(2)", 33, 8],
            ["jit_decode(1)", 41, 10], ["jit_decode(1)", 96, 8]])}]},
    {"name": "/host:CPU", "lines": [
        {"name": "scheduler", "events": _ms([
            ["bench:engine.loop", 4, 50],
            [STEP, 5, 10], ["trlx:sched.emit", 15, 4.9], [STEP, 20, 10],
            ["trlx:sched.admit", 30, 9], [STEP, 40, 12], [STEP, 95, 10],
            ["trlx:ppo.reward", 60, 1], ["trlx:ppo.reward", 70, 2],
            ["bench:check_outputs", 58, 35]])},
        {"name": "feeder", "events": _ms([["bench:window", 0, 100], ["trlx:ppo.reward", 71, 2]])}]},
]}
M = {"trace": HAND}


def test_step_span_minus_device_busy_by_hand():
    assert host_span.read(M, {"span": STEP, "stat": "host_ms"}, CTX) == pytest.approx(5 / 3)
    # the simplest case on its own: 10 ms of span, 8 ms of device work inside it
    one = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": _ms([["%f = fusion(", 1, 8]])}]},
        {"name": "/host:CPU", "lines": [
            {"name": "t", "events": _ms([["bench:window", 0, 20], [STEP, 0.5, 10]])}]}]}
    assert host_span.read({"trace": one}, {"span": STEP, "stat": "host_ms"}, CTX) == pytest.approx(2.0)


def test_gap_between_spans_and_share_by_hand():
    assert host_span.read(M, {"span": STEP, "stat": "gap_ms"}, CTX) == pytest.approx(7.5)
    assert host_span.read(M, {"span": "trlx:ppo.reward", "stat": "share"}, CTX) == pytest.approx(4.0)
    # the step cut by the window's end counts for the share as far as it lies inside
    assert host_span.read(M, {"span": STEP, "stat": "share"}, CTX) == pytest.approx(37.0)


def test_module_share_and_copies_a_step_by_hand():
    assert module_ops.read(M, {"module_prefix": "jit_insert", "per": "window"}, CTX) == pytest.approx(8.0)
    assert module_ops.read(
        M, {"module_prefix": "jit_decode", "op": "copy", "per": "module_event"}, CTX
    ) == pytest.approx(4 / 3)
    assert module_ops.operation(COPY.format(9)) == "copy"
    assert module_ops.operation(FUSION.format(9)) == "fusion"
    assert module_ops.operation('%paged_decode.43 = bf16[64,16,128]{2,1,0} custom-call(bf16[64,16,128]{2,1,0} %q), '
                                'custom_call_target="tpu_custom_call"') == "custom-call"


def test_flash_roofline_prices_each_event_by_its_own_shape():
    """Two calls: the scorer's [2 rows x 16 heads, 1024, 128] in 20 ms and a
    train step's forward with the log-sum-exp, [1 x 16, 512, 128] in 5 ms.
    Causal: 2 t^2 d operations a head; 4 t d 2 bytes a head. On a chip of
    1e12 FLOP/s and 1e11 B/s both are compute-bound:
    2 * 1024^2 * 128 * 32 / 1e12 = 8.590 ms, 2 * 512^2 * 128 * 16 / 1e12 =
    1.074 ms -> 9.664 of 25 ms."""
    flash = load_module("metrics/readers/flash_roofline.py")
    call = 'custom-call(bf16[32,1024,128]{2,1,0} %q), custom_call_target="tpu_custom_call"'
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": _ms([
            ["%flash_fwd.7 = bf16[32,1024,128]{2,1,0:T(8,128)(2,1)} " + call, 10, 20],
            ["%flash_fwd_lse.2 = (bf16[16,512,128]{2,1,0}, f32[16,8,512]{2,1,0}) " + call, 40, 5],
            ["%flash_bwd_dq.2 = bf16[16,512,128]{2,1,0} " + call, 50, 9],
            [FUSION.format(1), 60, 1]])}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": _ms([["bench:window", 0, 100]])}]}]}
    # heads and widths under the published configuration's own keys, the
    # sequence from the cell's recipe: nothing from the job
    ctx = types.SimpleNamespace(
        log=lambda msg: None, rehearse=False,
        peaks={"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11},
        config={"sizes": {"hidden_size": 2048, "num_attention_heads": 16}},
        cell={"recipe": {"train": {"seq_length": 1024}}})
    params = {"kernel_prefix": "flash_fwd", "heads_keys": ["num_attention_heads", "n_head"],
              "hidden_keys": ["hidden_size", "n_embd"]}
    m = {"trace": trace}
    least_ms = (2 * 1024**2 * 128 * 32 + 2 * 512**2 * 128 * 16) / 1e12 * 1e3
    assert flash.read(m, params, ctx) == pytest.approx(100 * least_ms / 25)
    assert least_ms == pytest.approx(9.664, abs=1e-3)
    # an event that does not fit the configuration is refused, not priced
    ctx.config = {"sizes": {"n_embd": 1024, "n_head": 16}}
    with pytest.raises(ValueError, match="does not fit"):
        flash.read(m, params, ctx)
    # a program whose kernels carry no name gives nothing to read
    assert flash.read({"trace": HAND}, params, ctx) is None


def test_the_breakdown_has_one_row_a_kernel():
    call = ' = bf16[64,16,128]{2,1,0} custom-call(bf16[64,16,128]{2,1,0} %q), custom_call_target="tpu_custom_call"'
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": _ms([
            ["%paged_decode.24" + call, 0, 3], ["%paged_decode.25" + call, 3, 3],
            ["%flash_fwd_lse.2" + call, 6, 1], [FUSION.format(7), 7, 2],
            ['%custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %x), custom_call_target="Sharding"', 9, 1]])}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": _ms([["bench:window", 0, 10]])}]}]}
    assert reduce.summary(trace)["breakdown"]["device_ops"] == [
        ["%paged_decode.* = custom-call [tpu_custom_call]", pytest.approx(6e-3)],
        ["%fusion.7 = fusion", pytest.approx(2e-3)],
        ["%flash_fwd_lse.* = custom-call [tpu_custom_call]", pytest.approx(1e-3)],
        ["%custom-call.9 = custom-call [Sharding]", pytest.approx(1e-3)]]


@pytest.mark.parametrize("reader, params", [
    (host_span, {"span": "trlx:not.there", "stat": "host_ms"}),
    (host_span, {"span": "trlx:not.there", "stat": "share"}),
    (module_ops, {"module_prefix": "jit_not_there", "per": "window"}),
])
def test_a_program_without_the_span_gives_nothing(reader, params):
    assert reader.read(M, params, CTX) is None
    assert reader.read({"trace": None}, params, CTX) is None


def test_idle_gaps_go_to_the_innermost_span_of_either_kind():
    gaps = reduce.idle_gaps_by_span(HAND, min_gap_ns=1)
    assert gaps == {"(no span)": pytest.approx(6e-3), "trlx:sched.emit": pytest.approx(7e-3),
                    "trlx:sched.admit": pytest.approx(4e-3),
                    "bench:check_outputs": pytest.approx(45e-3),
                    "trlx:engine.step": pytest.approx(1e-3)}
    assert sum(gaps.values()) == pytest.approx(63e-3)


def test_a_traced_run_lists_what_benchmark_json_lists_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["trace_in_run"] is True
    names = load_module("run.py").per_layer_names
    assert names({"per_layer": [{"name": "a", "workloads": ["c1"]}, {"name": "b", "workloads": ["c2"]},
                                {"name": "c"}, {"name": "d", "workloads": ["c2", "c1"]}]},
                 {"name": "c1", "per_layer": ["x", "a"]}) == ["x", "a", "d"]
    for cell in bench["workloads"]:
        in_file = load_json(f"workloads/{cell['name']}.json")
        listed = names(bench, in_file)
        assert listed[:len(in_file["per_layer"])] == in_file["per_layer"]
        assert set(listed) == {e["name"] for e in bench["per_layer"]
                               if cell["name"] in e.get("workloads", ())}
        for name in listed:
            spec = load_json(f"metrics/{name}.json")
            entry = next(e for e in bench["per_layer"] if e["name"] == name)
            assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == \
                {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
            assert hasattr(load_module(f"metrics/readers/{spec['reader']}.py"), "read")


def _run_trace2(cell):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", "2147483801",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    return subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)


def _rehearse_trace2(cell):
    proc = _run_trace2(cell)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "NOT CORRECT" not in proc.stdout
    seen = re.search(r"host spans seen: (\[.*\])", proc.stdout)
    assert seen, proc.stdout[-3000:]
    return set(ast.literal_eval(seen.group(1))), proc.stdout


def test_rehearsal_of_trace_2_ppo_puts_no_span_on_from_outside():
    spans, out = _rehearse_trace2("pythia-1.4b.ppo-hh")
    # the cycle's own three spans are `run_cycle`'s, under `--trace 0` too; the
    # wrappers of `--trace 1` (generate_dispatch, reward_fn, ...) are not there
    assert {s for s in spans if s.startswith("bench:")} == {
        "bench:window", "bench:make_experience", "bench:train_epochs", "bench:loss_fetch"}
    assert {"trlx:ppo.make_experience", "trlx:ppo.generate_dispatch", "trlx:ppo.rollout_fetch",
            "trlx:ppo.host_decode", "trlx:ppo.reward", "trlx:ppo.score_dispatch",
            "trlx:pipeline.collate", "trlx:ppo.train_minibatch"} <= spans
    assert "the traced cycle took" in out
    # both kinds of check ran on the closed window: the end-to-end metrics were taken
    assert "end-to-end metrics the cell names but the run could not take []: 0" in out


def test_trace_2_refuses_the_open_loop_that_no_cell_uses_yet():
    proc = _run_trace2("rehearsal.serve-open")
    assert proc.returncode != 0
    assert "--trace 2 traces a backlog" in proc.stdout + proc.stderr
    assert '"metrics"' not in proc.stdout  # no result line


def test_rehearsal_of_trace_2_serve_puts_no_span_on_from_outside():
    spans, out = _rehearse_trace2("pythia-1.4b.rollout-batch")
    assert {s for s in spans if s.startswith("bench:")} == {"bench:window"}
    assert {"trlx:sched.admit", "trlx:sched.insert_batch", "trlx:sched.decode_once",
            "trlx:sched.emit", "trlx:engine.step", "trlx:engine.dispatch", "trlx:engine.fetch",
            "trlx:engine.insert"} <= spans
    assert "end-to-end metrics the cell names but the run could not take []: 0" in out
