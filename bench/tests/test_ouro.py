"""`ouro-2.6b.rollout-math` at the rehearsal size: `bench/reference/ouro.py`
against the program's `TransformerLM` on the weights the benchmark makes from a
seed; the configuration file (nothing cut); `serve_loop`'s count of the pool's
bytes and `roofline_loop.decode_step`, each by hand; the reader the cell
brings, on a trace written by hand; and a walk of the cell, `--trace 0` and
`--trace 2`, with and without `--control`.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_ouro.py -q
"""

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from benchlib import weights  # noqa: E402
from benchlib.files import load_module  # noqa: E402

CELL = "ouro-2.6b.rollout-math"
with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
PUBLISHED = {k: v for k, v in RAW.items() if k != "bench"}
ref = load_module("reference/ouro.py")
plain = load_module("reference/plain_ops.py")


def _model():
    import jax.numpy as jnp

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    extra = dict(CONFIG["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(CONFIG["rehearse"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, dtype=jnp.float32)
    return CausalLMPolicy(cfg)


@pytest.mark.parametrize("seed", [101, 3_000_000_203])
def test_reference_against_the_program_at_the_rehearsal_sizes(seed):
    import jax
    import jax.numpy as jnp

    model = _model()
    t = jnp.zeros((1, 8), jnp.int32)
    params = weights.make_params(weights.param_shapes(model, t, jnp.ones_like(t)), seed, jnp.float32)
    assert "exit_gate" in params["lm"]
    rng = np.random.default_rng(seed)
    lens, width = (40, 33, 12), 40
    tokens = rng.integers(1, SIZES["vocab_size"], size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    tokens = tokens * mask
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, a, b: model.apply({"params": p}, a, b)[0])(
            params, jnp.asarray(tokens), jnp.asarray(mask))
    got = np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    assert np.abs(got - want)[valid].max() < 1e-4
    pdf = np.asarray(ref.exit_pdf(params["lm"], tokens, mask, SIZES))
    assert pdf.shape == (3, width, 4) and np.abs(pdf.sum(-1) - 1).max() < 1e-5
    # the control: the reference in int8 is far from itself, by more than the limit's floor
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - want)[valid] ** 2)) > 1e-3
    # and each departure is far from the reference: passes that all read pass 0's keys and values first
    assert ref.DEPARTURES[0] == "pass0_kv"
    for departure in ref.DEPARTURES:
        other = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, departure=departure))
        assert np.abs(other - want)[valid].max() > 1e-2, departure


def test_the_configuration_file_states_the_published_model_whole():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == CONFIG["reduced"] == [] and entry["source"] == CONFIG["source"]
    published = dict(  # the catalog row's `config`, letter for letter
        head_dim=128, hidden_act="silu", hidden_size=2048, intermediate_size=5632, layer_types=["full_attention"] * 48,
        max_position_embeddings=65536, max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
        sliding_window=None, tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1,
        use_sliding_window=False, vocab_size=49152)
    assert PUBLISHED == published
    assert sorted(CONFIG["assumed"]) == ["exit_gate", "kv_per_pass_layer", "no_bias_no_qk_norm", "norm_between_passes",
                                         "rope", "sandwich_norms", "tensor_names"]
    assert "one v5e chip holds the WHOLE published model" in CONFIG["deployment"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert CONFIG["parameters_held"] == 48 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657
    assert CONFIG["precision"]["serve"] == {**CONFIG["precision"]["serve"], "weights": "bfloat16",
                                            "compute": "bfloat16", "kv_cache": "bfloat16", "exit_gate": "float32"}
    assert SIZES["total_ut_steps"] == 4 and sorted(SIZES) == sorted(PUBLISHED)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "rollout-math", 1)
    assert len(bench["workloads"]) == 12 and not any(w["chips"] != 1 for w in bench["workloads"])
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        file = json.load(f)
    assert file["job"] == "serve_loop" and file["why"] == cell["why"] and file["check"] == {"requests": 2}
    assert file["engine"] == dict(num_slots=8, max_prompt_len=256, max_prefill_batch=1, prompt_bucket=64,
                                  kv_block_size=32, kv_pool_blocks=160, kv_cache_dtype="bf16", max_queue_depth=32,
                                  decode_kernel="auto")
    with open(os.path.join(BENCH, "traffic", "rollout-math.json")) as f:
        mix = json.load(f)
    assert (mix["pool"], mix["arrivals"], mix["ramp_seconds"]) == (8, {"kind": "backlog", "depth": 16}, 10)
    assert mix["prompt_len"] == dict(dist="lognormal", median=128, sigma=0.5, min=48, max=256)
    assert mix["output_len"] == dict(dist="fixed", value=352, min=352, max=352)  # the issue's fallback: 384 sat on a pass's edge
    # the pool's 8 rows all live at their longest fit the blocks the pool hands out (the zero block is the 160th)
    lens = load_module("benchlib/traffic.py").lengths(mix["prompt_len"], 8, np.random.default_rng(0))
    assert sorted(int(n) for n in lens) == [59, 82, 100, 118, 138, 163, 199, 256]
    assert sum(-(-(int(n) + 352) // 32) for n in lens) == 127 <= file["engine"]["kv_pool_blocks"] - 1
    assert sorted(set(-(-int(n) // 64) * 64 for n in lens)) == [64, 128, 192, 256]
    # every new metric has its file, and the cell stands on the lists every serve cell stands on
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == sorted(
        ["paged_decode_roofline.math", "attn.paged_share.math", "kv.read_mb_per_step.math",
         "loop.layer_calls_per_step", "decode_stream_roofline.math"]) and set(m["name"] for m in new) <= set(file["per_layer"])
    for m in new:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        assert (spec["unit"], spec["layer"], spec["moves"]) == (m["unit"], m["layer"], m["moves"])
        assert m["moves"] == "serve_tokens_per_s"
    shared = ["engine.step_ms.batch", "sched.slot_occupancy", "device.idle_share.batch",
              "engine.prefill_device_share.batch", "engine.decode_device_ms.batch", "engine.insert_device_ms.batch",
              "sched.prefill_padding_share.batch", "build.trace_lower_s", "build.backend_s", "build.cache_read_s",
              "build.cache_misses", "build.programs"]
    assert all(m["workloads"][-1] == CELL for m in bench["per_layer"] if m["name"] in shared)


def test_the_jobs_count_of_the_pools_bytes_by_hand():
    job = load_module("jobs/serve_loop.py")
    precision = CONFIG["precision"]["serve"]
    # 159 blocks to hand out and the zero block, 32 positions each, 4 x 48 planes of K and V, 16 heads of 128, 2 B
    assert job.stated_pool_bytes(159, 32, PUBLISHED, precision) == 160 * 32 * 1_572_864 == 8_053_063_680
    assert job.stated_pool_bytes(24, 8, SIZES, precision) == 25 * 8 * 4 * 2 * 2 * 4 * 16 * 2
    checks = load_module("benchlib/result.py").Checks()
    ctx = types.SimpleNamespace(rehearse=False, config={**CONFIG, "sizes": PUBLISHED})
    engine = types.SimpleNamespace(total_blocks=159, kv_block_size=32)
    job.check_kv_precision(ctx, engine, None, 8_053_063_680 + 40_000, checks)  # the pool's masks and tables
    assert checks.ok
    job.check_kv_precision(ctx, engine, None, 8_053_063_680 // 4, checks)  # one plane a layer for every pass
    assert not checks.ok


V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}


def test_a_looped_decode_step_priced_by_hand():
    loop = load_module("roofline_loop.py")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert loop.layer_parameters(PUBLISHED) == layer == 51_380_224
    rows, resident = 8, 8 * 320
    flops, nbytes = loop.decode_step(resident, rows, PUBLISHED)
    stack = 48 * (layer + 4 * 2048) + 2048
    weights = (4 * stack + 2048 + 1 + 2048 * 49152 + rows * 2048) * 2
    kv = (resident + rows) * 192 * 2 * 16 * 128 * 2
    acts = (192 * 2 * rows * 2048 + rows * 49152) * 2
    assert nbytes == weights + kv + acts
    assert 4 * 4.93e9 < weights < 4 * 4.94e9 + 0.21e9 and 4.03e9 < kv < 4.05e9  # four streams of the stack; 1.57 MB a position
    assert flops == 2.0 * rows * (192 * layer + 2048 * 49152) + 2.0 * 256 * 16 * resident * 192
    seconds, bound = load_module("roofline.py").least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 0.0290 < seconds < 0.0296  # 29.3 ms at the chip's 819 GB/s


def _trace(ops, modules, spans=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [["bench:window", 0, 100_000_000], *spans]}]}]}


def test_the_step_reader_prices_whole_steps_inside_the_window():
    spec = json.load(open(os.path.join(BENCH, "metrics", "decode_stream_roofline.math.json")))
    reader = load_module(f"metrics/readers/{spec['reader']}.py")
    assert spec["reader"] == "loop_step_roofline"
    loop, roofline = load_module("roofline_loop.py"), load_module("roofline.py")
    least = roofline.least_seconds(*loop.decode_step(2560, 8, PUBLISHED), V5E)[0]
    step = round(least * 1e9 / 0.8)  # a step at 80% of its roofline
    modules = [["jit_decode(1)", 1_000_000, step], ["jit_decode(1)", 40_000_000, step],
               ["jit_insert(2)", 80_000_000, 5_000_000], ["jit_decode(1)", 99_000_000, step]]  # the last is cut
    calls = {"paged_decode": {"steps_resident_tokens": [2560, 2560, -1], "layers": 48, "heads": 16, "kv_heads": 16,
                              "head_dim": 128, "kv_bytes": 2}}
    logs = []
    ctx = types.SimpleNamespace(rehearse=False, config={**CONFIG, "sizes": PUBLISHED}, peaks=V5E, log=logs.append)
    m = {"trace": _trace([], modules), "constants": {"num_slots": 8}, "kernel_calls": calls}
    assert reader.read(m, spec["params"], ctx) == pytest.approx(80.0, abs=0.01)
    assert "2 whole steps" in logs[-1] and "bound by memory" in logs[-1]
    # nothing to read: no trace, no decode module, a configuration that names no passes (a parent's cell)
    assert reader.read({"trace": None}, spec["params"], ctx) is None
    assert reader.read({**m, "trace": _trace([], modules[2:3])}, spec["params"], ctx) is None
    other = types.SimpleNamespace(**{**vars(ctx), "config": {**CONFIG, "sizes": {"hidden_size": 2048}}})
    assert reader.read(m, spec["params"], other) is None
    # the counters the cell's two span readers read
    counters = load_module("metrics/readers/span_counters.py")
    spans = [["trlx:engine.loop steps=1 passes=4 layer_calls=192 exit_early=0.5", 5_000_000, 0],
             ["trlx:engine.kv_walk resident=491520 walked_full=552960 layers=192 bytes=4529848320", 6_000_000, 0]]
    traced = {"trace": _trace([], modules, spans)}
    calls_spec = json.load(open(os.path.join(BENCH, "metrics", "loop.layer_calls_per_step.json")))
    assert counters.read(traced, calls_spec["params"], ctx) == 192.0
    kv_spec = json.load(open(os.path.join(BENCH, "metrics", "kv.read_mb_per_step.math.json")))
    assert counters.read(traced, kv_spec["params"], ctx) == pytest.approx(4529.84832)


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_cell_walks_on_the_cpu_with_its_own_checks(trace):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", trace, "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    assert "REHEARSAL" in proc.stdout, proc.stdout[-3000:] + proc.stderr[-3000:]
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert checks and set(checks.values()) == {"ok"}, checks
    assert any(what.startswith("engine_logprob_rms") for what in checks)
    assert any("4 passes x 2 layers" in what for what in checks)
    if trace == "2":
        assert "trlx:engine.loop steps=1 passes=4 layer_calls=8 exit_early=" in proc.stdout
        assert re.search(r"trlx:engine.kv_walk [^']*layers=8\b", proc.stdout)
        return
    # the control: an int8 arena holds half the bytes the configuration states
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert [what for what, verdict in checks.items() if verdict != "ok"] == [
        what for what in checks if what.startswith("bytes of the arrays the engine's pool holds")]
