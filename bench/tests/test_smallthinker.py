"""`smallthinker-21b-a3b.rollout-transcript` at the rehearsal size:
`bench/reference/smallthinker.py` against the program's `TransformerLM` on the
weights the benchmark makes from a seed; the configuration file's cut; the
two readers the cell brings, on a trace written by hand; and a walk of the
cell, with and without `--control`.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_smallthinker.py -q
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

CELL = "smallthinker-21b-a3b.rollout-transcript"
with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
ref = load_module("reference/smallthinker.py")


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
    rng = np.random.default_rng(seed)
    lens, width = (40, 33, 12), 40
    tokens = rng.integers(1, SIZES["vocab_size"], size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    tokens = tokens * mask
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, a, b: model.apply({"params": p}, a, b)[0])(
            params, jnp.asarray(tokens), jnp.asarray(mask))
    got = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    assert np.abs(got - want)[valid].max() < 1e-4
    # the head a block of the vocabulary and of the positions at a time is the head whole
    whole = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(
        ref.logits(params["lm"], tokens, mask, SIZES), jnp.asarray(tokens)))
    assert np.abs(whole - want)[valid].max() < 1e-5
    # the control: the reference in int8 is far from itself, by more than the limit's floor
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - want)[valid] ** 2)) > 1e-3
    # and each departure is far from the reference
    for departure in ref.DEPARTURES:
        other = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, departure=departure))
        assert np.abs(other - want)[valid].max() > 1e-2, departure


def test_the_configuration_file_states_the_cut_and_nothing_else_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "smallthinker-21b-a3b")
    reduced = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert entry["reduced"] == CONFIG["reduced"] == reduced and entry["source"] == CONFIG["source"]
    period = [0, 1, 1, 1]
    published = dict(  # the catalog row's `config`, letter for letter
        head_dim=128, hidden_size=2560, max_position_embeddings=16384, model_name="smallthinker_21b_instruct",
        moe_ffn_hidden_size=768, moe_num_active_primary_experts=6, moe_num_primary_experts=64,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True, num_attention_heads=28, num_hidden_layers=52,
        num_key_value_heads=4, rms_norm_eps=1e-06, rope_layout=period * 13, rope_scaling=None, rope_theta=1500000,
        sliding_window_layout=period * 13, sliding_window_size=4096, tie_word_embeddings=False, vocab_size=151936)
    held = {k: v for k, v in RAW.items() if k != "bench"}
    assert sorted(held) == sorted(published)
    assert {k for k in published if held[k] != published[k]} == set(reduced)
    assert held["num_hidden_layers"] == 8 and held["rope_layout"] == held["sliding_window_layout"] == period * 2
    assert CONFIG["published"]["num_hidden_layers"] == 52
    assert sorted(CONFIG["assumed"]) == ["rope", "router_input", "secondary_experts", "sparse_reglu", "tensor_names"]
    assert "7 pipeline stages" in CONFIG["deployment"] and CONFIG["parameters_held"] == 3_966_937_600
    assert CONFIG["precision"]["serve"] == {**CONFIG["precision"]["serve"], "weights": "bfloat16",
                                            "compute": "bfloat16", "kv_cache": "bfloat16", "router_scores": "float32"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("smallthinker-21b-a3b", "rollout-transcript", 1)
    assert len(bench["workloads"]) == 11 and not any(w["chips"] != 1 for w in bench["workloads"])
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        file = json.load(f)
    assert file["job"] == "serve" and file["why"] == cell["why"]
    assert {k: file["engine"][k] for k in ("num_slots", "max_prompt_len", "max_prefill_batch", "prompt_bucket",
                                           "kv_block_size", "kv_pool_blocks", "kv_cache_dtype", "decode_kernel")} == dict(
        num_slots=32, max_prompt_len=14336, max_prefill_batch=1, prompt_bucket=2048, kv_block_size=32,
        kv_pool_blocks=10240, kv_cache_dtype="bf16", decode_kernel="auto")
    with open(os.path.join(BENCH, "traffic", "rollout-transcript.json")) as f:
        mix = json.load(f)
    assert (mix["pool"], mix["arrivals"], mix["ramp_seconds"]) == (32, {"kind": "backlog", "depth": 64}, 10)
    assert mix["prompt_len"] == dict(dist="lognormal", median=8192, sigma=0.4, min=4096, max=14336)
    assert mix["output_len"] == dict(dist="fixed", value=1024, min=1024, max=1024)
    # the pool's 32 rows all live at their longest fit the pool, and a row ends inside the model's positions
    lens = load_module("benchlib/traffic.py").lengths(mix["prompt_len"], 32, np.random.default_rng(0))
    assert sum(-(-(int(n) + 1024) // 32) for n in lens) == 9687 < file["engine"]["kv_pool_blocks"]
    assert int(lens.min()) >= held["sliding_window_size"] and int(lens.max()) + 1024 <= held["max_position_embeddings"]
    assert sorted(set(-(-int(n) // 2048) * 2048 for n in lens)) == [4096, 6144, 8192, 10240, 12288, 14336]
    # every new metric has its file, and the cell stands on the end-to-end metric it reports
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == sorted(n for n in file["per_layer"] if n.endswith(".transcript"))
    for m in new:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        assert (spec["unit"], spec["layer"], spec["moves"]) == (m["unit"], m["layer"], m["moves"])
        assert m["moves"] == "serve_tokens_per_s"


V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}


def _ctx(logs):
    sizes = {k: v for k, v in RAW.items() if k != "bench"}
    return types.SimpleNamespace(rehearse=False, config={**CONFIG, "sizes": sizes}, peaks=V5E, log=logs.append,
                                 cell={"name": CELL}, traffic={})


def _trace(ops, modules, spans):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [["bench:window", 0, 10_000_000], *spans]}]}]}


def test_the_serving_moe_reader_prices_a_step_at_the_experts_met_and_a_block_at_its_prompts_share():
    """One decode step's down product (a buffer of 256 rows, 192 real, 61 of 64
    experts met by the counter) and one prefill block's gate product (24,576
    rows, of which 3/4 are prompt, which meet all 64), each given exactly its least
    time: 100%."""
    reader = load_module("metrics/readers/moe_serve_roofline.py")
    spec = json.load(open(os.path.join(BENCH, "metrics", "moe_gmm_roofline.transcript.json")))
    assert spec["reader"] == "moe_serve_roofline"
    step_bytes = (61 * 768 * 2560 + 192 * (768 + 2560)) * 2
    step_ns = step_bytes / 819e9 * 1e9  # memory-bound: 0.29 ms
    rows = 24576 * 0.75
    # 288 rows an expert is under the chip's ridge (240 operations a byte, but each row is read and written too):
    # memory-bound as well, 0.46 ms against 0.37 ms of products
    block_ns = (64 * 2560 * 768 + rows * (2560 + 768)) * 2 / 819e9 * 1e9
    assert 2 * 192 * 768 * 2560 / 197e12 < step_bytes / 819e9
    assert 2 * rows * 2560 * 768 / 197e12 < block_ns / 1e9
    ops = [["%moe_gmm.3 = bf16[256,2560]{1,0} custom-call(...)", 1_000_000, round(step_ns)],
           ["%moe_gmm.7 = bf16[24576,768]{1,0} custom-call(...)", 5_000_000, round(block_ns)]]
    modules = [["jit_decode(1)", 900_000, 1_000_000], ["jit_insert(2)", 4_900_000, 2_000_000]]
    spans = [["trlx:engine.moe local_assignment_share=1.0 experts_met=61.0 experts_held=64.0", 100, 0],
             ["trlx:sched.insert calls=1 rows=1 prompt_tokens=6144 padded_tokens=8192 pad_tokens=2048", 200, 0]]
    logs = []
    m = {"trace": _trace(ops, modules, spans), "constants": {"num_slots": 32}}
    assert reader.read(m, spec["params"], _ctx(logs)) == pytest.approx(100.0, abs=0.01)
    assert "192 rows over 61.00 experts met" in logs[-1] and "75.0% of their rows" in logs[-1]
    # a program that writes no `experts_met` (the parent): a step is priced at what a fair router meets
    assert reader.experts_met_expected(32, 64, 6) == pytest.approx(64 * (1 - (58 / 64) ** 32))
    m["trace"] = _trace(ops[:1], modules, [])
    assert 100.0 < reader.read(m, spec["params"], _ctx(logs)) < 100.6  # 61.3 against 61
    assert reader.read({"trace": _trace([], modules, spans), "constants": {"num_slots": 32}}, spec["params"],
                       _ctx(logs)) is None and reader.read({"trace": None}, spec["params"], _ctx(logs)) is None


def test_the_window_layout_reader_hands_the_accepted_readers_the_keys_they_read():
    """A full layer's step over 9,216 positions a row and a window layer's over
    4,096, each given its least time, read 100% through `paged_kinds_roofline`;
    the flash reader prices a banded forward by its band."""
    window = load_module("roofline_window.py")
    roofline = load_module("roofline.py")
    spec = json.load(open(os.path.join(BENCH, "metrics", "paged_window_roofline.transcript.json")))
    reader = load_module(f"metrics/readers/{spec['reader']}.py")
    resident = 32 * 9216
    full = roofline.least_seconds(*window.paged_decode_layer(resident, 32, 28, 4, 128, 2), V5E)[0]
    band = roofline.least_seconds(*window.paged_decode_layer(32 * 4096, 32, 28, 4, 128, 2), V5E)[0]
    ops = [["%paged_decode.1 = bf16[32,28,128]{2,1,0} custom-call(...)", 1_000_000, round(full * 1e9)],
           ["%paged_decode_window.1 = bf16[32,28,128]{2,1,0} custom-call(...)", 3_000_000, round(band * 1e9)]]
    modules = [["jit_decode(1)", 900_000, 5_000_000]]
    calls = {"paged_decode": {"steps_resident_tokens": [resident], "layers": 8, "heads": 28, "kv_heads": 4,
                              "head_dim": 128, "kv_bytes": 2}}
    logs = []
    m = {"trace": _trace(ops, modules, []), "constants": {"num_slots": 32}, "kernel_calls": calls}
    assert reader.read(m, spec["params"], _ctx(logs)) == pytest.approx(100.0, abs=0.01)
    assert "2 full and 6 window layers (band 4096), 28 query heads over 4 K/V heads of 128" in logs[-1]
    flash = json.load(open(os.path.join(BENCH, "metrics", "flash_window_roofline.transcript.json")))
    t = 8192
    least = [roofline.least_seconds(*window.flash_fwd_band(28, t, 128, band), V5E)[0] for band in (None, 4096)]
    ops = [[f"%flash_fwd.1 = bf16[28,{t},128]{{2,1,0}} custom-call(...)", 1_000_000, round(least[0] * 1e9)],
           [f"%flash_fwd_window.1 = bf16[28,{t},128]{{2,1,0}} custom-call(...)", 9_000_000, round(least[1] * 1e9)]]
    m = {"trace": _trace(ops, [["jit_insert(1)", 900_000, 9_000_000]], [])}
    assert reader.read(m, flash["params"], _ctx(logs)) == pytest.approx(100.0, abs=0.01)
    assert reader.read({"trace": _trace([], [], [])}, flash["params"], _ctx(logs)) is None


def test_the_cell_walks_on_the_cpu_with_its_own_checks():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    assert "REHEARSAL" in proc.stdout, proc.stdout[-3000:] + proc.stderr[-3000:]
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert checks and set(checks.values()) == {"ok"}, checks
    assert any(what.startswith("engine_logprob_rms") for what in checks)
    assert "experts_met=" in proc.stdout and "walked_window=" in proc.stdout
    # the control: an int8 arena holds half the bytes the configuration states
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert [what for what, verdict in checks.items() if verdict != "ok"] == [
        what for what in checks if what.startswith("bytes of the arrays the engine's pool holds")]
