"""`ling-3.0-flash-vl.rollout-reason` at the rehearsal size:
`bench/reference/ling_flash.py` against the program's `TransformerLM` on the
weights the benchmark makes from a seed; the configuration file against the
catalog's keys; the `serve_hybrid` job, which is `serve` with another count
of the pool's bytes; and a walk of the cell.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_ling.py -q
"""

import inspect
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
from benchlib.result import Checks  # noqa: E402

CELL = "ling-3.0-flash-vl.rollout-reason"
with open(os.path.join(BENCH, "configs", "ling-3.0-flash-vl.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
ref = load_module("reference/ling_flash.py")


@pytest.mark.parametrize("seed", [101, 3_000_000_203])
def test_reference_against_the_program_at_the_rehearsal_sizes(seed):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    extra = dict(CONFIG["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(CONFIG["rehearse"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, dtype=jnp.float32)
    model = CausalLMPolicy(cfg)
    t = jnp.zeros((1, 8), jnp.int32)
    params = weights.make_params(weights.param_shapes(model, t, jnp.ones_like(t)), seed, jnp.float32)
    rng = np.random.default_rng(seed)
    lens, width = (40, 33, 12), 40
    tokens = rng.integers(1, SIZES["vocab_size"], size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    tokens = tokens * mask
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))[0]
    got = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    assert np.abs(got - want)[valid].max() < 2e-5
    # the control: the reference in int8 is far from itself
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - want)[valid] ** 2)) > 1e-3
    # every departure the on-chip tool reads is one the comparison sees
    for name in load_module("tests/ling_onchip.py").DEPARTURES:
        departed = np.asarray(ref.logprobs(params["lm"], tokens, mask, dict(SIZES, departures=[name])))
        assert np.sqrt(np.mean((departed - want)[valid] ** 2)) > 1e-2, name


def test_the_configuration_file_states_the_cut_and_nothing_else_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "ling-3.0-flash-vl")
    reduced = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
               "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
    assert entry["reduced"] == CONFIG["reduced"] == reduced and entry["source"] == CONFIG["source"]
    held = {k: v for k, v in RAW.items() if k != "bench"}
    published = dict(held, num_hidden_layers=42, first_k_dense_replace=2, num_experts=512, vocab_size=157184)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash-VL")
        assert row["source_url"] == CONFIG["source"] and sorted(row["config"]) == sorted(held)
        assert {k for k in held if held[k] != row["config"][k]} == set(reduced)
        assert held["expert_swiglu_limit_list"] == row["config"]["expert_swiglu_limit_list"][:6] == [0] * 6
        published = row["config"]
    # every width, the router's groups and the experts a token as published
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_group", "topk_group",
                "num_experts_per_tok", "short_conv_kernel_size", "kda_lower_bound", "layer_group_size"):
        assert held[key] == published[key], key
    assert (held["num_hidden_layers"], held["first_k_dense_replace"], held["num_experts"], held["vocab_size"]) \
        == (6, 1, 64, 19648)
    assert sorted(CONFIG["assumed"]) == ["group_score", "kda_gate", "kda_output_gate", "kda_qk_norm", "mla_qk_norm",
                                         "rotary_layout", "shared_expert", "swiglu_limit"]
    assert "64 chips" in CONFIG["deployment"] and "no image token" in CONFIG["published"]["vision_tower"]
    assert CONFIG["precision"]["serve"]["recurrent_state"] == "float32"
    assert CONFIG["program"]["model_extra_configs"] == dict(vocab_size=19648, n_layers=6, moe_dense_layers=1,
                                                            moe_local_experts=64, attn_impl="flash")


def test_serve_hybrid_is_serve_but_for_the_count_of_the_pools_bytes_and_the_selection_bias():
    hybrid, serve = load_module("jobs/serve_hybrid.py"), load_module("jobs/serve.py")
    before = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    seen = {}
    original_run = serve.run
    serve.run = lambda ctx: seen.update(check=serve.check_kv_precision, weights=serve.weights) or "ran"
    try:
        ctx = types.SimpleNamespace(config={**CONFIG, "sizes": {"any": 1}}, log=print, rehearse=False,
                                    cell={"engine": {"max_prompt_len": 1024}},
                                    traffic={"output_len": {"max": 2048}, "rehearse": {"output_len": {"max": 12}}})
        assert hybrid.run(ctx) == "ran"
    finally:
        serve.run = original_run
    after = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    assert seen["check"] is hybrid.check_kv_precision
    assert {k for k in after if after[k] is not before.get(k)} == {"check_kv_precision"}
    # the rows that set the bias are as wide as the comparison's reference runs: its programs, no others
    assert isinstance(seen["weights"], hybrid.SeededBalanced) and seen["weights"].width == 1024 + 2048
    assert seen["weights"].sizes == {"any": 1} and "router_balance" not in CONFIG
    serve.check_kv_precision, serve.weights = before["check_kv_precision"], weights

    # the count: (blocks + 1) x 32 x 1 latent layer x 576 x 2 B + 128 slots x 5 layers x (2 MB + 73,728 B)
    sizes = {k: v for k, v in RAW.items() if k != "bench"}
    precision = CONFIG["precision"]["serve"]
    want = hybrid.stated_pool_bytes(12287, 32, 128, sizes, precision)
    state = 128 * 5 * 32 * 128 * 128 * 4
    assert want == 12288 * 32 * 576 * 2 + state + 128 * 5 * 3 * 3 * 4096 * 2
    ctx = types.SimpleNamespace(rehearse=False, config={"sizes": sizes, "reference": "ling_flash",
                                                        "precision": CONFIG["precision"]})
    engine = types.SimpleNamespace(total_blocks=12287, kv_block_size=32, num_slots=128)
    kv_planes = 12288 * 32 * (2 * 32 * 128 - 576) * 2  # keys and values by head in place of the latent plane
    for held, ok in ((want + 1_625_608, True), (want - state // 2, False), (want + kv_planes, False)):
        checks = Checks()
        hybrid.check_kv_precision(ctx, engine, None, held, checks)
        assert checks.ok is ok, held


def test_the_selection_bias_is_balanced_from_the_seed_and_no_other_leaf_moves():
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    hybrid = load_module("jobs/serve_hybrid.py")
    extra = dict(CONFIG["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(CONFIG["rehearse"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, dtype=jnp.float32)
    lines = []
    seeded = hybrid.SeededBalanced(SIZES, 512, lines.append)  # the reference alone makes the leaf: no model is handed in
    model = CausalLMPolicy(cfg)
    t = jnp.zeros((1, 8), jnp.int32)
    shapes = seeded.param_shapes(model, t, jnp.ones_like(t))
    plain = flatten_dict(weights.make_params(shapes, 3_000_000_203, jnp.float32))
    got = flatten_dict(seeded.make_params(shapes, 3_000_000_203, jnp.float32))
    again = flatten_dict(seeded.make_params(shapes, 3_000_000_203, jnp.float32))
    moved = sorted(k for k in plain if not np.array_equal(plain[k], got[k]))
    assert moved == [("lm", f"block_{i}", "mlp", "expert_bias", "bias") for i in (1, 2)]
    assert all(np.array_equal(got[k], again[k]) for k in got)  # the same seed, the same leaves
    # the line a run prints: the most chosen expert over an even share, a layer, before and after
    pairs = re.findall(r"\(([\d.]+), ([\d.]+)\)", lines[0])
    assert len(pairs) == 2 and all(float(after) <= 1.05 < float(before) for before, after in pairs), lines


def test_the_cell_walks_on_the_cpu_with_its_own_checks():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    # at the tiny widths, in bfloat16, over 48 tokens, one expert chosen otherwise on a near-tie moves the root
    # mean square past the chip's limit: the walk holds every other check, and the float32 comparison at this
    # size is tests/test_ling_flash.py's
    rms = [what for what in checks if what.startswith("engine_logprob_rms")]
    assert len(rms) == 1 and {ok for what, ok in checks.items() if what not in rms} == {"ok"}, proc.stdout[-3000:]
    assert any("1 latent planes a token and 2 layers' recurrent state" in what for what in checks)
    assert "trlx:engine.slot_state" in proc.stdout and "walked_latent=" in proc.stdout
    # the control: an int8 arena is refused by name, so the run ends without a result
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0 and "int8 arena" in proc.stderr and "over slot state" in proc.stderr
