"""`openpangu-ultra-moe-718b.rollout-longctx` at the rehearsal size:
`bench/reference/pangu_ultra_moe.py` against the program's `TransformerLM` on
the weights the benchmark makes from a seed; the `serve_latent` job, which
is `serve` with another count of the cache's bytes; and a walk of the cell.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_pangu.py -q
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

CELL = "openpangu-ultra-moe-718b.rollout-longctx"
with open(os.path.join(BENCH, "configs", "openpangu-ultra-moe-718b.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
ref = load_module("reference/pangu_ultra_moe.py")


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
        logits = model.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))[0]
    got = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    assert np.abs(got - want)[valid].max() < 1e-5
    # the control: the reference in int8 is far from itself, by more than the limit's floor
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - want)[valid] ** 2)) > 1e-3


def test_the_configuration_file_states_the_cut_and_nothing_else_moves():
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "openpangu-ultra-moe-718b")
    reduced = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers"]
    assert entry["reduced"] == CONFIG["reduced"] == reduced
    published = dict(attention_bias=False, first_k_dense_replace=3, hidden_act="silu", hidden_size=7680,
                     intermediate_size=18432, kv_lora_rank=512, max_position_embeddings=131072,
                     model_type="pangu_ultra_moe", moe_intermediate_size=2048, n_routed_experts=256,
                     n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
                     num_hidden_layers=61, num_key_value_heads=128, num_nextn_predict_layers=1, q_lora_rank=1536,
                     qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-05, rope_theta=25600000,
                     routed_scaling_factor=2.5, sandwich_norm=True, tie_word_embeddings=False, v_head_dim=128,
                     vocab_size=153600)
    held = {k: v for k, v in RAW.items() if k != "bench"}
    assert sorted(held) == sorted(published)
    assert {k for k in published if held[k] != published[k]} == set(reduced)
    assert (held["num_hidden_layers"], held["first_k_dense_replace"], held["n_routed_experts"],
            held["vocab_size"], held["num_nextn_predict_layers"]) == (5, 1, 8, 19200, 0)
    assert sorted(CONFIG["assumed"]) == ["moe_router", "multi_token_block", "rotary_layout", "shared_expert"]
    assert "32 chips sharing each layer" in CONFIG["deployment"]


def test_serve_latent_is_serve_but_for_the_count_of_the_caches_bytes():
    latent, serve = load_module("jobs/serve_latent.py"), load_module("jobs/serve.py")
    before = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    seen = {}
    original_run = serve.run
    serve.run = lambda ctx: seen.update(check=serve.check_kv_precision) or "ran"
    try:
        assert latent.run(types.SimpleNamespace()) == "ran"
    finally:
        serve.run = original_run
    after = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    assert seen["check"] is latent.check_kv_precision
    assert {k for k in after if after[k] is not before.get(k)} == {"check_kv_precision"}
    serve.check_kv_precision = before["check_kv_precision"]

    # the count: (blocks + 1) x block x 5 layers x 576 x 2 bytes; twice (K and V planes) and half (int8) refused
    sizes = {k: v for k, v in RAW.items() if k != "bench"}
    want = latent.stated_cache_bytes(12287, 32, sizes, "bfloat16")
    assert want == 12288 * 32 * 5 * 576 * 2
    ctx = types.SimpleNamespace(rehearse=False, config={"sizes": sizes, "reference": "pangu_ultra_moe",
                                                        "precision": CONFIG["precision"]})
    engine = types.SimpleNamespace(total_blocks=12287, kv_block_size=32)
    for held, ok in ((want + 2_434_824, True), (2 * want, False), (want // 2, False)):
        checks = Checks()
        latent.check_kv_precision(ctx, engine, None, held, checks)
        assert checks.ok is ok


def test_the_cell_walks_on_the_cpu_with_its_own_checks():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert checks and set(checks.values()) == {"ok"}
    assert any("one latent plane of 160 values a token" in what for what in checks)
    assert "walked_latent=" in proc.stdout and "bytes=" in proc.stdout
    # the control: an int8 latent arena is refused by name, so the run ends without a result
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0 and "int8 arena" in proc.stderr and "over a latent cache" in proc.stderr
