"""`falcon-h1-34b.rollout-chat` at the rehearsal size:
`bench/reference/falcon_h1.py` against the program's `TransformerLM` on the
weights the benchmark makes from a seed; the configuration file against the
catalog's keys; the `serve_parallel_hybrid` job, which is `serve` with another
count of the pool's bytes and three leaves a layer by the family's
initialisation; and a walk of the cell.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_falcon.py -q
"""

import inspect
import json
import os
import re
import subprocess
import sys
import types

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from benchlib import weights  # noqa: E402
from benchlib.files import load_module  # noqa: E402
from benchlib.result import Checks  # noqa: E402

CELL = "falcon-h1-34b.rollout-chat"
with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
PUBLISHED = {k: v for k, v in RAW.items() if k != "bench"}
ref = load_module("reference/falcon_h1.py")
job = load_module("jobs/serve_parallel_hybrid.py")


def rehearsal_model():
    import jax.numpy as jnp

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    extra = dict(CONFIG["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(CONFIG["rehearse"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, dtype=jnp.float32)
    model = CausalLMPolicy(cfg)
    t = jnp.zeros((1, 8), jnp.int32)
    return cfg, model, weights.param_shapes(model, t, jnp.ones_like(t))


def test_reference_against_the_program_at_the_rehearsal_sizes():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models import hf_interop

    seed = 3_000_000_203
    cfg, model, shapes = rehearsal_model()
    # the reference reads exactly the keys the program's own export gives
    assert {k: v for k, v in hf_interop.config_to_hf(cfg, "falcon_h1").items() if k in SIZES} == SIZES
    # steps thirty times the family's, as tests/test_falcon_h1.py: at a state of 16 the family's own show nothing
    params = job.family_leaves(weights.make_params(shapes, seed, jnp.float32), seed, (0.3, 3.0), (0.1, 1.0))
    rng = np.random.default_rng(seed)
    lens, width = (40, 33, 12), 40
    tokens = rng.integers(1, SIZES["vocab_size"], size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    tokens = tokens * mask
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t, m: model.apply({"params": p}, t, m)[0])(params, tokens, mask)
    real = mask.astype(bool)
    off = lambda got, want: float(np.abs(np.asarray(got) - np.asarray(want))[real].max() / np.abs(want).max())
    want = np.asarray(ref.logits(params["lm"], tokens, mask, SIZES))
    assert off(logits, want) < 2e-6  # float32 against float32, relative to the largest logit
    got = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    lp = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))  # the head a slice and a chunk at a time
    assert np.abs(got - lp)[valid].max() < 3e-6
    # the control: the reference in int8 is far from itself
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - lp)[valid] ** 2)) > 30 * np.sqrt(np.mean((got - lp)[valid] ** 2))
    # every departure the on-chip tool reads is one the comparison sees
    onchip = load_module("tests/falcon_onchip.py")
    for name, departure in onchip.DEPARTURES.items():
        other = np.asarray(ref.logits(params["lm"], tokens, mask, dict(SIZES, departures=[departure])))
        assert off(other, want) > (1e-5 if departure == "state_bf16" else 3e-3), name


def test_the_head_in_slices_is_the_head_whole(monkeypatch):
    """`head_logprobs` over 4 slices of the vocabulary and chunks of 8
    positions against `log_softmax` of the whole `head_logits`."""
    import jax
    import jax.numpy as jnp

    assert ref._head_slices(261120) == 8 and ref._head_slices(96) == 1
    monkeypatch.setattr(ref, "HEAD_COLUMNS", 128)
    monkeypatch.setattr(ref, "HEAD_POSITIONS", 8)
    key = jax.random.PRNGKey(5)
    h = jax.random.normal(key, (21, 64))
    head = {"kernel": jax.random.normal(jax.random.fold_in(key, 1), (64, 512)) * 0.125}
    ln = {"scale": jnp.ones((64,))}
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (21,), 0, 512)
    assert ref._head_slices(512) == 4
    with jax.default_matmul_precision("highest"):
        got = ref.head_logprobs.__wrapped__(h, ln, head, tokens, eps=1e-5, scale=0.5)
        whole = jax.nn.log_softmax(ref.head_logits(h, ln, head, eps=1e-5, scale=0.5), axis=-1)
    want = jnp.take_along_axis(whole[:-1], tokens[1:, None], axis=-1)[:, 0]
    assert got.shape == (20,) and float(jnp.abs(got - want).max()) < 2e-6


def test_the_configuration_file_states_the_cut_and_nothing_else_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"] == list(CONFIG["published"])
    assert entry["source"] == CONFIG["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
        assert row["source_url"] == CONFIG["source"] and sorted(row["config"]) == sorted(PUBLISHED)
        assert {k for k in PUBLISHED if PUBLISHED[k] != row["config"][k]} == {"num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 72
    assert PUBLISHED["num_hidden_layers"] == 4 and PUBLISHED["vocab_size"] == 261120
    assert sorted(CONFIG["assumed"]) == ["convolution", "d_skip", "dt", "gated_norm", "head_groups",
                                         "mamba_expand_and_mlp_expansion_factor", "multiplier_sites", "parallel_sum",
                                         "rope", "state_leaves"]
    assert all("chosen over" in CONFIG["assumed"][k] for k in ("multiplier_sites", "parallel_sum", "gated_norm",
                                                                "d_skip", "dt", "convolution"))
    assert "18 stages of four layers" in CONFIG["deployment"] and "no share-of-deployment test" in CONFIG["deployment"]
    assert "once every four layers" in CONFIG["deployment"]
    assert CONFIG["precision"]["serve"] | {"note": ""} == dict(
        weights="bfloat16", compute="bfloat16", kv_cache="bfloat16", recurrent_state="float32",
        conv_state="bfloat16", note="")
    assert CONFIG["program"] == dict(model_path="random:falcon-h1-34b", model_extra_configs=dict(
        vocab_size=261120, n_layers=4, attn_impl="flash"))
    # the cell, letter for letter as the issue names it
    cell = load_module("benchlib/files.py").load_json(f"workloads/{CELL}.json")
    assert {k: cell["engine"][k] for k in ("num_slots", "max_prompt_len", "max_prefill_batch", "prompt_bucket",
                                           "kv_block_size", "kv_pool_blocks", "kv_cache_dtype", "decode_kernel")} == dict(
        num_slots=128, max_prompt_len=1024, max_prefill_batch=1, prompt_bucket=256, kv_block_size=32,
        kv_pool_blocks=6144, kv_cache_dtype="bf16", decode_kernel="auto")
    assert cell["check"] == {"requests": 4} and cell["end_to_end"] == ["setup_s", "serve_tokens_per_s"]
    mix = load_module("benchlib/files.py").load_json("traffic/rollout-chat.json")
    assert (mix["pool"], mix["prompt_len"], mix["output_len"]["value"], mix["arrivals"], mix["ramp_seconds"],
            mix["drain_seconds"]) == (128, dict(dist="lognormal", median=256, sigma=0.7, min=64, max=1024), 512,
                                      dict(kind="backlog", depth=128), 10, 0)
    named = {e["name"] for e in bench["per_layer"] if CELL in e.get("workloads", ())}
    assert set(cell["per_layer"]) <= named and CELL in next(
        e for e in bench["end_to_end"] if e["name"] == "serve_tokens_per_s")["workloads"]


def test_serve_parallel_hybrid_is_serve_but_for_the_count_of_the_pools_bytes_and_three_leaves_a_layer():
    serve = load_module("jobs/serve.py")
    before = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    seen = {}
    original_run, original_weights = serve.run, serve.weights
    serve.run = lambda ctx: seen.update(check=serve.check_kv_precision, weights=serve.weights) or "ran"
    try:
        assert job.run(types.SimpleNamespace()) == "ran"
    finally:
        serve.run = original_run
    after = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    assert seen["check"] is job.check_kv_precision and isinstance(seen["weights"], job.SeededFamily)
    assert {k for k in after if after[k] is not before.get(k)} == {"check_kv_precision"}
    serve.check_kv_precision, serve.weights = before["check_kv_precision"], original_weights

    # the count: 6,145 blocks x 32 x 4 layers x 2 x 4 x 128 x 2 B + 128 slots x 4 layers x (4 MB + 3 x 5,120 x 2 B)
    precision = CONFIG["precision"]["serve"]
    want = job.stated_pool_bytes(6144, 32, 128, PUBLISHED, precision)
    state = 128 * 4 * 32 * 256 * 128 * 4
    assert want == 6145 * 32 * 4 * 2048 + state + 128 * 4 * 3 * 5120 * 2 == 1_610_874_880 + 2_163_212_288
    ctx = types.SimpleNamespace(rehearse=False, config={"sizes": PUBLISHED, "reference": "falcon_h1",
                                                        "precision": CONFIG["precision"]})
    engine = types.SimpleNamespace(total_blocks=6144, kv_block_size=32, num_slots=128)
    # its mask and tables; a bfloat16 recurrent state (1.07 GB fewer of 3.77); no keys and values at all
    for held, ok in ((want + 1_000_000, True), (want - state // 2, False), (want - 1_610_874_880, False)):
        checks = Checks()
        job.check_kv_precision(ctx, engine, None, held, checks)
        assert checks.ok is ok, held
    assert (state // 2) / want > 0.28


def test_three_leaves_a_layer_come_from_the_seed_by_the_family_s_rule_and_no_other_leaf_moves():
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    cfg, model, shapes = rehearsal_model()
    seeded = job.SeededFamily()
    plain = flatten_dict(weights.make_params(shapes, 3_000_000_203, jnp.bfloat16))
    got = flatten_dict(seeded.make_params(seeded.param_shapes(model, jnp.zeros((1, 8), jnp.int32),
                                                              jnp.ones((1, 8), jnp.int32)), 3_000_000_203, jnp.bfloat16))
    again = flatten_dict(seeded.make_params(shapes, 3_000_000_203, jnp.bfloat16))
    moved = sorted(k for k in plain if not np.array_equal(plain[k], got[k]))
    assert moved == sorted(("lm", f"block_{i}", "ssm", *leaf) for i in range(cfg.n_layers)
                           for leaf in (("a_log", "bias"), ("d", "scale"), ("dt_bias", "bias")))
    assert all(np.array_equal(got[k], again[k]) and got[k].dtype == plain[k].dtype for k in got)
    f32 = lambda k: np.asarray(got[k], np.float32)
    for i in range(cfg.n_layers):
        a = np.exp(f32(("lm", f"block_{i}", "ssm", "a_log", "bias")))
        dt = np.log1p(np.exp(f32(("lm", f"block_{i}", "ssm", "dt_bias", "bias"))))
        assert (0.99 <= a).all() and (a <= 16.1).all() and (9e-4 <= dt).all() and (dt <= 0.11).all()
        assert (f32(("lm", f"block_{i}", "ssm", "d", "scale")) == 1).all()
    other = flatten_dict(seeded.make_params(shapes, 11, jnp.bfloat16))
    assert not np.array_equal(other[("lm", "block_0", "ssm", "a_log", "bias")], got[("lm", "block_0", "ssm", "a_log", "bias")])


def test_the_cell_walks_on_the_cpu_with_its_own_checks():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    assert checks and set(checks.values()) == {"ok"}, proc.stdout[-3000:]
    assert any(what.startswith("engine_logprob_rms") for what in checks)
    assert any("2 layers' keys and values by head a token AND recurrent state" in what for what in checks)
    for span in ("trlx:engine.slot_state", "trlx:engine.prefill_state", "trlx:engine.kv_walk"):
        assert span in proc.stdout, span
    # the control: an int8 arena is refused by name, so the run ends without a result
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0 and "int8 arena" in proc.stderr and "over slot state" in proc.stderr
    assert "ssm_attention layers keep state, tails a slot" in proc.stderr
