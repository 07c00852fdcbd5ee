"""`dots3-note-prev.rollout-longdoc` at the rehearsal size:
`bench/reference/dots3_note.py` against the program's `TransformerLM` on the
weights the benchmark makes from a seed; the `serve_sparse_latent` job, which
is `serve` with another count of the cache's bytes; the configuration file's
cut; and a walk of the cell.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_dots3.py -q
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

CELL = "dots3-note-prev.rollout-longdoc"
with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
    RAW = json.load(f)
CONFIG = RAW["bench"]
SIZES = CONFIG["rehearse_sizes"]
ref = load_module("reference/dots3_note.py")


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
    assert np.abs(got - want)[valid].max() < 1e-5
    # the control: the reference in int8 is far from itself, by more than the limit's floor
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    assert np.sqrt(np.mean((control - want)[valid] ** 2)) > 1e-3
    # and a departure is far from the reference: the index left out
    dense = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, departure="no_index"))
    assert np.abs(dense - want)[valid].max() > 1e-2


def test_the_configuration_file_states_the_cut_and_nothing_else_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-prev")
    reduced = ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == CONFIG["reduced"] == reduced and entry["source"] == CONFIG["source"]
    period = ["full_attention"] + ["sliding_attention"] * 3
    published = dict(
        apply_mla_qkv_lora_rescale=True, attention_bias=False, attention_gate_type="headwise",
        first_k_dense_replace=1, hidden_act="silu", hidden_size=5120, index_head_dim=128, index_n_heads=64,
        index_topk=2048, intermediate_size=13824, kv_lora_rank=512,
        layer_types=["full_attention"] + period * 11 + ["full_attention"], max_position_embeddings=524288,
        model_type="dots3_note", moe_intermediate_size=1536, moe_layer_freq=1, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128, num_experts_per_tok=8,
        num_hidden_layers=46, num_key_value_heads=128, q_lora_rank=1024, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=80000000, routed_scaling_factor=1, scoring_func="sigmoid",
        sliding_window_size=513, swa_attention_gate_type="headwise", swa_kv_lora_rank=1024,
        swa_num_attention_heads=64, swa_num_key_value_heads=64, swa_q_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_rope_theta=50000, swa_v_head_dim=128, tie_word_embeddings=False,
        topk_method="noaux_tc", v_head_dim=128, vocab_size=152064)
    assert len(published["layer_types"]) == 46 and published["layer_types"].count("full_attention") == 13
    held = {k: v for k, v in RAW.items() if k != "bench"}
    assert sorted(held) == sorted(published)
    assert {k for k in published if held[k] != published[k]} == set(reduced)
    assert (held["num_hidden_layers"], held["n_routed_experts"], held["vocab_size"]) == (5, 32, 19008)
    # the published layers 0, 2, 3, 4, 5: the leading dense layer and one whole period behind it
    assert held["layer_types"] == [published["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert held["vocab_size"] * 8 == published["vocab_size"] and held["n_routed_experts"] * 8 == 256
    assert sorted(CONFIG["assumed"]) == ["index", "index_input", "index_ties", "latent_norm_scales", "mla_rescale", "not_run",
                                         "rotary_layout", "router", "swa_gate", "tensor_names", "window"]
    assert "8 chips sharing each layer" in CONFIG["deployment"] and CONFIG["parameters_held"] == 4_087_154_176
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", "rollout-longdoc", 1)
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        engine = json.load(f)["engine"]
    assert {k: engine[k] for k in ("num_slots", "max_prompt_len", "max_prefill_batch", "prompt_bucket",
                                   "kv_block_size", "kv_pool_blocks", "kv_cache_dtype", "decode_kernel")} == dict(
        num_slots=16, max_prompt_len=24576, max_prefill_batch=1, prompt_bucket=4096, kv_block_size=32,
        kv_pool_blocks=12544, kv_cache_dtype="bf16", decode_kernel="auto")
    # every new metric has its file, and the cell stands on the end-to-end metric it reports
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
                spec = json.load(f)
            assert (spec["unit"], spec["layer"], spec["moves"]) == (m["unit"], m["layer"], m["moves"])


def test_serve_sparse_latent_is_serve_but_for_the_count_of_the_caches_bytes():
    job, serve = load_module("jobs/serve_sparse_latent.py"), load_module("jobs/serve.py")
    before = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    seen = {}
    original_run, original_weights = serve.run, serve.weights
    serve.run = lambda ctx: seen.update(check=serve.check_kv_precision, weights=serve.weights) or "ran"
    try:
        assert job.run(types.SimpleNamespace(rehearse=True, config={"rehearse_sizes": SIZES})) == "ran"
    finally:
        serve.run, serve.weights = original_run, original_weights
    after = {k: v for k, v in vars(serve).items() if inspect.isfunction(v) or inspect.isclass(v)}
    assert seen["check"] is job.check_kv_precision and isinstance(seen["weights"], job.SeededTrainedNorms)
    assert {k for k in after if after[k] is not before.get(k)} == {"check_kv_precision"}
    serve.check_kv_precision = before["check_kv_precision"]

    # the leaves it serves: the seed's, the two latents' norms' scales divided by the rescale's factor
    import jax.numpy as jnp

    model = _model()
    t = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(model, t, jnp.ones_like(t))
    plain, served = weights.make_params(shapes, 7, jnp.float32), seen["weights"].make_params(shapes, 7, jnp.float32)
    import jax

    same = lambda x, y: all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(lambda u, v: bool((u == v).all()), x, y)))
    for i, kind in enumerate(SIZES["layer_types"]):
        a, b = plain["lm"][f"block_{i}"], served["lm"][f"block_{i}"]
        ranks = {"q_a_norm": 24, "kv_a_norm": 48 if kind == "sliding_attention" else 32}
        for leaf, rank in ranks.items():
            np.testing.assert_allclose(np.asarray(b["attn"][leaf]["scale"]),
                                       np.asarray(a["attn"][leaf]["scale"]) * (rank / 64) ** 0.5, rtol=1e-6)
        assert same({k: v for k, v in a["attn"].items() if k not in ranks},
                    {k: v for k, v in b["attn"].items() if k not in ranks})
        assert same(a["mlp"], b["mlp"]) and same(a["ln_attn"], b["ln_attn"]) and same(a["ln_mlp"], b["ln_mlp"])
    assert same(served["lm"]["lm_head"], plain["lm"]["lm_head"]) and same(served["lm"]["ln_f"], plain["lm"]["ln_f"])

    # the count: (blocks + 1) x block x (2 x (576 + 128) + 3 x 1088) x 2 bytes
    sizes = {k: v for k, v in RAW.items() if k != "bench"}
    assert job.cached_values_per_token(sizes) == 2 * (576 + 128) + 3 * 1088 == 4672
    want = job.stated_cache_bytes(12543, 32, sizes, "bfloat16")
    assert want == 12544 * 32 * 4672 * 2
    ctx = types.SimpleNamespace(rehearse=False, config={"sizes": sizes, "reference": "dots3_note",
                                                        "precision": CONFIG["precision"]})
    engine = types.SimpleNamespace(total_blocks=12543, kv_block_size=32)
    by_head = 12544 * 32 * 2 * (2 * 128 * (192 + 128) + 3 * 64 * (256 + 128))  # keys and values by head
    no_index = 12544 * 32 * 2 * (2 * 576 + 3 * 1088)  # the index keys dropped
    all_576 = 12544 * 32 * 2 * (2 * (576 + 128) + 3 * 576)  # the sliding layers at the full layers' width
    for held, ok in ((want + 2_434_824, True), (by_head, False), (no_index, False), (all_576, False),
                     (want // 2, False)):
        checks = Checks()
        job.check_kv_precision(ctx, engine, None, held, checks)
        assert checks.ok is ok, held


def test_the_cell_walks_on_the_cpu_with_its_own_checks():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000007",
           "--seconds", "3", "--trace", "2", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
                          text=True, timeout=900)
    assert "REHEARSAL" in proc.stdout, proc.stdout[-3000:] + proc.stderr[-3000:]
    checks = dict(re.findall(r"\[bench\] check (.*?): \S+ \(limit .*?\) (ok|NOT CORRECT)", proc.stdout))
    # at the rehearsal's size an index that keeps 6 of some 30 positions changes its mind under bfloat16
    # rounding, and each change weighs a sixth of a layer's attention: the logprob check is the chip's to
    # pass (PERF.md section 2); every other check holds here
    others = {what: verdict for what, verdict in checks.items() if not what.startswith("engine_logprob_rms")}
    assert others and set(others.values()) == {"ok"} and len(others) == len(checks) - 1
    assert any("index keys of 280 values a token" in what for what in checks)
    assert "index_chosen=" in proc.stdout and "bytes_dense_full=" in proc.stdout
    # the control: an int8 latent arena is refused by name, so the run ends without a result
    proc = subprocess.run(cmd + ["--control"], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0 and "int8 arena" in proc.stderr and "over a latent cache" in proc.stderr
