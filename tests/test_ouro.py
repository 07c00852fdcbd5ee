"""A looped stack (Ouro's LoopLM: ONE stack of layers under sandwich norms run
`loop_steps` times a token over the same weights, the final norm after every
pass, an exit gate on each pass's output, every (pass, layer) keeping keys and
values of its own) against the benchmark's plain reference
`bench/reference/ouro.py`, at test size on the CPU, on seeded weights.

Tolerances. Float32 program against float32 reference, both at `highest`:
2e-4 on a logit or a logprob, some tens of float32 roundings through 4 passes
of 2 layers (the readings are 1e-6 to 1e-5); a program whose passes all read
pass 0's keys and values moves a logprob by 1e-2 or more at this size."""

import json
import os
import sys
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

from benchlib.files import load_module  # noqa: E402

from parity import jitted_forward, jitted_init  # noqa: E402
from trlx_tpu.models import CausalLMPolicy, config_from_preset, hf_interop, resolve_split  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, TransformerConfig, TransformerLM, exit_distribution, init_kv_cache, init_paged_kv_arena)
from trlx_tpu.ops import paged_attention  # noqa: E402

VOCAB = 96
TOL = 2e-4
ROWS, WIDTH = 3, 32
ref = load_module("reference/ouro.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    return config_from_preset("ouro-tiny", VOCAB, **{"dtype": jnp.float32, **kw})


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    return hf_interop.config_to_hf(cfg, "ouro")


def seeded_params(model, seed):
    """Every leaf drawn from the seed, the norms' scales off 1 and the gate's bias off 0."""
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = jitted_init(model)(jax.random.PRNGKey(seed), tokens, jnp.ones_like(tokens))["params"]
    rng = np.random.default_rng(seed)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "scale":
            return jnp.asarray(1 + 0.05 * rng.normal(size=x.shape), x.dtype)
        if names[-2:] == ["exit_gate", "bias"]:
            return jnp.asarray([-0.7], x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def left_padded(rng, lens, width=WIDTH):
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


@pytest.fixture(scope="module")
def lm_params():
    return seeded_params(TransformerLM(tiny_cfg()), 5)


def test_presets_state_the_published_sizes():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        bench = json.load(f)
    cfg = config_from_preset("ouro-2.6b", bench["vocab_size"])
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff) == tuple(
        bench[k] for k in ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                           "head_dim", "intermediate_size"))
    assert (cfg.loop_steps, cfg.loop_gate, cfg.loop_exit_threshold) == (bench["total_ut_steps"], True, 1.0)
    assert cfg.sandwich_norm and not cfg.tie_embeddings and not cfg.use_bias and cfg.rope_theta == bench["rope_theta"]
    assert bench["bench"]["reduced"] == []
    # what a token caches: K and V of 16 heads of 128 in each of 4 x 48 (pass, layer) planes, 1,572,864 B in bfloat16
    assert len(cfg.cache_planes(0)) == 2 * 4 and cfg.cached_values_per_token * 2 == 1_572_864
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                            jnp.ones((1, 8), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == bench["bench"]["parameters_held"]
    assert {"ouro-2.6b", "ouro-tiny"} <= set(PRESETS) and PRESETS["ouro-tiny"]["loop_steps"] == 4


@pytest.mark.parametrize("passes", [4, 2])
def test_forward_matches_the_reference_in_logits_and_exit_distribution(passes, lm_params):
    cfg = tiny_cfg(loop_steps=passes)
    tokens, mask = left_padded(np.random.default_rng(passes), (32, 9, 20))
    with jax.default_matmul_precision("highest"):
        logits, _, caps = jax.jit(lambda p, t, m: TransformerLM(cfg).apply(
            {"params": p}, t, m, method=TransformerLM.forward, exit_pdf=True))(lm_params, tokens, mask)
    real = mask.astype(bool)
    want = np.asarray(ref.logits(lm_params, tokens, mask, sizes_of(cfg)))
    assert np.abs(np.asarray(logits) - want)[real].max() < TOL
    pdf = np.asarray(caps["exit_pdf"])
    assert pdf.shape == (3, WIDTH, passes) and np.abs(pdf.sum(-1) - 1).max() < 1e-5
    assert np.abs(pdf - np.asarray(ref.exit_pdf(lm_params, tokens, mask, sizes_of(cfg))))[real].max() < 1e-5
    assert 0.05 < pdf[real][:, 0].mean() < 0.95  # the gate decides something on these weights
    # what each pass adds: the same weights, one pass fewer, is another function
    fewer = np.asarray(ref.logits(lm_params, tokens, mask, {**sizes_of(cfg), "total_ut_steps": passes - 1}))
    assert np.abs(fewer - want)[real].max() > 100 * TOL


def test_the_exit_distribution_is_the_published_rule():
    logits = jnp.asarray([[0.3], [-1.2], [2.0], [0.5]])
    lam = 1 / (1 + np.exp(-np.asarray(logits)[:, 0]))
    want = [lam[0], lam[1] * (1 - lam[0]), lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    np.testing.assert_allclose(np.asarray(exit_distribution(logits))[0], want, rtol=1e-6)


def test_one_pass_is_the_unlooped_sandwich_model_bit_for_bit(lm_params):
    """`loop_steps` 1 leaves the path every other family runs: the same leaves (less the gate)
    under a configuration that names no loop give the same bits, and no scan is traced."""
    once = tiny_cfg(loop_steps=1, loop_gate=False)
    plain_kw = {k: v for k, v in PRESETS["ouro-tiny"].items() if not k.startswith("loop_")}
    unlooped = TransformerConfig(vocab_size=VOCAB, dtype=jnp.float32, **plain_kw)
    assert once == unlooped
    leaves = {k: v for k, v in lm_params.items() if k != "exit_gate"}
    tokens, mask = left_padded(np.random.default_rng(0), (32, 11, 4))
    np.testing.assert_array_equal(jitted_forward(once)(leaves, tokens, mask), jitted_forward(unlooped)(leaves, tokens, mask))
    text = str(jax.make_jaxpr(lambda p: TransformerLM(once).apply({"params": p}, tokens, mask)[0])(leaves))
    assert "scan" not in text and "while" not in text
    looped = str(jax.make_jaxpr(lambda p: TransformerLM(tiny_cfg()).apply({"params": p}, tokens, mask)[0])(lm_params))
    assert looped.count("scan[") == 1  # ONE traced body, whatever the passes
    cache = init_kv_cache(once, 2, 16)
    assert cache["layers"][0]["k"].shape == (2, 16, 4, 16) and len(once.cache_planes(0)) == 2


def cached_logits(cfg, params, tokens, lens, cache, chunk, **step_kw):
    """Right-padded rows of `lens` tokens through `decode_step`: a prefill of `chunk` columns, then one
    column a step, the logits of every real position put together [rows, width, vocab]."""
    step = jax.jit(lambda p, t, c, m: TransformerLM(cfg).apply({"params": p}, t, c, m,
                                                                method=TransformerLM.decode_step, **step_kw))
    width = tokens.shape[1]
    valid = (np.arange(width)[None, :] < np.asarray(lens)[:, None]).astype(np.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        logits, _, cache = step(params, tokens[:, :chunk], cache, valid[:, :chunk])
        out.append(logits)
        for i in range(chunk, width):
            logits, _, cache = step(params, tokens[:, i:i + 1], cache, valid[:, i:i + 1])
            out.append(logits)
    return np.concatenate(out, axis=1), cache


def test_prefill_then_decode_through_the_dense_cache_is_the_full_forward(lm_params):
    cfg = tiny_cfg()
    rng = np.random.default_rng(2)
    tokens = rng.integers(1, VOCAB, size=(2, 14)).astype(np.int32)
    cache = {**init_kv_cache(cfg, 2, 16), "row_index": jnp.zeros((2,), jnp.int32)}
    del cache["index"]
    got, cache = cached_logits(cfg, lm_params, tokens, (14, 14), cache, chunk=6)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jitted_forward(cfg)(lm_params, tokens, np.ones_like(tokens)))
    assert np.abs(got - want).max() < TOL
    # every (pass, layer) plane is written, and two passes' planes of one layer differ
    for layer in cache["layers"]:
        k = np.asarray(layer["k"])  # [passes, rows, columns, heads, head width]
        assert k.shape[0] == 4 and all(np.abs(k[t, :, :14]).min(axis=(-1, -2)).min() > 0 for t in range(4))
        assert all(np.abs(k[t] - k[0]).max() > 1e-2 for t in (1, 2, 3)) and not k[:, :, 14:].any()


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_prefill_then_decode_through_the_paged_arena_is_the_full_forward(kernel, lm_params):
    cfg = tiny_cfg()
    rng = np.random.default_rng(3)
    lens, blk, pool = (14, 9), 4, 9
    tokens = rng.integers(1, VOCAB, size=(2, 14)).astype(np.int32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)  # block 0 is the zero block
    arena = init_paged_kv_arena(cfg, pool, blk, dtype=jnp.float32)
    assert arena[0]["k"].shape == (4 * pool, 4, blk, 16)  # a pool a pass, end to end
    cache = {"layers": [{**layer, "table": table} for layer in arena], "mask": jnp.zeros((2, 16), jnp.int32),
             "pos": jnp.zeros((2,), jnp.int32), "row_index": jnp.zeros((2,), jnp.int32)}
    # the prefill scores against the gathered table; the steps after it ride the kernel (interpreted) or the gather
    valid = (np.arange(14)[None, :] < np.asarray(lens)[:, None]).astype(np.int32)
    prefill = jax.jit(lambda p, t, c, m: TransformerLM(cfg).apply({"params": p}, t, c, m,
                                                                   method=TransformerLM.decode_step))
    step = jax.jit(lambda p, t, c, m: TransformerLM(cfg).apply({"params": p}, t, c, m, attn_kernel=kernel,
                                                                method=TransformerLM.decode_step))
    with jax.default_matmul_precision("highest"):
        logits, _, cache = prefill(lm_params, tokens[:, :6], cache, valid[:, :6])
        out = [logits]
        for i in range(6, 14):
            logits, _, cache = step(lm_params, tokens[:, i:i + 1], cache, valid[:, i:i + 1])
            out.append(logits)
        want = np.asarray(jitted_forward(cfg)(lm_params, tokens, valid))
    got = np.concatenate(out, axis=1)
    assert np.abs(got - want)[valid.astype(bool)].max() < TOL
    for layer in cache["layers"]:
        k = np.asarray(layer["k"]).reshape(4, pool, 4, blk, 16)
        assert not k[:, 0].any()  # every pass's zero block stays zero
        assert all(np.abs(k[t, 1:4]).max() > 0 and np.abs(k[t, 1:4] - k[0, 1:4]).max() > 1e-2 for t in (1, 2, 3))
        assert not k[:, 8, :, 1:].any()  # row 1 holds 9 positions: one column of its third block


def test_a_program_that_reads_pass_0s_planes_in_every_pass_misses_the_tolerance(lm_params, monkeypatch):
    """Both ways round: the reference with that departure against the sound program, and the program
    made to read and write through pass 0's table in every pass against the sound reference."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(4), (32, 17, 25))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(plain.logprobs_of_next(jitted_forward(cfg)(lm_params, tokens, mask), jnp.asarray(tokens)))
    real = mask[:, 1:].astype(bool)
    sound = np.asarray(ref.logprobs(lm_params, tokens, mask, sizes_of(cfg)))
    shared = np.asarray(ref.logprobs(lm_params, tokens, mask, sizes_of(cfg), departure="pass0_kv"))
    assert np.abs(got - sound)[real].max() < TOL < 1e-2 < np.abs(got - shared)[real].max()
    for departure in ("no_pass_norm", "no_sandwich"):
        other = np.asarray(ref.logprobs(lm_params, tokens, mask, sizes_of(cfg), departure=departure))
        assert np.abs(got - other)[real].max() > 1e-2

    sound_table = paged_attention.pass_table
    monkeypatch.setattr(paged_attention, "pass_table", lambda table, t, passes, blocks: sound_table(table, 0, passes, blocks))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (13, 6)]
    params = {"lm": lm_params}
    with jax.default_matmul_precision("highest"):
        _, out, lps = run_engine(cfg, params, prompts, 6, decode_kernel="xla")
    assert max(engine_errors(cfg, params, prompts, out, lps)) > 1e-2


def test_the_gradient_through_the_looped_forward_is_the_references(lm_params):
    """A cross-entropy through `forward`: each shared leaf's gradient is the sum of its four passes'
    contributions, as `jax.grad` of the plain Python loop gives it."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(6), (16, 9), width=16)
    # a position that predicts and the token it predicts both real: a padded position's state is an average over
    # keys it may not see, which the program's additive mask and the reference's `where` differentiate differently
    weight = jnp.asarray(mask[:, :-1] * mask[:, 1:], jnp.float32)

    def loss_of(logprobs):
        return -(logprobs * weight).sum() / weight.sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: loss_of(plain.logprobs_of_next(
            TransformerLM(cfg).apply({"params": p}, tokens, mask)[0], jnp.asarray(tokens)))))(lm_params)
        want = jax.grad(lambda p: loss_of(ref.logprobs(p, tokens, mask, sizes_of(cfg))))(lm_params)
    got, want = (jax.tree_util.tree_leaves_with_path(g) for g in (got, want))
    for (path, a), (_, b) in zip(got, want):
        if "exit_gate" in jax.tree_util.keystr(path):  # the logits do not read the gate
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        scale = np.abs(np.asarray(b)).max()
        assert scale > 0 and np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-4 * max(scale, 1e-3), path


def run_engine(cfg, params, prompts, max_new, **engine_kw):
    """Every prompt through `Scheduler` over a paged `InferenceEngine` to `max_new`
    tokens: the engine, and per request its tokens and the logprobs it reports."""
    from trlx_tpu.inference import InferenceEngine, Scheduler
    from trlx_tpu.ops.sampling import GenerationConfig

    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    engine = InferenceEngine(CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=3, kv_paging=True,
                             num_slots=len(prompts), max_prompt_len=32, max_prefill_batch=1, prompt_bucket=16,
                             **{"kv_block_size": 4, **engine_kw})
    scheduler = Scheduler(engine, max_queue_depth=8).start()
    try:
        requests = [scheduler.submit(p, max_new_tokens=max_new) for p in prompts]
        assert all(r.wait(120) for r in requests)
    finally:
        scheduler.stop()
    return engine, [r.token_ids for r in requests], [r.token_logprobs for r in requests]


def engine_errors(cfg, params, prompts, out, got):
    seqs = [np.concatenate([p, np.asarray(new, np.int32)]) for p, new in zip(prompts, out)]
    tokens = np.zeros((ROWS, WIDTH), np.int32)
    mask = np.zeros_like(tokens)
    for r, seq in enumerate(seqs):
        tokens[r, :len(seq)], mask[r, :len(seq)] = seq, 1
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, sizes_of(cfg)))
    return [np.abs(np.asarray(lps) - want[r, len(p) - 1:len(p) - 1 + len(lps)]).max()
            for r, (p, lps) in enumerate(zip(prompts, got))]


@pytest.mark.parametrize("kernel", ["xla", "auto", "auto-heads128"])
def test_the_engine_through_the_scheduler_matches_the_reference(kernel, lm_params, monkeypatch):
    """Two requests: four passes over each prompt into the arena's four pools, then paged decode
    (the gather path; the kernel, interpreted, in 8 calls a step), against the plain reference.
    With heads of 128 in blocks of 8 rows the kernel also WRITES the step's keys and values, pass
    by pass through `pass_table` under the scan (`kv_kernel_writes`); the preset's heads of 16 keep
    `paged_kv_write` in front of it."""
    monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    kernel, _, wide = kernel.partition("-")
    cfg, params, engine_kw = tiny_cfg(), {"lm": lm_params}, {}
    if wide:
        cfg, engine_kw = tiny_cfg(n_heads=2, n_kv_heads=2, head_width=128), dict(kv_block_size=8)
        params = {"lm": seeded_params(TransformerLM(cfg), 5)}
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (21, 6)]
    with jax.default_matmul_precision("highest"):
        engine, out, got = run_engine(cfg, params, prompts, 9, decode_kernel=kernel, **engine_kw)
    assert engine.decode_path == ("xla" if kernel == "xla" else "interpret")
    assert [len(lps) for lps in got] == [9, 9] and max(engine_errors(cfg, params, prompts, out, got)) < TOL
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and 0.0 < engine._loop_exit_early < 1.0
    assert stats["kv_kernel_writes"] == (stats["kv_kernel_dispatches"] if wide else 0)
    assert (stats["kv_kernel_dispatches"] > 0) == (kernel != "xla")


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_pool_counts_a_plane_a_pass_and_a_row_counts_blocks_whatever_the_passes(passes, lm_params):
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.observability.hbm import paged_arena_bytes
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg(loop_steps=passes, loop_gate=passes > 1)
    leaves = lm_params if passes > 1 else {k: v for k, v in lm_params.items() if k != "exit_gate"}
    gen_cfg = GenerationConfig(max_new_tokens=8, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    before = sum(a.nbytes for a in jax.live_arrays())
    engine = InferenceEngine(CausalLMPolicy(cfg), cfg, {"lm": leaves}, gen_cfg, kv_paging=True, num_slots=2,
                             max_prompt_len=16, max_prefill_batch=1, prompt_bucket=8, kv_block_size=4,
                             kv_pool_blocks=12, decode_kernel="xla")
    held = sum(a.nbytes for a in jax.live_arrays()) - before
    # (blocks to hand out + the zero block) x block x passes x layers x K and V x heads x head width x 4 B
    assert engine.total_blocks == 11
    want = (11 + 1) * 4 * passes * 2 * 2 * 4 * 16 * 4
    assert engine.kv_stats()["kv_pool_bytes"] == paged_arena_bytes(cfg, 12, 4, jnp.float32) == want
    assert 0 <= held - want < 2048  # the pool's masks, tables and counters
    assert engine.kv_stats()["kv_bytes_per_token"] == passes * 2 * 2 * 4 * 16 * 4
    assert engine.projected_blocks(np.arange(1, 14), 8) == 6  # 21 positions over blocks of 4, whatever the passes
    engine.insert_requests([(np.arange(1, 10, dtype=np.int32), 8)], [0])
    walk = engine._kv_walk()
    assert walk["layers"] == 2 * passes and walk["resident"] == 10 * 2 * passes
    assert walk["bytes"] == walk["walked_full"] * 2 * 4 * 16 * 4 and walk["walked_full"] == 2 * passes * engine._n_tbl * 4


def test_hf_config_keys_and_tensor_names_round_trip(tmp_path):
    """The benchmark file's published keys give the program's configuration and come back; a random
    state dict under the family's tensor names loads into the tree and goes out again letter for
    letter (unchecked against the published weights: `assumed`)."""
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        bench = json.load(f)
    published = {k: v for k, v in bench.items() if k != "bench"}
    assert sorted(bench["bench"]["assumed"]) == [
        "exit_gate", "kv_per_pass_layer", "no_bias_no_qk_norm", "norm_between_passes", "rope", "sandwich_norms",
        "tensor_names"]
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    assert cfg == config_from_preset("ouro-2.6b", published["vocab_size"], hf_family="ouro", dtype=jnp.float32)
    again = hf_interop.config_to_hf(cfg)
    assert {k: again[k] for k in published} == published and again["architectures"] == ["OuroForCausalLM"]
    for key, value, match in (("use_sliding_window", True, "use_sliding_window=True"),
                              ("sliding_window", 4096, "sliding_window=4096"),
                              ("layer_types", ["sliding_attention"] * 48, "layer_types"),
                              ("rope_scaling", {"type": "yarn"}, "rope_scaling=")):
        with pytest.raises(NotImplementedError, match=f"ouro with .*{match}"):
            hf_interop._ouro_kwargs({**published, key: value})
    (tmp_path / "config.json").write_text(json.dumps({**published, "early_exit_threshold": 0.9}))
    with pytest.raises(NotImplementedError, match="loop_exit_threshold 0.9 < 1"):
        hf_interop.config_from_hf(str(tmp_path))

    tiny = tiny_cfg(hf_family="ouro")
    assert hf_interop.infer_family(tiny_cfg()) == "ouro"
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jitted_init(CausalLMPolicy(tiny))(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    rng = np.random.default_rng(0)
    names = hf_interop.params_to_hf_state_dict(template, tiny)
    sd = {k: rng.normal(size=v.shape).astype(np.float32) for k, v in names.items()}
    layer = "model.layers.1."
    assert {layer + n + ".weight" for n in (
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm", "post_attention_layernorm_2",
        "self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj", "self_attn.o_proj", "mlp.gate_proj",
        "mlp.up_proj", "mlp.down_proj")} <= set(sd)
    assert {"model.embed_tokens.weight", "model.norm.weight", "lm_head.weight", "model.early_exit_gate.weight",
            "model.early_exit_gate.bias"} <= set(sd)
    assert sd["model.early_exit_gate.weight"].shape == (1, 64) and len(sd) == 2 * 11 + 5
    lm = hf_interop._load_ouro(sd, tiny)
    jax.tree_util.tree_map(lambda t, a: np.testing.assert_equal(t.shape, np.shape(a)), template["lm"], lm)
    back = hf_interop.params_to_hf_state_dict({"lm": lm}, tiny)
    assert sorted(back) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k])


LOOPED = dict(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64, loop_steps=2)


@pytest.mark.parametrize("fields, error, match", [
    (dict(loop_exit_threshold=0.5), NotImplementedError, "loop_exit_threshold 0.5 < 1 .early_exit_threshold"),
    (dict(moe_experts=4), NotImplementedError, "looped stack .* with moe_experts"),
    (dict(mtp_layers=1), NotImplementedError, "looped stack .* with mtp_layers"),
    (dict(prompt_tokens=4), NotImplementedError, "looped stack .* with prompt_tokens"),
    (dict(prefix_tokens=4), NotImplementedError, "looped stack .* with prefix_tokens"),
    (dict(layer_types=("conv", "attention"), conv_kernel=3), NotImplementedError,
     "looped stack .* with layers that keep anything but K and V by head"),
    (dict(loop_steps=0), ValueError, "loop_steps 0 must be >= 1"),
    (dict(loop_steps=1, loop_gate=True), ValueError, "loop_gate needs loop_steps > 1"),
])
def test_what_a_looped_stack_cannot_run_is_refused_by_name_in_the_configuration(fields, error, match):
    with pytest.raises(error, match=match):
        TransformerConfig(**{**LOOPED, **fields})


def test_what_assumes_one_pass_is_refused_by_name(lm_params):
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.trainer.pipelined_mixin import PipelinedCausalMixin
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    cfg, lm = tiny_cfg(), TransformerLM(tiny_cfg())
    tokens, mask = left_padded(np.random.default_rng(8), (8, 5), width=8)
    assert resolve_split(cfg, -1) == 0
    for unfrozen in (0, 1):  # the hydra split and the frozen-trunk cache
        with pytest.raises(NotImplementedError, match=f"num_layers_unfrozen={unfrozen} .the hydra split, the "
                                                      "frozen-trunk cache.* over a looped stack"):
            resolve_split(cfg, unfrozen)
    for kw in (dict(start=1), dict(stop=1), dict(capture=(1,))):
        with pytest.raises(NotImplementedError, match="a looped stack .* runs whole: a forward from, to or capturing"):
            jax.eval_shape(lambda p: lm.apply({"params": p}, tokens, mask, method=TransformerLM.forward, **kw),
                           lm_params)
    cache = {**init_kv_cache(cfg, 2, 16), "row_index": jnp.zeros((2,), jnp.int32)}
    with pytest.raises(NotImplementedError, match="a looped stack .* runs whole: a cached step capturing"):
        jax.eval_shape(lambda p: lm.apply({"params": p}, tokens, cache, mask,
                                          method=TransformerLM.decode_step, capture_split=1), lm_params)
    with pytest.raises(ValueError, match="exit_pdf=True needs a looped stack"):
        once = TransformerLM(tiny_cfg(loop_steps=1, loop_gate=False))
        jax.eval_shape(lambda p: once.apply({"params": p}, tokens, mask, method=TransformerLM.forward,
                                            exit_pdf=True), {k: v for k, v in lm_params.items() if k != "exit_gate"})
    gen_cfg = GenerationConfig(max_new_tokens=4, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    engine = lambda **kw: InferenceEngine(CausalLMPolicy(cfg), cfg, {"lm": lm_params}, gen_cfg, num_slots=2,
                                          max_prompt_len=16, **kw)
    with pytest.raises(NotImplementedError, match=r"the dense slot pool \(kv_paging=False\) over a looped stack"):
        engine()
    with pytest.raises(NotImplementedError, match="prefix_cache over a looped stack"):
        engine(kv_paging=True, prefix_cache=True)
    trainer = types.SimpleNamespace(config=types.SimpleNamespace(method=types.SimpleNamespace(quantize_frozen_trunk=True)),
                                    model_cfg=cfg, split=0, seq2seq=False, params={})
    with pytest.raises(NotImplementedError, match="method.quantize_frozen_trunk .* over a looped stack"):
        PPOTrainer._decode_params(trainer)
    staged = types.SimpleNamespace(runtime=None, model_cfg=cfg, _n_microbatches=2, _n_virtual=1)
    with pytest.raises(NotImplementedError, match="pipeline stages .* over a looped stack"):
        PipelinedCausalMixin.place_params(staged, {"lm": lm_params})
