"""Laguna-style stacks (window and full attention layers with their own
query heads and rotary settings over one K/V width, a per-head output gate,
sigmoid-routed experts beside a shared one, one chip's share of them)
against the benchmark's plain reference `bench/reference/laguna.py`, at test
size on the CPU, on seeded weights.

Tolerances. Float32 program against float32 reference, both at `highest`:
2e-4 on a logprob, which is some tens of float32 roundings through 4 layers
(the readings are 4e-6 to 3e-5); a dropped gate, a window off by one, YaRN's
factor left out or a bfloat16 router each move a logprob by 1e-2 or more at
this size (`test_the_reference_tells_each_departure`)."""

import dataclasses
import hashlib
import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

from benchlib.files import load_module  # noqa: E402

from parity import jitted_forward, jitted_init  # noqa: E402
from trlx_tpu.models import CausalLMPolicy, CausalLMWithValueHead, config_from_preset  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, RopeSpec, SparseMoE, TransformerLM, init_paged_kv_arena, rope_tables)

VOCAB = 96
TOL = 2e-4
ref = load_module("reference/laguna.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 2, **kw}
    return config_from_preset("laguna-tiny", VOCAB, **kw)


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    hf = hf_interop.config_to_hf(cfg, "laguna")
    return {**hf, "expert_offset": cfg.moe_local_offset}


def seeded_params(model, seed, *init_args):
    """Every leaf drawn from the seed, the selection bias too (a fresh init
    leaves it at zero, and then it would steer nothing)."""
    params = jitted_init(model)(jax.random.PRNGKey(seed), *init_args)["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(seed)
    out = []
    for path, leaf in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "bias":
            leaf = jnp.asarray(rng.normal(size=leaf.shape) * 0.1, leaf.dtype)
        elif name == "scale":
            leaf = jnp.asarray(1 + 0.05 * rng.normal(size=leaf.shape), leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def left_padded(rng, lens, width):
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


ROWS, WIDTH = 4, 32


def reference_logprobs(lm_params, cfg, tokens, mask):
    """The reference's [rows, width - 1] logprobs, every call padded (on the
    right, mask 0) to the one shape [ROWS, WIDTH], so that its jitted layers
    compile once a process."""
    tokens, mask = np.asarray(tokens), np.asarray(mask)
    rows, width = tokens.shape
    pad = lambda a: np.pad(a, ((0, ROWS - rows), (0, WIDTH - width)))
    out = ref.logprobs(lm_params, pad(tokens), pad(mask), sizes_of(cfg))
    return np.asarray(out)[:rows, : width - 1]


def forward_logprobs(cfg, params, tokens, mask):
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params, tokens, mask)
    return np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))


def test_presets_state_the_head_width_and_the_layers_own_heads():
    cfg = tiny_cfg()
    assert (cfg.head_dim, cfg.kv_heads, cfg.d_model // cfg.n_heads) == (16, 2, 10)
    assert cfg.layer_types == ("full_attention",) + ("sliding_attention",) * 3
    assert cfg.attention_kinds == ("full_attention", "sliding_attention")
    assert [cfg.window_of(k) for k in cfg.layer_types] == [None, 8, 8, 8]
    assert [cfg.block_kwargs(i)["n_heads"] // cfg.kv_heads for i in range(4)] == [3, 4, 4, 4]
    assert [cfg.layer_ffn(i) for i in range(4)] == ["dense"] + ["sparse_moe"] * 3
    # the cell's cut of the published sizes: what `check_kv_precision` reads, and
    # the parameters ISSUE 33 reckons (2,182.5M)
    bench = json.load(open(os.path.join(BENCH, "configs", "laguna-xs.2.json")))["bench"]
    extra = dict(bench["program"]["model_extra_configs"])
    cut = config_from_preset("laguna-xs.2", extra.pop("vocab_size"), **extra)
    assert (cut.head_dim, cut.kv_heads, cut.n_layers, cut.experts_held, cut.moe_experts) == (128, 8, 8, 64, 256)
    assert cut.layer_heads == (48, 64, 64, 64) * 2 and cut.layer_types == PRESETS["laguna-xs.2"]["layer_types"][:8]
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: CausalLMPolicy(cut).init(jax.random.PRNGKey(0), tokens, tokens)["params"])
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 2_182_582_016
    arena = jax.eval_shape(lambda: init_paged_kv_arena(cut, 4, 32, jnp.bfloat16))
    assert {a.shape for layer in arena for a in layer.values()} == {(4, 8, 32, 128)}  # one shape a layer
    # a family that names no layer kinds keeps one bias and one head count
    plain_cfg = config_from_preset("llama-tiny", VOCAB)
    assert plain_cfg.attention_kinds == () and plain_cfg.block_kwargs(0)["n_heads"] is None
    assert config_from_preset("lfm2-tiny", VOCAB).attention_kinds == ()


def test_yarn_tables_against_numbers_worked_out_by_hand():
    """Laguna-XS.2's full-attention rotary: D = 64, theta 500,000, original
    context 4,096, beta_fast 64, beta_slow 1, factor 64. c(64) = 64 ln(4096 /
    (128 pi)) / (2 ln 5e5) = 5.66, so low = 5; c(1) = 15.80, so high = 16:
    dimensions 0-5 keep f_i, 16-31 are f_i / 64, and dimension 6 is
    f_6 (1/11 / 64 + 10/11)."""
    spec = dict(PRESETS["laguna-xs.2"]["rope_kinds"])["full_attention"]
    inv_freq, scale, rd = rope_tables(spec, 128)
    f = lambda i: 500000.0 ** (-2.0 * i / 64)
    assert rd == 64 and inv_freq.shape == (32,)
    np.testing.assert_allclose(inv_freq[:6], [f(i) for i in range(6)], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[16:], [f(i) / 64 for i in range(16, 32)], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[6], f(6) * (1 / 11 / 64 + 10 / 11), rtol=1e-6)
    np.testing.assert_allclose(inv_freq[5], 0.128687, rtol=1e-5)
    np.testing.assert_allclose(inv_freq[16], 2.20971e-05, rtol=1e-5)
    assert scale == 1.4158883083359672 and abs(scale - (0.1 * np.log(64) + 1)) < 1e-12
    assert rope_tables(dataclasses.replace(spec, attention_factor=None), 128)[1] == pytest.approx(scale)
    # the reference's own, written out from the same equations
    rope = sizes_of(config_from_preset("laguna-xs.2", VOCAB))["rope_parameters"]
    theirs, their_scale = ref.yarn_inv_freq(rope["full_attention"], 64)
    np.testing.assert_allclose(np.asarray(theirs), inv_freq, rtol=2e-6)
    assert their_scale == scale
    plain_freq, one, rd = rope_tables(RopeSpec(theta=10000.0), 128)
    assert (one, rd) == (1.0, 128) and plain_freq[1] == pytest.approx(10000.0 ** (-2 / 128))


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("seed", [0, 3_000_000_019 % (2 ** 31)])
def test_forward_matches_the_reference(seed, attn_impl):
    """Sequences three windows long, left-padded rows of unequal length; under
    "flash" the full layers take the fused path and the sliding ones, longer
    than their window, the dense band (`fused_attention_ok`)."""
    cfg = tiny_cfg(attn_impl=attn_impl)
    tokens, mask = left_padded(np.random.default_rng(seed), [26, 9, 2, 17], 26)
    params = seeded_params(TransformerLM(cfg), seed, jnp.asarray(tokens), jnp.asarray(mask))
    want = reference_logprobs(params, cfg, tokens, mask)
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    assert np.abs(forward_logprobs(cfg, params, tokens, mask) - want)[valid].max() < TOL


def test_the_reference_tells_each_departure():
    """What the chip's limit has to refuse, at test size: the program with
    one thing left out reads far outside the tolerance."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(1), [26, 20], 26)
    params = seeded_params(TransformerLM(cfg), 1, jnp.asarray(tokens), jnp.asarray(mask))
    want = reference_logprobs(params, cfg, tokens, mask)
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    err = lambda c, p=params: np.abs(forward_logprobs(c, p, tokens, mask) - want)[valid].max()
    assert err(cfg) < TOL
    no_yarn_scale = tuple((k, dataclasses.replace(r, attention_factor=1.0)) for k, r in cfg.rope_kinds)
    ungated = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 0 if any(getattr(k, "key", None) == "gate_proj" for k in path) else x, params)
    readings = {
        "window off by one": err(dataclasses.replace(cfg, sliding_window=cfg.sliding_window + 1)),
        "YaRN's factor left out": err(dataclasses.replace(cfg, rope_kinds=no_yarn_scale)),
        "gate at 1/2 everywhere": err(cfg, ungated),
        "routed scale left out": err(dataclasses.replace(cfg, moe_routed_scale=1.0)),
    }
    assert min(readings.values()) > 50 * TOL, readings


def test_sampler_through_the_dense_cache_matches_the_reference():
    """`generate`: the prefill of left-padded prompts (`flash_prefill`: within
    the block, banded on the sliding layers), then the fused decode loop past
    the window's edge, every captured logprob against the reference's full
    forward over the sampled sequence."""
    from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn

    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMWithValueHead(cfg)
    tokens, mask = left_padded(np.random.default_rng(5), [12, 5, 1, 10], 12)
    params = seeded_params(model, 5, jnp.asarray(tokens), jnp.asarray(mask))
    gen_cfg = GenerationConfig(max_new_tokens=14, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    generate = jax.jit(make_generate_fn(model, cfg, gen_cfg, capture=True))
    with jax.default_matmul_precision("highest"):
        out = generate(params, jnp.asarray(tokens), jnp.asarray(mask), jax.random.PRNGKey(0))
    want = reference_logprobs(params["lm"], cfg, out["samples"], out["samples_mask"])[:, 11:]
    assert np.abs(np.asarray(out["logprobs"]) - want).max() < TOL


def run_engine(cfg, params, prompts, max_new, **engine_kw):
    """Every prompt through a paged `InferenceEngine` to `max_new` tokens:
    per request its tokens and the logprobs the engine reports for them."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    engine = InferenceEngine(CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=3, kv_paging=True,
                             num_slots=len(prompts), max_prompt_len=32, max_prefill_batch=2, prompt_bucket=16,
                             kv_block_size=4, **engine_kw)
    slots = list(range(len(prompts)))
    engine.insert_requests([(p, max_new) for p in prompts], slots)
    tokens, logprobs = [[] for _ in prompts], [[] for _ in prompts]
    for _ in range(max_new):
        tok, lp, emitted, _ = engine.step()
        for s in slots:
            if emitted[s]:
                tokens[s].append(int(tok[s]))
                logprobs[s].append(float(lp[s]))
    return engine, tokens, logprobs


def engine_errors(cfg, params, prompts, out, got):
    """Largest |engine logprob - reference logprob| over each request's
    sampled tokens, the reference run over prompt + output, right-padded."""
    seqs = [np.concatenate([p, np.asarray(new, np.int32)]) for p, new in zip(prompts, out)]
    tokens = np.zeros((len(seqs), WIDTH), np.int32)
    mask = np.zeros_like(tokens)
    for r, seq in enumerate(seqs):
        tokens[r, :len(seq)], mask[r, :len(seq)] = seq, 1
    want = reference_logprobs(params["lm"], cfg, tokens, mask)
    return [np.abs(np.asarray(lps) - want[r, len(p) - 1:len(p) - 1 + len(lps)]).max()
            for r, (p, lps) in enumerate(zip(prompts, got))]


@pytest.mark.parametrize("kernels", ["decode_kernel", "env"])
def test_engine_end_to_end_matches_the_reference_with_no_fallback(kernels, monkeypatch):
    """Prefill through the fresh-prompt program (the prompt attends within
    itself, banded on the sliding layers, K/V into the arena), then paged
    decode through the kernel in interpret mode on every layer, windowed calls
    and full ones, groups of 3 and 4: past the window's edge (8) and across
    block boundaries (4), rows of unequal length, one shorter than the window.
    `env` interprets the flash forward kernels too (TRLX_TPU_KERNELS)."""
    if kernels == "env":
        monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMPolicy(cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (21, 5, 13)]
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 11, tokens, jnp.ones_like(tokens))
    with jax.default_matmul_precision("highest"):
        engine, out, got = run_engine(cfg, params, prompts, 11,
                                      decode_kernel="auto" if kernels == "env" else "interpret")
    assert engine.decode_path == "interpret"
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["kv_kernel_dispatches"] == 11
    assert sorted(k for k in engine._paged_insert_fns) == [(1, 32, True), (2, 16, True)]
    assert [len(lps) for lps in got] == [11] * 3 and max(engine_errors(cfg, params, prompts, out, got)) < TOL
    # the step's counters: what a sliding layer reads against what is resident
    walk = engine._kv_walk()
    cols = np.asarray([len(p) + 11 + 1 for p in prompts])
    assert walk["layers"] == 4 and walk["resident"] == 4 * cols.sum()
    assert walk["walked_full"] == (-(-cols // 4) * 4).sum()
    assert walk["walked_window"] == 3 * ((-(-cols // 4) - np.maximum(cols - 8, 0) // 4) * 4).sum()
    assert 0 < stats["kv_walked_share"] < 1 and stats["moe_dropped_tokens"] == 0.0
    assert 0 < stats["moe_local_assignment_share"] < 1


def test_engine_gather_path_and_int8_arena_follow_the_window():
    """`decode_kernel="xla"` (the gather and the dense band) reads what the
    kernel reads; an int8 arena goes through the windowed kernel and stays
    within int8's error of the reference."""
    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMPolicy(cfg)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (19, 7)]
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 12, tokens, jnp.ones_like(tokens))

    def worst(engine_kw):
        with jax.default_matmul_precision("highest"):
            engine, out, got = run_engine(cfg, params, prompts, 10, **engine_kw)
        return engine, max(engine_errors(cfg, params, prompts, out, got))

    engine, err = worst(dict(decode_kernel="xla"))
    assert err < TOL and engine.kv_stats()["kv_walked_share"] > 1  # the gather reads whole tables
    engine, err = worst(dict(decode_kernel="interpret", kv_cache_dtype="int8"))
    # int8 keys and values: 1/254 of a row's largest magnitude a element, through 4 layers
    assert TOL < err < 0.05 and engine.kv_stats()["kv_kernel_fallbacks"] == {}


# sha256 of the lowered text (`jit(...).lower(...).as_text()`, which carries
# no source locations) of three neox-tiny programs, recorded on the parent
# commit b8ba58b under jax 0.9.0 by this very code: a family that names no
# layer kinds lowers to the program it lowered to before the layers could
# differ, the paged kernel's body (interpreted) included. The insert's was
# recorded again at PR 50, whose point is that program's text: it unembeds each
# row's last live position (`decode_step`'s `head_at`) where it unembedded all
# (19f396a3...385 until then). The decode step's was recorded again at PR 56,
# whose point is the paged kernel's body: a row's first tile is told by the
# row's change and no longer by a fifth scalar operand, and an int8 arena's
# value scales are selected by the mask (5ac5d745...ffa until then; neox-tiny's
# heads of 16 keep every block an operand, `copies_blocks`), and again at PR
# 58, which left the kernel's body for this arena as it was (heads of 16 keep
# `paged_kv_write` too: the text held while only the write was added) and made
# the call one jitted function, so the layers' calls are ONE private function
# of the module, called a layer, where each layer had a copy of it
# (4c97953a...f14 until then); the forward stands as recorded.
LOWERED_BEFORE = {
    "forward": "803f839d0b7e6abadd156f8b2cb8bd16c9d3034a7f14eecc5b4922349f28de6d",
    "decode": "ba4d54c3b3028db9bbbe690786224dd21600a5076b08fe048dfa040426bd7ade",
    "insert": "6503e6edc5c8f4b5bae8b96cd693b1d431f0b7431c99f5d28a572f5bb6974d3f",
}


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE))
def test_a_family_without_layer_kinds_lowers_to_the_program_it_lowered_to(program):
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = config_from_preset("neox-tiny", 96)
    tok = jnp.zeros((2, 24), jnp.int32)
    if program == "forward":
        lm = TransformerLM(cfg)
        params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), tok, jnp.ones_like(tok))["params"])
        text = jax.jit(lambda p, t, m: lm.apply({"params": p}, t, m)[0]).lower(
            params, tok, jnp.ones_like(tok)).as_text()
    else:
        model = CausalLMPolicy(cfg)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok, jnp.ones_like(tok))["params"])
        gen = GenerationConfig(max_new_tokens=8, do_sample=True, eos_token_id=97, pad_token_id=0)
        eng = InferenceEngine(model, cfg, None, gen, kv_paging=True, num_slots=4, max_prompt_len=16,
                              prompt_bucket=8, kv_block_size=4, decode_kernel="interpret")
        if program == "decode":
            text = eng._decode_fn.lower(params, eng._pool).as_text()
        else:
            shapes = ((2, 8), (2, 8), (2, eng._n_tbl), (2,), (2,), (2,))
            text = eng._get_paged_insert(2, 8).lower(
                eng._pool, params, *(jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_BEFORE[program]


def _layer_inputs(cfg, seed, tokens=40):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, tokens // 2, cfg.d_model)), jnp.float32)
    params = seeded_params(SparseMoE(cfg), seed, x)
    return x, params


def _reference_layer(x, params, cfg):
    return ref.expert_ffn(x, params, top_k=cfg.moe_top_k, offset=cfg.moe_local_offset,
                          scaling=cfg.moe_routed_scale, int8=False)


def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """Section 4 of the model-configs guide, for a layer with a shared
    expert: what each of 4 chips computes for its 8 of 32 experts (the router
    scoring all 32 on every chip, the shared expert computed whole on each)
    adds up, the shared expert's part taken once, to the reference's uncut
    layer; the routed scale is on the experts' part only."""
    kw = dict(d_model=32, head_width=8, moe_d_ff=16, moe_shared_d_ff=16, moe_experts=32, moe_top_k=4)
    whole = tiny_cfg(**kw, moe_local_experts=0)
    x, params = _layer_inputs(whole, 21)
    shared_only = {k: v for k, v in params.items() if k.startswith("shared_")}
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(x, params, whole)  # every expert in the stack: the published layer
        shared = ref.glu(x, *(params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")), False)
        assert float(jnp.abs(shared).max()) > 0 and shared_only
        total = shared
        for chip in range(4):
            cfg = tiny_cfg(**kw, moe_local_experts=8, moe_local_offset=8 * chip)
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 4, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(np.asarray(part), np.asarray(_reference_layer(x, {**params, **share}, cfg)),
                                       atol=2e-5)
            assert float(jnp.abs(part - shared).max()) > 0
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def test_one_ppo_cycle_through_train_at_laguna_tiny(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=20, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3),
        model=dict(model_path="random:laguna-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=2)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"], eval_prompts=["ab", "cd"], config=config)
    assert trainer.iter_count == 1 and trainer.model_cfg.attention_kinds
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    assert any("gate_proj" in k for k in train) and any("shared_up" in k for k in train)
    assert [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))] == []


def test_hf_config_keys_round_trip_and_tensor_names_are_refused_by_name(tmp_path):
    bench = json.load(open(os.path.join(BENCH, "configs", "laguna-xs.2.json")))
    published = {k: v for k, v in bench.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    extra = dict(bench["bench"]["program"]["model_extra_configs"])
    extra.pop("moe_local_experts")  # the file's `num_experts` is the experts held: the router's width is the preset's
    want = config_from_preset("laguna-xs.2", extra.pop("vocab_size"), **{**extra, "attn_impl": "xla"},
                              moe_experts=64, hf_family="laguna", dtype=jnp.float32)
    assert cfg == want
    again_dir = tmp_path / "again"
    again_dir.mkdir()
    (again_dir / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    assert hf_interop.config_from_hf(str(again_dir), dtype=jnp.float32) == cfg
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf_interop.params_to_hf_state_dict({"lm": {}}, cfg)
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf_interop.load_params_from_hf(str(tmp_path), cfg, {})


def test_paths_that_cannot_follow_refuse_by_name():
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    build = lambda **kw: InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2,
                                         max_prompt_len=8, **kw)
    with pytest.raises(NotImplementedError, match="prefix_cache over attention layers of several kinds"):
        build(kv_paging=True, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="dense slot pool"):
        build()
    with pytest.raises(NotImplementedError, match="sessions .* over attention layers of several kinds"):
        build(kv_paging=True).enable_sessions()
    from trlx_tpu.ops.attention import flash_attention

    q = jnp.ones((1, 16, 2, 8))
    with pytest.raises(NotImplementedError, match="windowed flash backward"):
        jax.grad(lambda a: flash_attention(a, q, q, window=4).sum())(q)


def test_flops_and_cache_bytes_follow_each_layers_heads_window_and_experts():
    from trlx_tpu.observability import flops, hbm

    cfg = tiny_cfg()
    d, hd, kv = cfg.d_model, cfg.head_dim, cfg.kv_heads * cfg.head_dim
    attn = lambda heads: 2 * d * heads * hd * 2 + 4 * d * kv + 2 * d * heads  # q and o, k and v, the gate
    dense = 6 * d * cfg.d_ff
    # 2 of 8 experts held, 2 a token: half an expert a token is computed here, the shared one whole
    experts = 2 * d * 8 + (2 * 2 / 8) * 6 * d * cfg.expert_d_ff + 6 * d * cfg.moe_shared_d_ff
    want = [attn(6) + dense] + [attn(8) + experts] * 3
    assert [flops.layer_matmul_flops(cfg, i) for i in range(4)] == want
    # a context of 20: a full layer reads all of it, a sliding one its window of 8
    assert flops.layer_attention_flops(cfg, 0, 20) == 4 * 20 * 6 * hd
    assert flops.layer_attention_flops(cfg, 1, 20) == 4 * 8 * 8 * hd
    assert hbm.kv_arena_bytes(cfg.n_layers, cfg.kv_heads, cfg.head_dim, 9, 4, "bfloat16") == sum(
        leaf.nbytes for layer in init_paged_kv_arena(cfg, 9, 4, jnp.bfloat16) for leaf in layer.values())
