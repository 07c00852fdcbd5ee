"""LFM2-style hybrids (short-convolution and attention layers in one stack,
sigmoid-routed experts with grouped dispatch, one chip's share of them)
against the benchmark's plain reference `bench/reference/lfm2_moe.py`, at
test size on the CPU, on seeded weights."""

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
from trlx_tpu.models import CausalLMWithValueHead, config_from_preset, init_kv_cache  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import SparseMoE, TransformerLM, init_paged_kv_arena  # noqa: E402
from trlx_tpu.ops import moe  # noqa: E402

VOCAB = 96
ref = load_module("reference/lfm2_moe.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 2, **kw}
    return config_from_preset("lfm2-tiny", VOCAB, **kw)


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    return dict(
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.kv_heads, rope_theta=cfg.rope_theta,
        norm_eps=cfg.layer_norm_epsilon, num_experts_per_tok=cfg.moe_top_k,
        expert_offset=cfg.moe_local_offset, num_hidden_layers=cfg.n_layers,
        layer_types=["conv" if t == "conv" else "full_attention" for t in cfg.layer_types],
        num_dense_layers=cfg.moe_dense_layers)


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


def test_presets_and_cache_by_layer_kind():
    cfg = tiny_cfg()
    assert cfg.layer_types == ("conv", "conv", "attention", "conv", "conv", "conv")
    assert [cfg.layer_ffn(i) for i in range(6)] == ["dense"] * 2 + ["sparse_moe"] * 4
    cut = config_from_preset("lfm2-8b-a1b", 16384, n_layers=10, moe_local_experts=8)
    assert cut.layer_types == tuple("attention" if i in (2, 6) else "conv" for i in range(10))
    assert (cut.experts_held, cut.moe_experts, cut.expert_d_ff, cut.d_ff) == (8, 32, 1792, 7168)
    cache = init_kv_cache(cfg, 3, 20)
    assert [sorted(layer) for layer in cache["layers"]] == [
        ["conv"], ["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    assert cache["layers"][0]["conv"].shape == (3, cfg.conv_kernel - 1, cfg.d_model)
    # a config that names no layer kinds builds the blocks it always built
    plain_cfg = config_from_preset("llama-tiny", VOCAB)
    assert plain_cfg.layer_types == () and not plain_cfg.blocks_read_token_mask
    assert not plain_cfg.sows_moe_aux and config_from_preset("moe-tiny", VOCAB).sows_moe_aux
    assert not cfg.sows_moe_aux


@pytest.mark.parametrize("seed", [0, 3_000_000_019 % (2 ** 31)])
def test_forward_matches_the_reference(seed):
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    tokens, mask = left_padded(np.random.default_rng(seed), [24, 9, 2, 17], 24)
    params = seeded_params(model, seed, jnp.asarray(tokens), jnp.asarray(mask))
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params, tokens, mask)
    got = np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params, tokens, mask, sizes_of(cfg)))
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    assert np.abs(got - want)[valid].max() < 2e-4


def test_sampler_through_both_caches_matches_the_reference():
    """`generate`: prefill of left-padded prompts of unequal length, then
    the fused decode loop through K/V tables and convolution states, one row
    finishing early; every captured logprob against the reference's full
    forward over the sampled sequence."""
    from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn

    cfg = tiny_cfg()
    model = CausalLMWithValueHead(cfg)
    rng = np.random.default_rng(5)
    tokens, mask = left_padded(rng, [12, 5, 1, 8], 12)
    params = seeded_params(model, 5, jnp.asarray(tokens), jnp.asarray(mask))
    eos = 7
    gen_cfg = GenerationConfig(max_new_tokens=10, do_sample=True, eos_token_id=eos, pad_token_id=0)
    generate = jax.jit(make_generate_fn(model, cfg, gen_cfg, capture=True))
    for key in range(40):  # a draw in which some row, not every row, meets eos early
        out = generate(params, jnp.asarray(tokens), jnp.asarray(mask), jax.random.PRNGKey(key))
        lengths = np.asarray(out["response_mask"]).sum(-1)
        if lengths.min() < 10 and lengths.max() == 10:
            break
    else:
        pytest.fail("no draw finished a row early")
    samples, smask = np.asarray(out["samples"]), np.asarray(out["samples_mask"])
    want = np.asarray(ref.logprobs(params["lm"], samples, smask, sizes_of(cfg)))[:, 11:]
    rmask = np.asarray(out["response_mask"]).astype(bool)
    assert np.abs(np.asarray(out["logprobs"]) - want)[rmask].max() < 2e-4


def _layer_inputs(cfg, seed, tokens=40):
    layer = SparseMoE(cfg)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, tokens // 2, cfg.d_model)), jnp.float32)
    return layer, x, seeded_params(layer, seed, x)


def _reference_layer(x, params, cfg):
    return ref.expert_ffn(x, params, top_k=cfg.moe_top_k, offset=cfg.moe_local_offset, scaling=1.0,
                          int8=False)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_expert_layer_gradients_match_the_reference(mode, monkeypatch):
    """Forward and `jax.grad` with respect to the input, the router and the
    three stacks, through the grouped dispatch (ragged_dot, and the Pallas
    kernels interpreted at widths they tile), against the reference's plain
    loop over the experts held."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "kernel_mode", lambda: mode)
    cfg = tiny_cfg(d_model=128, n_heads=4, moe_d_ff=128, moe_experts=8, moe_local_experts=4,
                   moe_local_offset=4)
    layer, x, params = _layer_inputs(cfg, 11)
    probe = jnp.asarray(np.random.default_rng(1).normal(size=x.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p, h: (layer.apply({"params": p}, h) * probe).sum(), (0, 1)))(params, x)
        want = jax.jit(jax.value_and_grad(lambda p, h: (_reference_layer(h, p, cfg) * probe).sum(), (0, 1)))(params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    flat_got, flat_want = (jax.tree_util.tree_leaves_with_path(g[1]) for g in (got, want))
    for (path, a), (_, b) in zip(flat_got, flat_want):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        scale = float(jnp.abs(b).max())
        if "expert_bias" in name:  # steers a top-k: no gradient, in either
            assert float(jnp.abs(a).max()) == 0.0 and scale == 0.0
            continue
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_grouped_dispatch_equals_the_dense_masked_layer_under_a_skewed_router(mode):
    """A selection bias that sends every token to one expert: the static row
    count (tokens x experts a token) still holds every assignment, none is
    dropped, and the result is the dense-masked computation's."""
    cfg = tiny_cfg(d_model=128, n_heads=4, moe_d_ff=128, moe_experts=8, moe_local_experts=4, moe_top_k=2)
    _, x, params = _layer_inputs(cfg, 3, tokens=64)
    router = params["router"]["kernel"]
    params = {**params, "expert_bias": {"bias": params["expert_bias"]["bias"].at[1].add(10.0)}}
    flat = x.reshape(-1, cfg.d_model)
    token_mask = jnp.asarray(np.random.default_rng(0).integers(0, 4, size=flat.shape[0]) > 0, jnp.int32)
    stacks = (params["expert_gate"]["kernel"], params["expert_up"]["kernel"], params["expert_down"]["kernel"])
    with jax.default_matmul_precision("highest"):
        routing = moe.route_sigmoid(flat, router, params["expert_bias"]["bias"], 2)
        got, stats = moe.routed_experts(flat, *routing, *stacks, token_mask=token_mask, mode=mode)
        want = _reference_layer(flat, params, cfg) * token_mask[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(stats["dropped_tokens"]) == 0.0
    assert float(stats["tokens_per_expert_max_over_mean"]) > 2.0  # the skew is there
    assert 0.0 < float(stats["local_assignment_share"]) <= 1.0


def test_the_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: what each of 4 chips computes
    for its 8 of 32 experts (the router scoring all 32 on every chip) adds
    up to the reference's uncut layer."""
    whole = tiny_cfg(d_model=32, n_heads=4, moe_d_ff=16, moe_experts=32, moe_local_experts=0, moe_top_k=4)
    _, x, params = _layer_inputs(whole, 21)
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(x, params, whole)  # every expert in the stack: the published layer
        total = jnp.zeros_like(want)
        for chip in range(4):
            cfg = tiny_cfg(d_model=32, n_heads=4, moe_d_ff=16, moe_experts=32, moe_local_experts=8,
                           moe_local_offset=8 * chip, moe_top_k=4)
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 4, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(np.asarray(part), np.asarray(_reference_layer(x, {**params, **share}, cfg)),
                                       atol=2e-5)
            assert float(jnp.abs(part).max()) > 0
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def test_one_ppo_cycle_through_train_moves_every_trainable_leaf_and_not_the_selection_bias(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline import MiniBatchIterator

    config = default_ppo_config().evolve(
        train=dict(seq_length=24, epochs=1, total_steps=2, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3),
        model=dict(model_path="random:lfm2-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=2)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=8, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=6, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"] * 2, eval_prompts=["ab", "cd"], config=config)
    assert trainer.iter_count == 2 and trainer.model_cfg.has_conv_layers
    start = flatten_dict(trainer.ref_params)  # the top blocks as they were
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    assert train and not any("expert_bias" in k for k in trainer.train_params)
    stuck = [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))]
    assert stuck == []
    bias = {k: v for k, v in trainer.frozen_params.items() if "expert_bias" in k and k[1:] in start}
    assert len(bias) == 2 and all(bool(jnp.all(start[k[1:]] == v)) for k, v in bias.items())
    # the dispatch counters ride every logged train step
    loader = trainer.create_train_dataloader()
    stats = trainer.train_minibatch(next(iter(MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb))))
    counters = {k: float(v) for k, v in stats["moe"].items()}
    assert sorted(counters) == sorted(moe.STATS)
    assert counters["dropped_tokens"] == 0.0 and 0.0 < counters["local_assignment_share"] < 1.0


def test_hf_round_trip_on_a_random_state_dict(tmp_path):
    cfg = tiny_cfg(moe_local_experts=0)  # a checkpoint holds every expert
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 9, tokens, jnp.ones_like(tokens))
    sd = hf_interop.params_to_hf_state_dict({"lm": params}, cfg)
    assert sd["model.layers.0.conv.conv.weight"].shape == (cfg.d_model, 1, cfg.conv_kernel)
    assert sd["model.layers.2.self_attn.q_layernorm.weight"].shape == (cfg.head_dim,)
    assert sd["model.layers.3.feed_forward.experts.3.w2.weight"].shape == (cfg.d_model, cfg.expert_d_ff)
    assert sd["model.layers.0.feed_forward.w1.weight"].shape == (cfg.d_ff, cfg.d_model)
    back = hf_interop._load_lfm2_moe(sd, cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
    # a chip that holds experts [2, 4) loads its share of the same checkpoint
    share = hf_interop._load_lfm2_moe(sd, tiny_cfg(moe_local_experts=2, moe_local_offset=2))
    np.testing.assert_array_equal(
        share["block_3"]["mlp"]["expert_up"]["kernel"],
        np.split(np.asarray(params["block_3"]["mlp"]["expert_up"]["kernel"]), 2, axis=1)[1])
    # config keys -> TransformerConfig -> config keys
    hf_cfg = hf_interop.config_to_hf(cfg)
    assert hf_cfg["model_type"] == "lfm2_moe" and hf_cfg["layer_types"][2] == "full_attention"
    (tmp_path / "config.json").write_text(json.dumps(hf_cfg))
    again = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    for field in ("layer_types", "moe_experts", "moe_top_k", "moe_d_ff", "moe_dense_layers", "moe_router",
                  "qk_norm", "conv_kernel", "n_kv_heads", "d_ff", "rope_theta", "tie_embeddings"):
        assert getattr(again, field) == getattr(cfg, field), field


def test_the_per_row_paths_carry_the_convolution_state_and_refuse_what_cannot_follow_it():
    """The paged engine holds a `conv` layer's last inputs a slot beside its
    arena (`cfg.layer_keeps`); what would share a slot's state through block
    tables or roll it back by mask bits is refused by name (the engine's own
    refusals over slot state: tests/test_ling_flash.py, both presets)."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    engine = InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8, kv_paging=True)
    assert [sorted(layer) for layer in engine._pool["layers"]] == [
        ["conv"], ["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    assert engine._pool["layers"][0]["conv"].shape == (2, 2, 64)
    with pytest.raises(NotImplementedError, match="dense slot pool .* over slot state"):
        InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8)
    with pytest.raises(NotImplementedError, match="needs its number of slots"):
        init_paged_kv_arena(cfg, 4, 8)


@pytest.mark.parametrize("path", ["interpret", "xla"])
def test_the_engine_gives_what_generate_gives(path):
    """`lfm2-tiny` through the paged engine (a right-padded fresh-prompt
    insert that leaves each row's convolution tails in its slot, then decode
    steps that shift them in place) against the fused sampler over its
    scalar-index cache: the same greedy tokens, the same logprobs."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy
    from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn

    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMWithValueHead(cfg)
    rng = np.random.default_rng(21)
    lens, width, new = [13, 2, 7], 13, 12
    tokens = rng.integers(1, VOCAB, size=(3, width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    tokens = tokens * mask
    params = seeded_params(model, 21, jnp.asarray(tokens), jnp.asarray(mask))
    gen_cfg = GenerationConfig(max_new_tokens=new, do_sample=False, eos_token_id=VOCAB + 1, pad_token_id=0)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(make_generate_fn(model, cfg, gen_cfg, capture=True))(
            params, jnp.asarray(tokens), jnp.asarray(mask), jax.random.PRNGKey(0))
        engine = InferenceEngine(CausalLMPolicy(cfg), cfg, {"lm": params["lm"]}, gen_cfg, kv_paging=True,
                                 num_slots=3, max_prompt_len=16, max_prefill_batch=2, prompt_bucket=8,
                                 kv_block_size=4, decode_kernel=path)
        engine.insert_requests([(tokens[r, width - n:], new) for r, n in enumerate(lens)], [0, 1, 2])
        got_tokens, got_lp = [[] for _ in lens], [[] for _ in lens]
        for _ in range(new):
            tok, lp, emitted, _ = engine.step()
            for r in range(3):
                if emitted[r]:
                    got_tokens[r].append(int(tok[r]))
                    got_lp[r].append(float(lp[r]))
    assert engine.kv_stats()["kv_kernel_fallbacks"] == {} and engine.decode_path == path
    assert np.array_equal(np.asarray(got_tokens), np.asarray(want["samples"])[:, width:])
    assert np.abs(np.asarray(got_lp) - np.asarray(want["logprobs"])).max() < 1e-5
    assert engine.kv_stats()["slot_state_bytes_per_slot"] == 5 * 2 * 64 * 4


def test_flops_and_cache_bytes_follow_the_layer_kinds():
    from trlx_tpu.observability import flops, hbm

    cfg = tiny_cfg()
    d, f, fe, kv = cfg.d_model, cfg.d_ff, cfg.expert_d_ff, cfg.kv_heads * cfg.head_dim
    conv = 8 * d * d + 2 * cfg.conv_kernel * d
    attn = 4 * d * d + 4 * d * kv
    dense = 6 * d * f
    # 2 of 4 experts held, 2 a token: one expert a token is computed here
    experts = 2 * d * 4 + (2 * 2 / 4) * 6 * d * fe
    want = [conv + dense, conv + dense, attn + experts, conv + experts, conv + experts, conv + experts]
    assert [flops.layer_matmul_flops(cfg, i) for i in range(6)] == want
    cycle = flops.flops_per_cycle(cfg, 8, 4, 1, 1, unfrozen=2)
    ctx = 8 + 4 / 2
    decode = 4 * (sum(want) + 1 * 4 * ctx * d + 2 * d * VOCAB)  # one attention layer reads a context
    prefill = 8 * (sum(want) + 1 * 4 * 4 * d + 2 * d * VOCAB)
    assert cycle["generate"] == pytest.approx(prefill + decode)
    # the same estimate as ever for a config that names no layer kinds
    dense_cfg = config_from_preset("llama-tiny", VOCAB)
    assert flops.layer_matmul_flops(dense_cfg, 0) == 8 * 64 * 64 + 4 * 64 * 256
    cache = init_kv_cache(cfg, 3, 20, dtype=jnp.bfloat16)
    held = sum(leaf.nbytes for layer in cache["layers"] for leaf in layer.values())
    assert hbm.decode_state_bytes(cfg, 3, 20, "bfloat16") == held
    assert hbm.decode_state_bytes(dense_cfg, 3, 20, "bfloat16") == hbm.kv_cache_bytes(
        2, dense_cfg.kv_heads, dense_cfg.head_dim, 3, 20, "bfloat16")
