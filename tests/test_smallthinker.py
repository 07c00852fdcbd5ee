"""SmallThinker-style stacks (full layers that rotate nothing and banded ones
that rotate, 1:3, over one K/V width; ReLU-gated experts routed by a softmax
over the chosen logits of the block's INPUT, every expert held or one chip's
share) against the benchmark's plain reference
`bench/reference/smallthinker.py`, at test size on the CPU, on seeded weights.

Tolerances. Float32 program against float32 reference, both at `highest`:
2e-4 on a logprob, which is some tens of float32 roundings through 4 layers
(the readings are 1e-6 to 3e-5); the router fed from the feed-forward's input,
SiLU for ReLU or rotary on the full layers each move a logprob by 1e-2 or more
at this size (`test_the_reference_tells_each_departure`)."""

import dataclasses
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
from trlx_tpu.models import CausalLMPolicy, config_from_preset  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, Attention, SparseMoE, TransformerConfig, TransformerLM, causal_bias, init_paged_kv_arena)
from trlx_tpu.ops import moe  # noqa: E402

VOCAB = 96
TOL = 2e-4
ROWS, WIDTH = 4, 32
ref = load_module("reference/smallthinker.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    return config_from_preset("smallthinker-tiny", VOCAB, **{"dtype": jnp.float32, **kw})


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "smallthinker"), "expert_offset": cfg.moe_local_offset}


def seeded_params(model, seed, *init_args):
    """Every leaf drawn from the seed, the norms' scales off 1."""
    params = jitted_init(model)(jax.random.PRNGKey(seed), *init_args)["params"]
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(1 + 0.05 * rng.normal(size=leaf.shape), leaf.dtype)
        if str(getattr(path[-1], "key", path[-1])) == "scale" else leaf, params)


def left_padded(rng, lens, width):
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


def reference_logprobs(lm_params, cfg, tokens, mask, departure=None):
    """The reference's [rows, width - 1] logprobs, every call padded (on the
    right, mask 0) to the one shape [ROWS, WIDTH], so that its jitted layers
    compile once a process and a departure."""
    tokens, mask = np.asarray(tokens), np.asarray(mask)
    rows, width = tokens.shape
    pad = lambda a: np.pad(a, ((0, ROWS - rows), (0, WIDTH - width)))
    out = ref.logprobs(lm_params, pad(tokens), pad(mask), sizes_of(cfg), departure=departure)
    return np.asarray(out)[:rows, : width - 1]


def forward_logprobs(cfg, params, tokens, mask):
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params, tokens, mask)
    return np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))


def test_presets_state_the_published_sizes_and_the_cut():
    cfg = tiny_cfg()
    assert (cfg.head_dim, cfg.kv_heads, cfg.n_heads // cfg.kv_heads) == (16, 2, 3)
    assert cfg.layer_types == ("full_attention",) + ("sliding_attention",) * 3
    assert cfg.attention_kinds == ("full_attention", "sliding_attention")
    assert [cfg.window_of(k) for k in cfg.layer_types] == [None, 8, 8, 8]
    assert cfg.rope_of("full_attention").pct == 0.0 and cfg.rope_of("sliding_attention").theta == 1.5e6
    assert [cfg.layer_ffn(i) for i in range(4)] == ["sparse_moe"] * 4
    assert cfg.has_sparse_moe and not cfg.sows_moe_aux and cfg.blocks_read_token_mask
    assert (cfg.moe_router, cfg.moe_route_on, cfg.experts_held) == ("topk_softmax", "block_input", 16)
    # the cell's cut of the published sizes, and the parameters ISSUE 55 reckons
    bench = json.load(open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")))["bench"]
    extra = dict(bench["program"]["model_extra_configs"])
    cut = config_from_preset("smallthinker-21b-a3b", extra.pop("vocab_size"), **extra)
    assert (cut.head_dim, cut.kv_heads, cut.n_heads, cut.n_layers) == (128, 4, 28, 8)
    assert (cut.experts_held, cut.moe_experts, cut.moe_top_k, cut.expert_d_ff, cut.moe_token_block) == (
        64, 64, 6, 768, 4096)
    assert cut.layer_types == PRESETS["smallthinker-21b-a3b"]["layer_types"][:8]
    count = lambda c: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: CausalLMPolicy(c).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                                       jnp.ones((1, 8), jnp.int32))["params"])))
    assert count(cut) == bench["parameters_held"] == 3_966_937_600
    whole = config_from_preset("smallthinker-21b-a3b", 151936)
    assert count(whole) == 21_506_562_560  # the published "21B"
    arena = jax.eval_shape(lambda: init_paged_kv_arena(cut, 4, 32, jnp.bfloat16))
    assert {a.shape for layer in arena for a in layer.values()} == {(4, 4, 32, 128)}  # 2,048 B a token a layer


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


@pytest.mark.parametrize("departure, program", [
    ("router_on_ffn_input", dict(moe_route_on="ffn_input")),
    ("silu", dict(activation="silu")),
    ("rope_on_full", dict(rope_kinds=(("sliding_attention", dict(theta=1.5e6)),), rope_theta=1.5e6)),
])
def test_the_reference_tells_each_departure(departure, program):
    """What the chip's limit has to refuse, at test size: the sound program
    reads far outside the tolerance against the reference WITH the departure,
    and the program with the same departure agrees with it. The first case is
    the test that fails if the routing moves behind the attention: the sound
    program routes on the block's input and on nothing else."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(1), [26, 20], 26)
    params = seeded_params(TransformerLM(cfg), 1, jnp.asarray(tokens), jnp.asarray(mask))
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    err = lambda c, want: np.abs(forward_logprobs(c, params, tokens, mask) - want)[valid].max()
    sound, departed = reference_logprobs(params, cfg, tokens, mask), reference_logprobs(
        params, cfg, tokens, mask, departure)
    assert err(cfg, sound) < TOL < 50 * TOL < err(cfg, departed)
    assert err(dataclasses.replace(cfg, **program), departed) < TOL


def test_the_router_reads_the_blocks_input_and_the_experts_the_normed_attention_output():
    """One block, by hand: the routing `Block` hands its feed-forward is
    `route_softmax` of the block's own input h, un-normed; routed on the
    feed-forward's input the same block chooses other experts."""
    from trlx_tpu.models.transformer import Block

    cfg = tiny_cfg()
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(1, 12, cfg.d_model)), jnp.float32)
    mask = jnp.ones((1, 12), jnp.int32)
    positions = jnp.arange(12)[None]
    bias = {kind: causal_bias(mask, cfg.window_of(kind)) for kind in cfg.attention_kinds}
    block = Block(cfg, **cfg.block_kwargs(1))
    params = jax.jit(block.init)(jax.random.PRNGKey(4), h, bias, positions, attn_mask=mask)["params"]
    seen = []
    routed = moe.routed_experts

    def spy(x, top_i, top_w, *stacks, **kw):
        seen.append((np.asarray(top_i), np.asarray(top_w)))
        return routed(x, top_i, top_w, *stacks, **kw)

    moe.routed_experts = spy
    try:
        with jax.default_matmul_precision("highest"):
            block.apply({"params": params}, h, bias, positions, attn_mask=mask)
            other = Block(dataclasses.replace(cfg, moe_route_on="ffn_input"), **cfg.block_kwargs(1))
            other.apply({"params": params}, h, bias, positions, attn_mask=mask)
            want_i, want_w = moe.route_softmax(h[0], params["mlp"]["router"]["kernel"], cfg.moe_top_k)
    finally:
        moe.routed_experts = routed
    (got_i, got_w), (ffn_i, _) = seen
    np.testing.assert_array_equal(got_i, np.asarray(want_i))
    np.testing.assert_allclose(got_w, np.asarray(want_w), atol=1e-6)
    assert (np.sort(ffn_i, -1) != np.sort(got_i, -1)).any()


def test_softmax_over_the_chosen_logits_is_the_full_softmax_renormalised():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    top_i, top_w = moe.route_softmax(x, kernel, 3)
    logits = np.asarray(x, np.float64) @ np.asarray(kernel, np.float64)
    full = np.exp(logits - logits.max(-1, keepdims=True))
    full /= full.sum(-1, keepdims=True)
    chosen = np.take_along_axis(full, np.asarray(top_i), -1)
    np.testing.assert_allclose(np.asarray(top_w), chosen / chosen.sum(-1, keepdims=True), rtol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(top_i), -1), np.sort(np.argsort(-logits, -1)[:, :3], -1))
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, rtol=1e-6)
    # `route` names the router by `moe_router`; the sigmoid one is what it was
    assert all((a == b).all() for a, b in zip(moe.route(x, kernel, None, 3, "topk_softmax"), (top_i, top_w)))
    bias = jnp.zeros((16,))
    assert all((a == b).all() for a, b in zip(moe.route(x, kernel, bias, 3), moe.route_sigmoid(x, kernel, bias, 3)))


def _attention_layer(kind, seed=6, t=20):
    cfg = tiny_cfg()
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(1, t, cfg.d_model)), jnp.float32)
    mask = jnp.ones((1, t), jnp.int32)
    layer = Attention(cfg, kind=kind)
    bias = causal_bias(mask, cfg.window_of(kind))
    params = jax.jit(layer.init)(jax.random.PRNGKey(seed), h, bias, jnp.arange(t)[None])["params"]

    @jax.jit
    def run(h, positions):
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": params}, h, bias, positions)[0]

    return cfg, h, run


def test_a_full_layer_ignores_positions_and_a_window_layer_does_not():
    """Every position moved (p -> 2 p + 3: a shift alike for all would leave a
    rotary layer's scores where they were, being relative): the full layer's
    output is the same to the bit, the window layer's is not."""
    positions = jnp.arange(20)[None]
    _, h, full = _attention_layer("full_attention")
    np.testing.assert_array_equal(np.asarray(full(h, positions)), np.asarray(full(h, 2 * positions + 3)))
    _, h, banded = _attention_layer("sliding_attention")
    assert float(jnp.abs(banded(h, positions) - banded(h, 2 * positions + 3)).max()) > 1e-2


def test_the_band_counts_the_querys_own_position():
    """A window of 8: query 15 sees keys 8..15 (its own among them). The input
    at position 8 moves its output, the input at position 7 does not; on the
    full layer both do."""
    cfg, h, banded = _attention_layer("sliding_attention")
    assert cfg.sliding_window == 8
    positions = jnp.arange(20)[None]
    moved = lambda run, j: float(jnp.abs(run(h.at[0, j].add(1.0), positions) - run(h, positions))[0, 15].max())
    assert moved(banded, 8) > 1e-3 and moved(banded, 7) == 0.0
    _, h, full = _attention_layer("full_attention")
    assert moved(full, 8) > 1e-3 and moved(full, 7) > 1e-3
    bias = np.asarray(causal_bias(jnp.ones((1, 20), jnp.int32), 8))[0, 0]
    assert bias[15, 8] == 0.0 and bias[15, 7] < -1e8 and bias[15, 15] == 0.0 and bias[15, 16] < -1e8


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
    seqs = [np.concatenate([p, np.asarray(new, np.int32)]) for p, new in zip(prompts, out)]
    tokens = np.zeros((len(seqs), WIDTH), np.int32)
    mask = np.zeros_like(tokens)
    for r, seq in enumerate(seqs):
        tokens[r, :len(seq)], mask[r, :len(seq)] = seq, 1
    want = reference_logprobs(params["lm"], cfg, tokens, mask)
    return [np.abs(np.asarray(lps) - want[r, len(p) - 1:len(p) - 1 + len(lps)]).max()
            for r, (p, lps) in enumerate(zip(prompts, got))]


@pytest.mark.parametrize("token_block", [0, 8])
def test_engine_end_to_end_matches_the_reference_with_no_fallback(token_block, monkeypatch):
    """Prefill through the fresh-prompt program (the prompt attends within
    itself, banded on the window layers, K/V into the arena; with
    `moe_token_block` 8 the routing of the whole prompt is made on the block's
    input and handed to the experts 8 positions at a time), then paged decode
    through the kernel in interpret mode on every layer, windowed calls and full
    ones, a group of 3: past the window's edge (8) and across block boundaries
    (4), rows of unequal length, one shorter than the window. The flash forward
    kernels are interpreted too (TRLX_TPU_KERNELS)."""
    monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    cfg = tiny_cfg(attn_impl="flash", moe_token_block=token_block)
    model = CausalLMPolicy(cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (21, 5, 13)]
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 11, tokens, jnp.ones_like(tokens))
    with jax.default_matmul_precision("highest"):
        engine, out, got = run_engine(cfg, params, prompts, 11, decode_kernel="auto")
    assert engine.decode_path == "interpret"
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["kv_kernel_dispatches"] == 11
    assert [len(lps) for lps in got] == [11] * 3 and max(engine_errors(cfg, params, prompts, out, got)) < TOL
    # the step's counters: two kinds walked as laguna's, and a layer that holds every expert
    # says how many it met: 3 rows x 3 experts a token meet at most 9 of 16
    walk = engine._kv_walk()
    cols = np.asarray([len(p) + 11 + 1 for p in prompts])
    assert walk["layers"] == 4 and walk["resident"] == 4 * cols.sum()
    assert walk["walked_full"] == (-(-cols // 4) * 4).sum()
    assert walk["walked_window"] == 3 * ((-(-cols // 4) - np.maximum(cols - 8, 0) // 4) * 4).sum()
    assert stats["moe_dropped_tokens"] == 0.0 and stats["moe_local_assignment_share"] == 1.0
    assert stats["moe_experts_held"] == 16.0 and 3.0 <= stats["moe_experts_met"] <= 9.0


def _layer_inputs(cfg, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
    params = jax.jit(SparseMoE(cfg).init)(jax.random.PRNGKey(seed), x)["params"]
    return x, params


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide under the new router: what each of
    4 chips computes for its 4 of 16 experts (the router scoring all 16 on every
    chip, `moe_local_experts` / `moe_local_offset`, nothing standing in for the
    absent ones) adds up to the reference's uncut layer; the reference, given
    the same share, gives the same part; a share counts no experts met."""
    whole = tiny_cfg()
    x, params = _layer_inputs(whole, 21)
    assert "expert_bias" not in params  # this router has no bias
    reference = lambda p, offset: ref.expert_ffn(
        x, *ref.routing(x, p, whole.moe_top_k), p, offset=offset, act=lambda z: jnp.maximum(z, 0.0), int8=False)
    with jax.default_matmul_precision("highest"):
        want = reference(params, 0)
        got, state = SparseMoE(whole).apply({"params": params}, x, mutable=["moe_stats"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        (stats,) = state["moe_stats"]["stats"]
        assert float(stats["experts_held"]) == 16.0 and 3.0 <= float(stats["experts_met"]) <= 16.0
        total = jnp.zeros_like(want)
        for chip in range(4):
            cfg = tiny_cfg(moe_local_experts=4, moe_local_offset=4 * chip)
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 4, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part, state = SparseMoE(cfg).apply({"params": {**params, **share}}, x, mutable=["moe_stats"])
            np.testing.assert_allclose(np.asarray(part), np.asarray(reference({**params, **share}, 4 * chip)),
                                       atol=2e-5)
            (stats,) = state["moe_stats"]["stats"]
            assert "experts_met" not in stats and 0 < float(stats["local_assignment_share"]) < 1
            assert float(jnp.abs(part).max()) > 0
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def test_hf_config_keys_and_tensor_names_round_trip(tmp_path):
    """The benchmark file's published keys give the program's configuration and
    come back; a random state dict under the family's tensor names loads into
    the tree and goes out again letter for letter (unchecked against the
    published weights: `assumed`)."""
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        bench = json.load(f)
    published = {k: v for k, v in bench.items() if k != "bench"}
    assert sorted(bench["bench"]["assumed"]) == [
        "rope", "router_input", "secondary_experts", "sparse_reglu", "tensor_names"]
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    extra = dict(bench["bench"]["program"]["model_extra_configs"])
    want = config_from_preset("smallthinker-21b-a3b", extra.pop("vocab_size"), **{**extra, "attn_impl": "xla"},
                              moe_token_block=0, hf_family="smallthinker", dtype=jnp.float32)
    assert cfg == want
    again = hf_interop.config_to_hf(cfg)
    assert {k: again[k] for k in published if k != "model_name"} == {
        k: v for k, v in published.items() if k != "model_name"}
    again_dir = tmp_path / "again"
    again_dir.mkdir()
    (again_dir / "config.json").write_text(json.dumps(again))
    assert hf_interop.config_from_hf(str(again_dir), dtype=jnp.float32) == cfg
    for key, value in (("rope_scaling", {"type": "yarn"}), ("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False)):
        with pytest.raises(NotImplementedError, match=f"smallthinker with {key}="):
            hf_interop._smallthinker_kwargs({**published, key: value})
    with pytest.raises(NotImplementedError, match="rope_layout != sliding_window_layout"):
        hf_interop._smallthinker_kwargs({**published, "rope_layout": [1] * 8})

    tiny = tiny_cfg(moe_local_experts=4, moe_local_offset=8, hf_family="smallthinker")
    assert hf_interop.infer_family(tiny_cfg()) == "smallthinker"
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jitted_init(CausalLMPolicy(tiny))(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    rng = np.random.default_rng(0)
    names = hf_interop.params_to_hf_state_dict(template, tiny)
    sd = {k: rng.normal(size=v.shape).astype(np.float32) for k, v in names.items()}
    layer = "model.layers.2."
    assert {layer + n for n in (
        "input_layernorm.weight", "post_attention_layernorm.weight", "self_attn.q_proj.weight",
        "self_attn.k_proj.weight", "self_attn.v_proj.weight", "self_attn.o_proj.weight",
        "block_sparse_moe.primary_router.weight", "block_sparse_moe.experts.8.gate.weight",
        "block_sparse_moe.experts.11.down.weight")} <= set(sd)
    assert {"model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"} <= set(sd)
    assert layer + "block_sparse_moe.experts.7.up.weight" not in sd
    assert sd[layer + "block_sparse_moe.primary_router.weight"].shape == (16, 64)
    lm = hf_interop._load_smallthinker(sd, tiny)
    jax.tree_util.tree_map(lambda t, a: np.testing.assert_equal(t.shape, np.shape(a)), template["lm"], lm)
    back = hf_interop.params_to_hf_state_dict({"lm": lm}, tiny)
    assert sorted(back) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k])


DENSE = dict(vocab_size=VOCAB, d_model=32, n_layers=1, n_heads=2, d_ff=64, moe_experts=4)


@pytest.mark.parametrize("fields, error, match", [
    (dict(moe_shared_d_ff=16), NotImplementedError, "a shared expert and a routed scale: only the grouped dispatch of `SparseMoE`"),
    (dict(moe_routed_scale=2.5), NotImplementedError, "a shared expert and a routed scale: only"),
    (dict(moe_local_experts=2), NotImplementedError, "moe_local_experts .*: only the grouped dispatch of `SparseMoE`"),
    (dict(moe_route_on="block_input"), NotImplementedError, "moe_route_on='block_input': only"),
    (dict(moe_router="topk_softmax", moe_n_group=2, moe_topk_group=1), NotImplementedError,
     "group-limited routing .* needs moe_router='sigmoid'"),
    (dict(moe_router="softmax_topk"), ValueError, "moe_router must be 'softmax' or one of"),
    (dict(moe_router="sigmoid", moe_route_on="attention_output"), ValueError, "moe_route_on must be"),
])
def test_what_only_the_grouped_dispatch_can_do_is_refused_under_the_dense_router(fields, error, match):
    """The refusals that said "needs moe_router='sigmoid'" name `SparseMoE`'s
    two routers now; each field is accepted under either of them."""
    with pytest.raises(error, match=match):
        TransformerConfig(**{**DENSE, **fields})
    if error is NotImplementedError and "moe_n_group" not in fields:
        for router in ("sigmoid", "topk_softmax"):
            assert TransformerConfig(**{**DENSE, **fields, "moe_router": router}).has_sparse_moe
