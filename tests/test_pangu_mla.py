"""openPangu-style stacks (latent attention over one cached plane of latents,
a norm after attention and after the feed-forward too, sigmoid-routed experts
beside a shared one, a multi-token-prediction block) against the benchmark's
plain reference `bench/reference/pangu_ultra_moe.py`, at test size on the
CPU, on seeded weights.

The reference decompresses per-head keys and values; the program's cached
steps run ABSORBED over the latents, so every cached test here holds one
form against the other.

Tolerances. Float32 program against float32 reference, both at `highest`:
1e-5 on a logprob or a logit (the readings are 1e-6 to 4e-6: some tens of
float32 roundings through 4 layers); a sandwich norm left out, the rotary
part of the score left out or 1/sqrt(qk_nope) for 1/sqrt(qk_nope + qk_rope)
each move a logprob by 1e-2 or more at this size
(`test_the_reference_tells_each_departure`)."""

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
from trlx_tpu.models import CausalLMPolicy, CausalLMWithValueHead, config_from_preset  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, LatentAttention, SparseMoE, TransformerLM, init_kv_cache, init_paged_kv_arena)
from trlx_tpu.ops import paged_attention as paged  # noqa: E402

VOCAB = 96
TOL = 1e-5
ref = load_module("reference/pangu_ultra_moe.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 2, **kw}
    return config_from_preset("openpangu-ultra-moe-tiny", VOCAB, **kw)


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "pangu_ultra_moe"), "expert_offset": cfg.moe_local_offset}


def seeded_params(model, seed, *init_args):
    """Every leaf drawn from the seed, the norms' scales and the selection
    bias too (a fresh init leaves them at one and at zero)."""
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


def forward_logprobs(cfg, params, tokens, mask, program=jitted_forward):
    with jax.default_matmul_precision("highest"):
        logits = program(cfg)(params, tokens, mask)
    return np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))


def test_presets_state_every_published_size_and_what_a_token_caches():
    full = PRESETS["openpangu-ultra-moe-718b"]
    assert (full["d_model"], full["n_layers"], full["n_heads"], full["d_ff"]) == (7680, 61, 128, 18432)
    assert (full["q_lora_rank"], full["kv_lora_rank"], full["qk_nope_head_dim"], full["qk_rope_head_dim"],
            full["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (full["moe_experts"], full["moe_top_k"], full["moe_d_ff"], full["moe_dense_layers"],
            full["moe_routed_scale"], full["mtp_layers"], full["sandwich_norm"]) == (256, 8, 2048, 3, 2.5, 1, True)
    # the benchmark's cut: its parameters and its cache, counted from shapes
    with open(os.path.join(BENCH, "configs", "openpangu-ultra-moe-718b.json")) as f:
        extra = dict(json.load(f)["bench"]["program"]["model_extra_configs"])
    cut = config_from_preset("openpangu-ultra-moe-718b", extra.pop("vocab_size"), **extra)
    assert cut.layer_types == ("latent_attention",) * 5 and cut.attention_kinds == ("latent_attention",)
    assert cut.latent_width == 576 and cut.cached_values_per_token == 5 * 576
    assert cut.cache_planes(0) == (576,)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: CausalLMPolicy(cut).init(jax.random.PRNGKey(0), tokens, tokens)["params"])
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 3_409_191_424
    # the arena: one plane a layer, tokens x 576 x itemsize and nothing else
    arena = jax.eval_shape(lambda: init_paged_kv_arena(cut, 12289, 32, jnp.bfloat16))
    assert [sorted(layer) for layer in arena] == [["latent"]] * 5
    assert sum(a.size * a.dtype.itemsize for layer in arena for a in layer.values()) == 12289 * 32 * 5 * 576 * 2
    tiny = tiny_cfg()
    dense = init_kv_cache(tiny, 3, 20)
    assert [{k: v.shape for k, v in layer.items()} for layer in dense["layers"]] == [{"latent": (3, 20, 40)}] * 4
    assert (tiny.qk_nope_head_dim, tiny.qk_rope_head_dim, tiny.v_head_dim) == (16, 8, 12)  # three widths apart


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("seed", [0, 3_000_000_019 % (2 ** 31)])
def test_forward_matches_the_reference(seed, attn_impl):
    cfg = tiny_cfg(attn_impl=attn_impl)
    tokens, mask = left_padded(np.random.default_rng(seed), [32, 21, 9, 3], WIDTH)
    params = seeded_params(TransformerLM(cfg), seed, jnp.asarray(tokens), jnp.asarray(mask))
    got = forward_logprobs(cfg, params, tokens, mask)
    want = reference_logprobs(params, cfg, tokens, mask)
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    assert np.abs(got - want)[valid].max() < TOL


def test_the_reference_tells_each_departure(monkeypatch):
    """What the limit of `correct` has to refuse on the chip, at test size:
    each of these programs is far from the reference where the sound one is
    within `TOL`."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(2), [32, 21, 9, 3], WIDTH)
    params = seeded_params(TransformerLM(cfg), 2, jnp.asarray(tokens), jnp.asarray(mask))
    want = reference_logprobs(params, cfg, tokens, mask)
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    # a program traced anew every call: the patches below are read when the forward is traced
    traced_anew = jitted_forward.__wrapped__
    err = lambda c: np.abs(forward_logprobs(c, params, tokens, mask, traced_anew) - want)[valid].max()
    assert err(cfg) < TOL
    assert err(dataclasses.replace(cfg, sandwich_norm=False)) > 1e-2
    # 1/sqrt(qk_nope) in place of 1/sqrt(qk_nope + qk_rope): a wider nope head moves the scale alone
    import trlx_tpu.models.transformer as tr

    sqrt = np.sqrt
    monkeypatch.setattr(tr.np, "sqrt", lambda x: sqrt(x - cfg.qk_rope_head_dim) if x == 24 else sqrt(x))
    assert err(cfg) > 1e-2
    monkeypatch.undo()
    # the rotary part of the score left out: positions stop mattering
    monkeypatch.setattr(tr, "apply_rope", lambda x, *a, **k: jnp.zeros_like(x))
    assert err(cfg) > 1e-2


def test_sandwich_off_is_the_plain_block_and_matches_the_reference_without_it():
    cfg = tiny_cfg(sandwich_norm=False)
    tokens, mask = left_padded(np.random.default_rng(4), [32, 21, 9, 3], WIDTH)
    params = seeded_params(TransformerLM(cfg), 4, jnp.asarray(tokens), jnp.asarray(mask))
    assert not any("ln_post" in k for k in params["block_1"])
    assert sorted(k for k in seeded_params(TransformerLM(tiny_cfg()), 4, jnp.asarray(tokens), jnp.asarray(mask))[
        "block_1"] if k.startswith("ln_")) == ["ln_attn", "ln_mlp", "ln_post_attn", "ln_post_mlp"]
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    got, want = forward_logprobs(cfg, params, tokens, mask), reference_logprobs(params, cfg, tokens, mask)
    assert np.abs(got - want)[valid].max() < TOL


def test_multi_token_block_matches_the_reference():
    """`forward(mtp=True)`: the block's logits for position t + 2, from the
    state under the final norm and the next token's embedding; and the main
    logits beside them, unchanged."""
    cfg = tiny_cfg(mtp_layers=1)
    tokens, mask = left_padded(np.random.default_rng(6), [32, 21, 9, 3], WIDTH)
    lm = TransformerLM(cfg)
    params = seeded_params(lm, 6, jnp.asarray(tokens), jnp.asarray(mask))
    assert sorted(params["mtp_0"]) == ["block", "eh_proj", "enorm", "hnorm"]
    assert params["mtp_0"]["eh_proj"]["kernel"].shape == (2 * cfg.d_model, cfg.d_model)
    with_mtp = jax.jit(lambda p, t, m: lm.apply({"params": p}, t, m, mtp=True, method=TransformerLM.forward))
    with jax.default_matmul_precision("highest"):
        logits, _, caps = with_mtp(params, tokens, mask)
        plain_logits = jitted_forward(cfg)(params, tokens, mask)
    want, (want_mtp,) = ref.logits(params, tokens, mask, sizes_of(cfg), mtp=True)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain_logits))
    valid = mask.astype(bool)
    assert np.abs(np.asarray(logits) - np.asarray(want))[valid].max() < TOL
    has_next = (mask * np.pad(mask[:, 1:], ((0, 0), (0, 1)))).astype(bool)
    assert np.abs(np.asarray(caps["mtp"][0]) - np.asarray(want_mtp))[has_next].max() < TOL
    # the block reads the NEXT token: another token there moves its logits and not the main ones'
    other = tokens.copy()
    other[:, -1] = (other[:, -1] % (VOCAB - 1)) + 1
    with jax.default_matmul_precision("highest"):
        _, _, caps2 = with_mtp(params, other, mask)
    assert np.abs(np.asarray(caps2["mtp"][0]) - np.asarray(caps["mtp"][0]))[:, -2].max() > 1e-3
    with pytest.raises(NotImplementedError, match="mtp=True takes a whole forward"):
        lm.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask), mtp=True, window=(0, 4),
                 method=TransformerLM.forward)


def test_sampler_through_the_dense_cache_matches_the_reference():
    """`generate`: the prefill of left-padded prompts (`flash_prefill`:
    decompressed, within the block), then the fused decode loop absorbed over
    the dense latent cache, every captured logprob against the reference's
    full forward over the sampled sequence."""
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
    seqs = [np.concatenate([p, np.asarray(new, np.int32)]) for p, new in zip(prompts, out)]
    tokens = np.zeros((len(seqs), WIDTH), np.int32)
    mask = np.zeros_like(tokens)
    for r, seq in enumerate(seqs):
        tokens[r, :len(seq)], mask[r, :len(seq)] = seq, 1
    want = reference_logprobs(params["lm"], cfg, tokens, mask)
    return [np.abs(np.asarray(lps) - want[r, len(p) - 1:len(p) - 1 + len(lps)]).max()
            for r, (p, lps) in enumerate(zip(prompts, got))]


@pytest.mark.parametrize("path", ["interpret", "env", "xla"])
def test_engine_end_to_end_matches_the_reference_with_no_fallback(path, monkeypatch):
    """Prefill through the fresh-prompt program (the prompt decompressed and
    attending within itself, its latents into the arena), then absorbed paged
    decode with a step in flight: through the kernel in interpret mode
    (`env` interprets the flash forward too) and through the gather path,
    across block boundaries (4), rows of unequal length, against the
    reference's unabsorbed full forward."""
    if path == "env":
        monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMPolicy(cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (21, 5, 13)]
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 11, tokens, jnp.ones_like(tokens))
    with jax.default_matmul_precision("highest"):
        engine, out, got = run_engine(cfg, params, prompts, 11, decode_kernel="auto" if path == "env" else path)
    assert engine.decode_path == ("xla" if path == "xla" else "interpret")
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["decode_steps_ahead_total"] > 0
    assert sorted(k for k in engine._paged_insert_fns) == [(1, 32, True), (2, 16, True)]
    assert [len(lps) for lps in got] == [11] * 3 and max(engine_errors(cfg, params, prompts, out, got)) < TOL
    # the arena and the step's counters: one plane of 40 values a token a layer, float32 here
    assert stats["kv_bytes_per_token"] == 4 * 40 * 4
    assert stats["kv_pool_bytes"] == sum(a.nbytes for layer in engine._pool["layers"] for a in layer.values())
    assert stats["kv_pool_bytes"] == engine._n_blocks * 4 * stats["kv_bytes_per_token"]
    walk = engine._kv_walk()
    cols = np.asarray([len(p) + 11 + 1 for p in prompts])
    walked = 3 * engine._n_tbl * 4 if path == "xla" else (-(-cols // 4) * 4).sum()
    assert walk["layers"] == 4 and walk["resident"] == 4 * cols.sum()
    assert (walk["walked_full"], walk["walked_window"], walk["walked_latent"]) == (0, 0, 4 * walked)
    assert walk["bytes"] == 4 * walked * 40 * 4
    assert stats["moe_dropped_tokens"] == 0.0 and 0 < stats["moe_local_assignment_share"] < 1


def test_latent_kernel_against_its_plain_reference_with_dead_entries_and_a_half_full_block():
    """`paged_attention_latent` in interpret mode: rows of unequal length
    whose tables end in dead entries (an id past the arena, the zero block),
    a last block half full, a mask with a hole, an all-masked row (zeros),
    more table entries than a tile; float32 and bfloat16 arenas."""
    rng = np.random.default_rng(0)
    b, nh, dc, dr, blk, n_blocks, n_tbl = 4, 4, 32, 8, 4, 40, 9
    width = dc + dr
    lens = [33, 6, 0, 18]
    table = np.full((b, n_tbl), n_blocks + 5, np.int32)  # dead: never dereferenced
    ids = iter(rng.permutation(np.arange(1, n_blocks)))
    for r, n in enumerate(lens):
        for j in range(-(-n // blk)):
            table[r, j] = next(ids)
    table[1, 2:] = 0  # the zero block behind a short row
    mask = np.asarray([[1] * n + [0] * (n_tbl * blk - n) for n in lens], np.int32)
    mask[0, 7] = 0
    q = jnp.asarray(rng.normal(size=(b, nh, width)), jnp.float32)
    for dtype, tol in ((jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)):
        tokens = jnp.asarray(rng.normal(size=(n_blocks, blk, width)), dtype)
        arena = paged._pack_latent(tokens, dc)
        assert arena.shape == (n_blocks, blk // 2, 2 * width)
        np.testing.assert_array_equal(np.asarray(paged._unpack_latent(arena, dc)), np.asarray(tokens))
        kw = dict(values=dc, scale=(16 + dr) ** -0.5, out_dtype=jnp.float32)
        safe = jnp.asarray(np.where(table >= n_blocks, 0, table))  # the gather dereferences every entry
        want = paged.paged_latent_reference(q, arena, safe, jnp.asarray(mask), **kw)
        got = paged.paged_attention_latent(q, arena, jnp.asarray(table), jnp.asarray(mask), interpret=True, **kw)
        assert got.shape == (b, nh, dc)
        live = [0, 1, 3]  # an all-masked row: zeros from the kernel, a mean over nothing from the shadow
        np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=tol)
        assert float(jnp.abs(got[2]).max()) == 0.0
        # by hand, row 3: softmax over its 18 latents, the value their first dc columns
        rows = np.asarray(tokens, np.float32)[table[3, :5]].reshape(-1, width)[:18]
        s = np.asarray(q[3]) @ rows.T * kw["scale"]
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(np.asarray(got[3]), (p / p.sum(-1, keepdims=True)) @ rows[:, :dc], atol=tol)


def test_latent_write_patches_whole_blocks_and_drops_what_is_masked():
    rng = np.random.default_rng(1)
    blk, width, dc = 4, 10, 6
    layer = paged.init_paged_latent_layer(7, blk, width, jnp.float32)
    assert layer["latent"].shape == (7, 2, 20)
    table = jnp.asarray([[3, 5, 9], [2, 1, 9]], jnp.int32)  # 9: past the arena, dropped
    latent = jnp.asarray(rng.normal(size=(2, 6, width)), jnp.float32)
    valid = jnp.asarray([[1, 1, 1, 1, 1, 0], [1, 1, 1, 0, 0, 0]])
    new = paged.paged_latent_write(layer, latent, table, jnp.asarray([2, 0]), valid, values=dc)
    dense = np.asarray(paged.paged_latent_gather(new, jnp.asarray([[3, 5], [2, 1]]), values=dc))
    np.testing.assert_array_equal(dense[0, 2:7], np.asarray(latent[0, :5]))
    np.testing.assert_array_equal(dense[1, :3], np.asarray(latent[1, :3]))
    assert np.abs(dense[0, :2]).max() == 0 and np.abs(dense[0, 7:]).max() == 0 and np.abs(dense[1, 3:]).max() == 0
    with pytest.raises(NotImplementedError, match="int8 latent arena"):
        paged.init_paged_latent_layer(7, blk, width, jnp.int8)
    with pytest.raises(ValueError, match="must be even"):
        paged.init_paged_latent_layer(7, 3, width, jnp.float32)


def _layer_inputs(cfg, seed, tokens=40):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, tokens, cfg.d_model)), jnp.float32)
    params = seeded_params(SparseMoE(cfg), seed, x)
    return x, params


def test_the_thirty_two_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """Section 4 of the model-configs guide, for the deployment the cell
    states: what each of 32 chips' expert layers gives (1 of 32 experts held,
    the router scoring all 32 on every chip, the shared expert computed whole
    on each) adds up, the shared expert's part taken once, to the reference's
    uncut layer; and the reference given a share gives that share's part."""
    kw = dict(d_model=32, moe_d_ff=16, moe_shared_d_ff=16, moe_experts=32, moe_top_k=4, n_layers=2,
              layer_types=("latent_attention",) * 2)
    whole = tiny_cfg(moe_local_experts=0, **kw)
    x, params = _layer_inputs(whole, 9)
    layer = lambda p, c: ref.expert_ffn(x[0], p, top_k=c.moe_top_k, offset=c.moe_local_offset,
                                        scaling=c.moe_routed_scale, int8=False)
    with jax.default_matmul_precision("highest"):
        want = layer(params, whole)
        shared = ref.glu(x[0], *(params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")), False)
        assert float(jnp.abs(shared).max()) > 0
        total = shared
        for chip in range(32):
            cfg = tiny_cfg(moe_local_experts=1, moe_local_offset=chip, **kw)
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 32, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)[0]
            np.testing.assert_allclose(np.asarray(part), np.asarray(layer({**params, **share}, cfg)), atol=5e-5)
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)


def test_one_ppo_cycle_through_train_at_pangu_tiny(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=20, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3),
        model=dict(model_path="random:openpangu-ultra-moe-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=2)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"], eval_prompts=["ab", "cd"], config=config)
    assert trainer.iter_count == 1 and trainer.model_cfg.has_latent_layers
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    assert any("kv_b_proj" in k for k in train) and any("ln_post_mlp" in k for k in train)
    assert [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))] == []


def test_hf_config_keys_and_tensor_names_round_trip(tmp_path):
    """`model_type: pangu_ultra_moe`: the benchmark file's published keys
    give the program's configuration and come back; a random state dict
    under the family's tensor names loads into the tree and goes out again
    letter for letter (unchecked against the published weights)."""
    with open(os.path.join(BENCH, "configs", "openpangu-ultra-moe-718b.json")) as f:
        bench = json.load(f)
    published = {k: v for k, v in bench.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    extra = dict(bench["bench"]["program"]["model_extra_configs"])
    extra.pop("moe_local_experts")  # the file's `n_routed_experts` is the experts held
    want = config_from_preset("openpangu-ultra-moe-718b", extra.pop("vocab_size"),
                              **{**extra, "attn_impl": "xla"}, moe_experts=8, hf_family="pangu_ultra_moe",
                              dtype=jnp.float32)
    assert cfg == want
    again_dir = tmp_path / "again"
    again_dir.mkdir()
    (again_dir / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    assert hf_interop.config_from_hf(str(again_dir), dtype=jnp.float32) == cfg

    tiny = tiny_cfg(mtp_layers=1, moe_local_experts=4, moe_local_offset=2, hf_family="pangu_ultra_moe")
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jitted_init(CausalLMPolicy(tiny))(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    # a random state dict with the names and shapes the exporter gives
    rng = np.random.default_rng(0)
    names = hf_interop.params_to_hf_state_dict(template, tiny)
    sd = {k: rng.normal(size=v.shape).astype(np.float32) for k, v in names.items()}
    layer = "model.layers.1."
    assert {layer + n for n in (
        "input_layernorm.weight", "post_attention_layernorm.weight", "pre_mlp_layernorm.weight",
        "post_mlp_layernorm.weight", "self_attn.q_a_proj.weight", "self_attn.q_a_layernorm.weight",
        "self_attn.q_b_proj.weight", "self_attn.kv_a_proj_with_mqa.weight", "self_attn.kv_a_layernorm.weight",
        "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight", "mlp.gate.weight",
        "mlp.experts.2.gate_proj.weight", "mlp.experts.5.down_proj.weight",
        "mlp.shared_experts.up_proj.weight")} <= set(sd)
    assert {"model.layers.4.enorm.weight", "model.layers.4.hnorm.weight", "model.layers.4.eh_proj.weight",
            "model.layers.0.mlp.gate_proj.weight", "model.norm.weight", "lm_head.weight"} <= set(sd)
    assert layer + "mlp.experts.1.gate_proj.weight" not in sd and sd[layer + "self_attn.kv_b_proj.weight"].shape == (
        4 * (16 + 12), 32)
    lm = hf_interop._load_pangu_ultra_moe(sd, tiny)
    jax.tree_util.tree_map(lambda t, a: np.testing.assert_equal(t.shape, np.shape(a)), template["lm"], lm)
    back = hf_interop.params_to_hf_state_dict({"lm": lm}, tiny)
    assert sorted(back) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k])


def test_paths_that_cannot_follow_refuse_by_name():
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.attention import flash_attention
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    build = lambda **kw: InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2,
                                         max_prompt_len=8, **kw)
    with pytest.raises(NotImplementedError, match="prefix_cache over a latent cache"):
        build(kv_paging=True, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="dense slot pool .* over a latent cache"):
        build()
    with pytest.raises(NotImplementedError, match="int8 arena .* over a latent cache"):
        build(kv_paging=True, kv_cache_dtype="int8")
    with pytest.raises(NotImplementedError, match="sessions .* over a latent cache"):
        build(kv_paging=True).enable_sessions()
    with pytest.raises(NotImplementedError, match="int8 latent arena"):
        init_paged_kv_arena(cfg, 4, 4, jnp.int8)
    q, v = jnp.ones((1, 16, 2, 8)), jnp.ones((1, 16, 2, 4))
    assert flash_attention(q, q, v).shape == v.shape
    with pytest.raises(NotImplementedError, match="value heads narrower"):
        jax.grad(lambda a: flash_attention(a, q, v).sum())(q)
    with pytest.raises(ValueError, match="q_lora_rank"):
        tiny_cfg(q_lora_rank=-1)  # 0 or None is a full-rank query (tests/test_ling_flash.py)
    with pytest.raises(NotImplementedError, match="latent_attention layers with lora_rank"):
        tiny_cfg(lora_rank=4, moe_experts=0, moe_router="softmax", moe_shared_d_ff=0, moe_routed_scale=1.0,
                 moe_local_experts=0)
    with pytest.raises(NotImplementedError, match="sandwich_norm under parallel_residual"):
        config_from_preset("neox-tiny", VOCAB, sandwich_norm=True)


def test_flops_and_cache_bytes_count_the_latent_layers():
    from trlx_tpu.observability import flops, hbm

    cfg = tiny_cfg()
    d, heads = cfg.d_model, cfg.n_heads
    attn = 2 * (d * 24 + 24 * heads * 24 + d * 40 + 32 * heads * 28 + heads * 12 * d)
    dense = 6 * d * cfg.d_ff
    experts = 2 * d * 8 + (2 * 2 / 8) * 6 * d * cfg.expert_d_ff + 6 * d * cfg.moe_shared_d_ff
    assert [flops.layer_matmul_flops(cfg, i) for i in range(4)] == [attn + dense] + [attn + experts] * 3
    assert flops.layer_attention_flops(cfg, 1, 20) == 2 * 20 * heads * (24 + 12)
    arena = init_paged_kv_arena(cfg, 9, 4, jnp.bfloat16)
    assert hbm.paged_arena_bytes(cfg, 9, 4, "bfloat16") == sum(a.nbytes for layer in arena for a in layer.values())
    assert hbm.paged_arena_bytes(cfg, 9, 4, "bfloat16") == 9 * 4 * 4 * 40 * 2
    dense_cache = init_kv_cache(cfg, 3, 20, jnp.bfloat16)
    assert hbm.decode_state_bytes(cfg, 3, 20, "bfloat16") == sum(
        a.nbytes for layer in dense_cache["layers"] for a in layer.values())
    # the families that cache K and V by head count as they did
    neox = config_from_preset("neox-tiny", VOCAB)
    assert hbm.paged_arena_bytes(neox, 9, 4, "bfloat16") == hbm.kv_arena_bytes(
        neox.n_layers, neox.kv_heads, neox.head_dim, 9, 4, "bfloat16")


def test_latent_attention_module_absorbed_equals_decompressed_at_a_prefill_behind_a_prefix():
    """A t > 1 step against a cache that already holds positions (the
    general cached path: absorbed, any t) gives what the whole block gives
    without a cache (decompressed)."""
    from trlx_tpu.models.transformer import cached_bias, train_bias

    cfg = tiny_cfg()
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(2, 12, cfg.d_model)), jnp.float32)
    mask = jnp.ones((2, 12), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    mod = LatentAttention(cfg, kind="latent_attention")
    params = jax.jit(mod.init)(jax.random.PRNGKey(0), h, train_bias(cfg, mask, "latent_attention"), pos)["params"]
    cached = jax.jit(mod.apply, static_argnums=5)  # the cache index is the program's, as in `decode_step`
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(mod.apply)({"params": params}, h, train_bias(cfg, mask, "latent_attention"), pos)
        cache = {"latent": jnp.zeros((2, 16, cfg.latent_width), jnp.float32)}
        seen = jnp.zeros((2, 16), jnp.int32).at[:, :5].set(1)
        _, cache = cached({"params": params}, h[:, :5], cached_bias(cfg, seen, pos[:, :5], 0, "latent_attention"),
                          pos[:, :5], cache, 0)
        seen = seen.at[:, 5:12].set(1)
        got, _ = cached({"params": params}, h[:, 5:], cached_bias(cfg, seen, pos[:, 5:], 5, "latent_attention"),
                        pos[:, 5:], cache, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 5:]), atol=2e-6)
