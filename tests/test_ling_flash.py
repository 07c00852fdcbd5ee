"""Ling-3.0-flash-style stacks (Kimi delta attention keeping a recurrent
matrix and convolution tails a ROW, latent attention keeping one plane a
TOKEN, a per-head gate on both, group-limited sigmoid routing beside a shared
expert) against the benchmark's plain reference `bench/reference/ling_flash.py`,
at test size on the CPU, on seeded weights.

The leaves are the benchmark's (`bench/benchlib/weights.py`: every leaf from
the seed) with every `dt_bias/bias` shifted by -4: at the benchmark's own
leaves a key channel forgets in a few tokens (g near -2.5 a step), so a stale
or misplaced state far back would not show; shifted, g is near -0.09 and a
state forty tokens back still counts. The reference scans the recurrence a
token at a time and decompresses per-head keys and values; the program runs
chunks, then the decode kernel, and absorbed latent attention.

Tolerances. Float32 program against float32 reference, both at `highest`:
2e-5 on a logprob (readings 2e-6 to 9e-6); each assumed item flipped in the
reference moves a logit by 1 or more at this size."""

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

from benchlib import weights  # noqa: E402
from benchlib.files import load_module  # noqa: E402

from parity import jitted_forward, jitted_init  # noqa: E402
from trlx_tpu.inference import InferenceEngine, Scheduler  # noqa: E402
from trlx_tpu.models import CausalLMPolicy, CausalLMWithValueHead, config_from_preset  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, LayerKeeps, SparseMoE, init_kv_cache, init_paged_kv_arena)
from trlx_tpu.observability import flops, hbm  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn  # noqa: E402
from trlx_tpu.ops import linear_attention  # noqa: E402

VOCAB = 96
TOL = 2e-5
ROWS, WIDTH = 3, 80
ref = load_module("reference/ling_flash.py")
plain = load_module("reference/plain_ops.py")


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 4, **kw}
    return config_from_preset("ling-flash-tiny", VOCAB, **kw)


def sizes_of(cfg, *departures):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "ling_flash"), "expert_offset": cfg.moe_local_offset,
            "departures": list(departures)}


def slow_decay(params):
    from flax.traverse_util import flatten_dict, unflatten_dict

    return unflatten_dict({k: v - 4.0 if k[-2] == "dt_bias" else v for k, v in flatten_dict(params).items()})


def seeded_params(model, seed):
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(model, tokens, jnp.ones_like(tokens))
    return slow_decay(weights.make_params(shapes, seed, jnp.float32))


@pytest.fixture(scope="module")
def policy():
    cfg = tiny_cfg(attn_impl="flash")
    return cfg, seeded_params(CausalLMPolicy(cfg), 41)


def reference_logprobs(lm_params, cfg, tokens, mask, *departures):
    tokens, mask = np.asarray(tokens), np.asarray(mask)
    rows, width = tokens.shape
    pad = lambda a: np.pad(a, ((0, ROWS - rows), (0, WIDTH - width)))
    out = ref.logprobs(lm_params, pad(tokens), pad(mask), sizes_of(cfg, *departures))
    return np.asarray(out)[:rows, : width - 1]


def left_padded(rng, lens, width):
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


def test_presets_state_every_published_size_and_what_each_layer_keeps():
    published = config_from_preset("ling-3.0-flash-vl", 157184)
    assert (published.n_layers, published.d_model, published.n_heads, published.head_dim) == (42, 2560, 32, 128)
    assert published.layer_types.count("latent_attention") == 7 and published.layer_types[5] == "latent_attention"
    assert (published.moe_experts, published.moe_n_group, published.moe_topk_group, published.moe_top_k) == (512, 8, 4, 8)
    assert published.q_lora_rank == 0 and published.latent_width == 576 and published.kda_lower_bound == -5.0
    cut = config_from_preset("ling-3.0-flash-vl", 19648, n_layers=6, moe_dense_layers=1, moe_local_experts=64,
                             dtype=jnp.bfloat16)
    assert cut.layer_types == ("linear_attention",) * 5 + ("latent_attention",)
    # a token: the latent layer's one plane; a slot: 2 MB of float32 matrix and 73,728 B of tails a linear layer
    assert cut.cache_planes(5) == (576,) and cut.cache_planes(0) == () and cut.cached_values_per_token == 576
    assert cut.layer_keeps(0) == LayerKeeps(slot=(("state", (32, 128, 128), jnp.float32), ("tails", (3, 12288), None)))
    assert cut.layer_keeps(0).slot_bytes(jnp.bfloat16) == 2_097_152 + 73_728
    assert cut.slot_state_bytes_per_slot(jnp.bfloat16) == 5 * (2_097_152 + 73_728)
    assert hbm.slot_state_bytes(cut, 128, "bfloat16") == 128 * 5 * (2_097_152 + 73_728)
    assert hbm.paged_arena_bytes(cut, 12289, 32, "bfloat16") == 12289 * 32 * 576 * 2
    # (f) the parameters held at the cut, from shapes, and with 64 held of 512 a chip holds one whole group
    model = CausalLMPolicy(dataclasses.replace(cut, param_dtype=jnp.bfloat16))
    t = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(model, t, jnp.ones_like(t))["lm"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 2_366_497_312
    leaf = lambda *path: int(np.prod(shapes[path[0]][path[1]][path[2]][path[3]].shape))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes["block_0"]["attn"])) == 52_646_048
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes["block_5"]["attn"])) == 31_965_952
    assert leaf("block_1", "mlp", "expert_up", "kernel") == 64 * 5_898_240 // 3
    assert cut.moe_experts // cut.moe_n_group == cut.experts_held == 64
    assert PRESETS["ling-flash-tiny"]["layer_types"] == ("linear_attention", "linear_attention", "latent_attention")
    # the linear layers' work does not grow with the context; the recurrence is 7 d_k d_v a head a token
    assert flops.layer_attention_flops(cut, 0, 4096) == 0.0 and flops.layer_attention_flops(cut, 5, 4096) > 0
    kda = 2 * (52_428_800 + 2 * 81_920) + 2 * 4 * 12288 + 7 * 32 * 128 * 128  # products, gates, taps, recurrence
    mla = 2 * (15_728_640 + 1_474_560 + 4_194_304 + 10_485_760 + 81_920)
    assert flops.layer_matmul_flops(cut, 1) - flops.layer_matmul_flops(cut, 5) == kda - mla


DEPARTURES = ["unbounded_gate", "no_kda_gate", "no_qk_l2norm", "no_mla_qk_norm", "group_score_max"]


@pytest.mark.parametrize("departure", [None] + DEPARTURES)
def test_forward_matches_the_reference_and_each_assumed_item_flipped_does_not(policy, departure):
    """(b) the forward without a cache (chunks, decompressed latent attention)
    against the reference (a scan, per head), left-padded rows of unequal
    length; (e) each assumed item flipped in the reference is seen."""
    cfg, params = policy
    tokens, mask = left_padded(np.random.default_rng(7), [70, 33, 5], 70)
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params["lm"], tokens, mask)
    got = np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))
    want = reference_logprobs(params["lm"], cfg, tokens, mask, *([departure] if departure else []))
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    err = np.abs(got - want)[valid].max()
    assert err < TOL if departure is None else err > 0.05, (departure, err)


def test_a_non_zero_swiglu_limit_and_other_equations_are_refused_by_name(tmp_path):
    hf = hf_interop.config_to_hf(tiny_cfg())
    assert "model_type" not in hf and hf["layer_group_size"] == 3
    for key, value, match in (("expert_swiglu_limit_list", [0, 4, 0], "non-zero swiglu limit"),
                              ("kda_safe_gate", False, "kda_safe_gate=False"), ("use_kda_lora", True, "use_kda_lora"),
                              ("score_function", "softmax", "score_function")):
        (tmp_path / "config.json").write_text(json.dumps({**hf, key: value}))
        with pytest.raises(NotImplementedError, match=match):
            hf_interop.config_from_hf(str(tmp_path))
    with pytest.raises(NotImplementedError, match="non-zero swiglu limit"):
        ref.logits({}, np.zeros((1, 4), np.int32), np.ones((1, 4), np.int32),
                   {**sizes_of(tiny_cfg()), "share_expert_swiglu_limit_list": [5, 0, 0]})


def test_hf_config_keys_round_trip_and_tensor_names_are_refused(tmp_path):
    """(i) the catalog's config keys give the preset; a config written out
    reads back; loading and saving tensors refuse the family by name."""
    with open(os.path.join(BENCH, "configs", "ling-3.0-flash-vl.json")) as f:
        raw = json.load(f)
    keys = {k: v for k, v in raw.items() if k != "bench"}
    keys.update(num_hidden_layers=42, first_k_dense_replace=2, num_experts=512, vocab_size=157184,
                expert_swiglu_limit_list=[0] * 42, share_expert_swiglu_limit_list=[0] * 42)
    (tmp_path / "config.json").write_text(json.dumps(keys))
    read = hf_interop.config_from_hf(str(tmp_path))
    preset = config_from_preset("ling-3.0-flash-vl", 157184, hf_family="ling_flash")
    assert read == preset
    cfg = tiny_cfg(moe_local_experts=0)
    (tmp_path / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    again = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    assert again == dataclasses.replace(cfg, hf_family="ling_flash")
    assert hf_interop.infer_family(cfg) == "ling_flash"
    with pytest.raises(NotImplementedError, match="no tensor-name mapping for HF family 'ling_flash'"):
        hf_interop.load_params_from_hf(str(tmp_path), cfg, {})
    with pytest.raises(NotImplementedError, match="no tensor-name mapping for HF family 'ling_flash'"):
        hf_interop.params_to_hf_state_dict({"lm": {}}, cfg)


def test_sampler_through_the_scalar_index_cache_matches_the_reference(policy):
    """(b) `generate`: the prefill of left-padded prompts (chunks from an empty
    state, the tails that end at each row's last token), then the fused decode
    loop a token at a time over the same cache, against the reference."""
    cfg, params = policy
    model = CausalLMWithValueHead(cfg)
    tokens, mask = left_padded(np.random.default_rng(5), [20, 5, 1], 20)
    full = jitted_init(model)(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))["params"]
    full = {**full, "lm": params["lm"]}
    gen_cfg = GenerationConfig(max_new_tokens=40, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    generate = jax.jit(make_generate_fn(model, cfg, gen_cfg, capture=True))
    with jax.default_matmul_precision("highest"):
        out = generate(full, jnp.asarray(tokens), jnp.asarray(mask), jax.random.PRNGKey(0))
    want = reference_logprobs(params["lm"], cfg, out["samples"], out["samples_mask"])[:, 19:]
    assert np.abs(np.asarray(out["logprobs"]) - want).max() < TOL
    cache = init_kv_cache(cfg, 2, 8, jnp.bfloat16)["layers"]
    assert {k: (v.shape, v.dtype) for k, v in cache[0].items()} == {
        "state": ((2, 4, 16, 16), jnp.float32), "tails": ((2, 3, 192), jnp.bfloat16)}
    assert {k: v.shape for k, v in cache[2].items()} == {"latent": (2, 8, 40)}


def make_engine(cfg, params, slots, max_new, **kw):
    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    kw = {"decode_kernel": "interpret", "max_prefill_batch": 2, **kw}
    return InferenceEngine(CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=3, kv_paging=True, num_slots=slots,
                           max_prompt_len=32, prompt_bucket=16, kv_block_size=4, **kw)


def drain(engine, slots, steps):
    tokens, logprobs = {s: [] for s in slots}, {s: [] for s in slots}
    for _ in range(steps):
        tok, lp, emitted, _ = engine.step()
        for s in slots:
            if emitted[s]:
                tokens[s].append(int(tok[s]))
                logprobs[s].append(float(lp[s]))
    return tokens, logprobs


def engine_errors(cfg, params, prompts, out, got):
    seqs = [np.concatenate([p, np.asarray(new, np.int32)]) for p, new in zip(prompts, out)]
    tokens = np.zeros((len(seqs), WIDTH), np.int32)
    mask = np.zeros_like(tokens)
    for r, seq in enumerate(seqs):
        tokens[r, :len(seq)], mask[r, :len(seq)] = seq, 1
    want = reference_logprobs(params["lm"], cfg, tokens, mask)
    return [np.abs(np.asarray(lps) - want[r, len(p) - 1:len(p) - 1 + len(lps)]).max()
            for r, (p, lps) in enumerate(zip(prompts, got))]


@pytest.mark.parametrize("path", ["interpret", "xla"])
def test_engine_end_to_end_matches_the_reference_with_no_fallback(policy, path, monkeypatch):
    """(b) the fresh-prompt insert (right-padded rows through the chunked form
    from an empty state, the final state and tails into each row's slot), then
    48 decode steps (3 x the prompt bucket's chunk) with a step in flight:
    `kda_decode` through the interpreter or the plain step, absorbed paged
    latent attention beside it, rows of unequal length. Where kernels are
    interpreted (`TRLX_TPU_KERNELS`, the rule the insert's kernels ask) the
    prompts' recurrence is `kda_chunk_fwd` a span; else the XLA form."""
    from trlx_tpu.inference.engine import _prefill_state_form
    from trlx_tpu.ops.attention import KERNEL_PATHS

    cfg, params = policy
    if path == "interpret":
        monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    monkeypatch.setitem(KERNEL_PATHS, "kda_chunk_fwd", {})
    assert _prefill_state_form(cfg) == ("kernel" if path == "interpret" else "xla")
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (29, 5, 16)]
    with jax.default_matmul_precision("highest"):
        engine = make_engine(cfg, params, 3, 48, decode_kernel=path)
        assert "table" not in engine._pool["layers"][0] and set(engine._pool["layers"][0]) == {"state", "tails"}
        engine.insert_requests([(p, 48) for p in prompts], [0, 1, 2])
        counters = engine._slot_state_step()
        out, got = drain(engine, [0, 1, 2], 48)
    assert engine.decode_path == path
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["decode_steps_ahead_total"] > 0
    assert sorted(engine._paged_insert_fns) == [(1, 32, True), (2, 16, True)]
    # the two insert programs' KDA layers: one row of 32 positions and two of 16, 4 heads of 16, a span each
    assert KERNEL_PATHS["kda_chunk_fwd"] == (
        {"interpret": [(1, 32, 4, 16), (2, 16, 4, 16)]} if path == "interpret" else {})
    assert [len(got[s]) for s in range(3)] == [48] * 3
    assert max(engine_errors(cfg, params, prompts, [out[s] for s in range(3)], [got[s] for s in range(3)])) < TOL
    # what a slot holds beside the arena, and what a step does to it: float32 matrices, float32 tails here
    per_slot = 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["slot_state_bytes_per_slot"] == per_slot and stats["slot_state_bytes"] == 3 * per_slot
    assert counters == {"steps": 1, "slots": 3, "live": 3, "layers": 2, "bytes": 2 * 3 * per_slot}
    assert stats["kv_bytes_per_token"] == 40 * 4  # the latent layer's plane alone
    held = sum(a.nbytes for layer in engine._pool["layers"] for a in layer.values())
    assert stats["kv_pool_bytes"] + stats["slot_state_bytes"] == held
    walk = engine._kv_walk()
    assert walk["layers"] == 1 and walk["walked_full"] == walk["walked_window"] == 0 and walk["walked_latent"] > 0
    assert stats["moe_dropped_tokens"] == 0.0 and 0 < stats["moe_local_assignment_share"] < 1


def test_a_state_the_compiled_kda_kernel_cannot_tile_is_a_counted_fallback(policy, monkeypatch):
    """`kda_decode` compiled takes whole groups of 32 heads of 128-multiples.
    Where an engine would run compiled kernels and the model's state does not
    fit, the whole step takes the plain path and every dispatch is counted
    under `kda_decode_tiling`: "0 fallbacks" covers the new kernel too."""
    cfg, params = policy
    assert make_engine(cfg, params, 1, 4)._kernel_unsupported is None  # the interpreter takes any shape
    monkeypatch.setattr(InferenceEngine, "_resolve_attn_kernel", lambda self: "pallas")  # as on one chip
    engine = make_engine(cfg, params, 1, 4, decode_kernel="auto")
    assert engine._kernel_unsupported == "kda_decode_tiling" and engine.decode_path == "xla"
    engine.insert_requests([(np.arange(1, 6, dtype=np.int32), 4)], [0])
    drain(engine, [0], 3)
    stats = engine.kv_stats()
    assert stats["kv_kernel_dispatches"] == 0 and set(stats["kv_kernel_fallbacks"]) == {"kda_decode_tiling"}
    published = config_from_preset("ling-3.0-flash-vl", 157184)
    assert linear_attention.decode_kernel_takes(published.n_heads, published.head_dim, published.head_dim)
    # the same predicate decides the span kernel of a prompt's prefill: where compiled kernels run, this
    # model's 4 heads of 16 go the XLA form (and the counter above is the count), the published 32 of 128 the kernel
    from trlx_tpu.inference.engine import _prefill_state_form
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "kernel_mode", lambda: "pallas")
    assert linear_attention.chunk_kernel_mode(cfg.n_heads, cfg.head_dim, cfg.head_dim) is None
    assert _prefill_state_form(cfg) == "xla" and _prefill_state_form(published) == "kernel"


def test_a_reused_slot_and_a_cancelled_step_in_flight_touch_nobody_s_state(policy):
    """(c) Nothing clears a slot's state: an insert overwrites the whole row
    from an empty state, so the second request in a slot reads what a fresh
    engine reads (slow-decay leaves: the first request's state would show);
    and a request released with a step in flight (PR 36's rule: the step still
    moves the row) leaves its neighbour, and whoever gets the slot next, alone."""
    cfg, params = policy
    rng = np.random.default_rng(13)
    first, second, other = (rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (30, 9, 14))
    with jax.default_matmul_precision("highest"):
        fresh = make_engine(cfg, params, 2, 20)
        fresh.insert_requests([(second, 20)], [0])
        fresh_out, fresh_lp = drain(fresh, [0], 20)
        engine = make_engine(cfg, params, 2, 20)
        engine.insert_requests([(first, 20), (other, 20)], [0, 1])
        head_out, head_lp = drain(engine, [1], 2)  # a step is now in flight for both rows
        before = np.asarray(engine._pool["layers"][0]["state"][0])
        engine.release_slots([0])  # cancelled: the step in flight still decodes a token for it
        engine.insert_requests([(second, 20)], [0])
        assert not np.array_equal(before, np.asarray(engine._pool["layers"][0]["state"][0]))
        out, lp = drain(engine, [0, 1], 21)
    out[1], lp[1] = head_out[1] + out[1], head_lp[1] + lp[1]
    # the same rng stream position differs, so the tokens do too: hold the logprobs to the reference
    errs = engine_errors(cfg, params, [second, other], [out[0], out[1]], [lp[0], lp[1]])
    assert max(errs) < TOL and len(lp[0]) >= 19
    assert max(engine_errors(cfg, params, [second], [fresh_out[0]], [fresh_lp[0]])) < TOL


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """(d) 16 experts in 4 groups over 4 chips, 4 held (one whole group) a chip,
    the router scoring all 16 and limiting by group on every chip, the shared
    expert computed whole on each: the shares add up, the shared expert's part
    taken once, to the reference's uncut layer with the group rule written out."""
    whole = tiny_cfg(moe_local_experts=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, whole.d_model))
    shapes = jax.eval_shape(lambda: SparseMoE(whole).init(jax.random.PRNGKey(0), x)["params"])
    params = weights.make_params(shapes, 9, jnp.float32)
    kw = dict(top_k=whole.moe_top_k, n_group=whole.moe_n_group, topk_group=whole.moe_topk_group,
              scaling=whole.moe_routed_scale, departs=(), int8=False)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn(x[0], params, offset=0, **kw)
        shared = ref.glu(x[0], *(params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")), False)
        total = shared
        for chip in range(4):
            cfg = tiny_cfg(moe_local_experts=4, moe_local_offset=4 * chip)
            assert cfg.moe_local_offset // (cfg.moe_experts // cfg.moe_n_group) == chip  # its one group
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 4, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)[0]
            np.testing.assert_allclose(np.asarray(part), np.asarray(
                ref.expert_ffn(x[0], {**params, **share}, offset=4 * chip, **kw)), atol=5e-5)
            total = total + (part - shared)
        without = ref.expert_ffn(x[0], params, offset=0, **{**kw, "departs": ("no_group_limit",)})
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert float(jnp.abs(without - want).max()) > 1e-2  # the group limit is no formality at this size
    with pytest.raises(ValueError, match="go together"):
        tiny_cfg(moe_topk_group=0)
    with pytest.raises(ValueError, match="do not fit"):
        tiny_cfg(moe_n_group=3)


REFUSALS = [
    ("prefix_cache", dict(prefix_cache=True), "prefix_cache over slot state"),
    ("dense_slot_pool", dict(kv_paging=False), "dense slot pool .* over slot state"),
    ("int8_arena", dict(kv_cache_dtype="int8"), "int8 arena .* over slot state"),
]


@pytest.mark.parametrize("preset", ["ling-flash-tiny", "lfm2-tiny"])
@pytest.mark.parametrize("name,kw,match", REFUSALS, ids=[r[0] for r in REFUSALS])
def test_what_cannot_follow_slot_state_refuses_by_name(preset, name, kw, match):
    """(g) over a state a slot (a convolution's tails, a recurrent matrix)
    nothing can be shared through block tables or rolled back by mask bits."""
    cfg = config_from_preset(preset, VOCAB, dtype=jnp.float32)
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8,
                        **{"kv_paging": True, **kw})


def test_sessions_and_submit_n_refuse_slot_state_by_name(policy):
    cfg, params = policy
    engine = make_engine(cfg, params, 2, 4)
    with pytest.raises(NotImplementedError, match="sessions .* over slot state"):
        engine.enable_sessions()
    scheduler = Scheduler(engine)
    with pytest.raises(NotImplementedError, match="submit_n's shared prompt over slot state"):
        scheduler.submit_n(np.arange(1, 6, dtype=np.int32), 3)
    with pytest.raises(NotImplementedError, match="needs its number of slots"):
        init_paged_kv_arena(cfg, 4, 8)
    with pytest.raises(NotImplementedError, match="floating cache type"):
        init_paged_kv_arena(cfg, 4, 8, jnp.int8, num_slots=2)
    linear_only = dict(layer_types=("linear_attention",) * 3)
    for bad, match in ((dict(lora_rank=4, moe_experts=0, moe_router="softmax", moe_shared_d_ff=0, moe_routed_scale=1.0,
                             moe_local_experts=0, moe_n_group=0, moe_topk_group=0, **linear_only),
                        "linear_attention layers with lora_rank"),
                       (dict(attn_impl="ring", **linear_only), "attn_impl='ring'")):
        with pytest.raises(NotImplementedError, match=match):
            tiny_cfg(**bad)


def test_one_ppo_cycle_through_train_at_ling_tiny(tmp_path):
    """(h)"""
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=20, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3),
        model=dict(model_path="random:ling-flash-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=4)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"], eval_prompts=["ab", "cd"], config=config)
    assert trainer.iter_count == 1 and trainer.model_cfg.has_linear_layers and trainer.model_cfg.has_slot_state
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    assert any("f_proj" in k for k in train) and any("kv_b_proj" in k for k in train)
    frozen_by_design = ("expert_bias",)  # steers the selection, moved by no gradient
    assert [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))
            and not any(n in k for n in frozen_by_design)] == []


PARENT_DECODE_SHA256 = {  # the lowered text of the engine's decode program at commit 26d2888 (PR 40)
    "neox-tiny": "d0c9914696730ff32f316ef92730b5d604b89b6c7ba4aff9798f46c431a0aa3f",
    # the two with experts, recorded again at PR 55: these presets hold EVERY expert at their defaults, and
    # such a layer now hands out two more counters (`experts_met`, `experts_held`); with `count_met` off both
    # texts are PR 40's to the letter (7f70498a...c0 and bf0d2613...15), as a share's programs are
    "laguna-tiny": "45dcb490d870bfd5167fb9f9e7c83d54b368ac06489b99cee6b18a168d61de7e",
    "openpangu-ultra-moe-tiny": "3f66b05b1c397e8b84df0204e0ba159b1f26aa528da3a5671ef74dec35bb0b69",
}


@pytest.mark.parametrize("preset", sorted(PARENT_DECODE_SHA256))
def test_the_families_that_were_there_decode_through_the_parent_s_program(preset):
    """(k) one description of what a layer keeps moved Python and no operation:
    the decode program of an engine over K/V layers, over window and full
    layers and over latent layers lowers to the text it had."""
    cfg = config_from_preset(preset, 97, dtype=jnp.float32)
    model = CausalLMPolicy(cfg)
    t = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), t, jnp.ones_like(t))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=8, do_sample=True, eos_token_id=98, pad_token_id=0)
    engine = InferenceEngine(model, cfg, None, gen_cfg, num_slots=2, max_prompt_len=16, prompt_bucket=8,
                             kv_paging=True, kv_block_size=8, decode_kernel="xla")
    text = engine._decode_fn.trace(params, engine._pool).lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DECODE_SHA256[preset]
