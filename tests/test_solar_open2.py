"""Solar-Open2-style stacks (softmax GQA layers without positions keeping K
and V by head a TOKEN, Kimi delta attention in the published Kimi Linear form
keeping a recurrent matrix and convolution tails a ROW, elementwise gates on
both, sigmoid-routed experts in every layer beside a shared one) against the
benchmark's plain reference `bench/reference/solar_open2.py`, at test size on
the CPU, on seeded weights.

The leaves are the benchmark's (`bench/benchlib/weights.py`: every leaf from
the seed) with every `dt_bias/bias` shifted by -4, as tests/test_ling_flash.py
has them and for its reason: shifted, a state forty tokens back still counts.
One policy, one engine a decode path and one prefill shape serve every test
of this file (tests/test_solar_open2_train.py holds the PPO cycle, a file and
so a worker of its own).

Tolerances. Float32 program against float32 reference, both at `highest`:
1e-4 on a logit and 2e-5 on a logprob; each assumed item or published flag
flipped in the reference moves a logprob by 0.05 or more at this size."""

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

from benchlib import weights  # noqa: E402
from benchlib.files import load_module  # noqa: E402

from parity import jitted_forward, jitted_init  # noqa: E402
from trlx_tpu.inference import InferenceEngine, Scheduler  # noqa: E402
from trlx_tpu.models import CausalLMPolicy, CausalLMWithValueHead, config_from_preset  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402
from trlx_tpu.models.transformer import LayerKeeps, SparseMoE, TransformerLM, init_kv_cache  # noqa: E402
from trlx_tpu.observability import hbm  # noqa: E402
from trlx_tpu.ops import linear_attention  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn  # noqa: E402

VOCAB = 96
TOL = 2e-5
ROWS, WIDTH = 3, 64
ref = load_module("reference/solar_open2.py")
plain = load_module("reference/plain_ops.py")
with open(os.path.join(BENCH, "configs", "solar-open2-250b.json")) as f:
    RAW = json.load(f)


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 4, **kw}
    return config_from_preset("solar-open2-tiny", VOCAB, **kw)


def sizes_of(cfg, *departures, **flags):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "solar_open2"), "expert_offset": cfg.moe_local_offset,
            "departures": list(departures), **flags}


@pytest.fixture(scope="module")
def policy():
    from flax.traverse_util import flatten_dict, unflatten_dict

    cfg = tiny_cfg(attn_impl="flash")
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(CausalLMPolicy(cfg), tokens, jnp.ones_like(tokens))
    seeded = flatten_dict(weights.make_params(shapes, 43, jnp.float32))
    return cfg, unflatten_dict({k: v - 4.0 if k[-2] == "dt_bias" else v for k, v in seeded.items()})


def reference_logprobs(lm_params, cfg, tokens, mask, *departures, **flags):
    tokens, mask = np.asarray(tokens), np.asarray(mask)
    rows, width = tokens.shape
    pad = lambda a: np.pad(a, ((0, ROWS - rows), (0, WIDTH - width)))
    out = ref.logprobs(lm_params, pad(tokens), pad(mask), sizes_of(cfg, *departures, **flags))
    return np.asarray(out)[:rows, : width - 1]


def left_padded(rng, lens, width):
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


def test_presets_state_every_published_size_and_the_cut_counts_what_the_file_says():
    published = config_from_preset("solar-open2-250b", 196608)
    assert (published.n_layers, published.d_model, published.n_heads, published.kv_heads, published.head_dim) \
        == (48, 4096, 64, 8, 128)
    assert [i for i, k in enumerate(published.layer_types) if k == "attention"] == list(range(0, 48, 4))
    assert (published.moe_experts, published.moe_top_k, published.moe_dense_layers, published.moe_n_group) \
        == (320, 8, 0, 0)
    assert (published.pos_embed, published.alibi, published.attn_gate) == ("none", False, "elementwise")
    assert (published.kda_decay, published.kda_gate_rank, published.kda_beta_max) == ("softplus", 128, 2.0)
    assert published.attention_kinds == ()  # one kind of K/V layer: one bias, the plain paged kernel
    extra = dict(RAW["bench"]["program"]["model_extra_configs"])
    cut = config_from_preset("solar-open2-250b", extra.pop("vocab_size"), **extra, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
    assert cut.layer_types == ("attention",) + ("linear_attention",) * 3 == tuple(
        "attention" if i in RAW["gqa_layers"] else "linear_attention" for i in range(RAW["num_hidden_layers"]))
    # a token: K and V of 8 heads of 128 in the one GQA layer; a slot: 4 MB of float32 matrix and 147,456 B of tails
    assert cut.cache_planes(0) == (1024, 1024) and cut.cache_planes(1) == () and cut.cached_values_per_token == 2048
    assert cut.layer_keeps(1) == LayerKeeps(slot=(("state", (64, 128, 128), jnp.float32), ("tails", (3, 24576), None)))
    assert cut.slot_state_bytes_per_slot(jnp.bfloat16) == 3 * (4_194_304 + 147_456) == 13_025_280
    assert hbm.slot_state_bytes(cut, 64, "bfloat16") == 64 * 13_025_280
    assert hbm.paged_arena_bytes(cut, 12289, 32, "bfloat16") == 12289 * 32 * 4096
    assert linear_attention.decode_kernel_takes(cut.n_heads, cut.head_dim, cut.head_dim)
    # the parameters held at the cut, recounted from shapes, against the issue's and the file's count
    t = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(CausalLMPolicy(cut), t, jnp.ones_like(t))["lm"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == RAW["bench"]["parameters_held"]["total"] == 3_308_377_920
    assert count(shapes["block_0"]["attn"]) == RAW["bench"]["parameters_held"]["gqa_layer_attention"] == 109_051_904
    assert count(shapes["block_1"]["attn"]) == RAW["bench"]["parameters_held"]["kda_layer_attention"] == 137_740_480
    experts = sum(count(shapes["block_2"]["mlp"][n]) for n in ("expert_gate", "expert_up", "expert_down"))
    assert experts == 40 * 15_728_640 and count(shapes["block_2"]["mlp"]) - experts == 15_728_640 + 4096 * 320 + 320
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == 2 * 24576 * 4096
    # the low-rank pairs are leaves of their own: without them (kda_gate_rank 0) the tree is another model's
    names = lambda cfg: set(jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), t, t))["params"]
                            ["block_1"]["attn"])
    assert names(tiny_cfg()) - names(tiny_cfg(kda_gate_rank=0)) == {"f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj",
                                                                   "g_bias"}
    assert names(tiny_cfg(kda_gate_rank=0)) - names(tiny_cfg()) == {"f_proj", "gate_proj"}


@pytest.fixture(scope="module")
def forward(policy):
    """The forward without a cache, once: left-padded rows of unequal length."""
    cfg, params = policy
    tokens, mask = left_padded(np.random.default_rng(7), [60, 33, 5], 60)
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params["lm"], tokens, mask)
    return tokens, mask, logits


# every assumed item of the configuration file (reference `departures`) and every published flag, flipped
FLIPPED = {name: ((name,), {}) for name in ("gqa_gate_per_head", "gqa_qk_norm", "bounded_gate", "no_qk_l2norm",
                                             "no_conv", "no_kda_gate", "no_gate_bias", "no_selection_bias")}
FLIPPED.update(use_rope=((), {"use_rope": True}), use_gqa_gate=((), {"use_gqa_gate": False}),
               kda_allow_neg_eigval=((), {"kda_allow_neg_eigval": False}))


@pytest.mark.parametrize("flipped", [None] + sorted(FLIPPED))
def test_forward_matches_the_reference_and_each_assumed_item_or_flag_flipped_does_not(policy, forward, flipped):
    """The forward without a cache (chunks, fused attention without positions)
    against the reference (a scan, a head at a time)."""
    (cfg, params), (tokens, mask, logits) = policy, forward
    departures, flags = FLIPPED.get(flipped, ((), {}))
    got = np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))
    want = reference_logprobs(params["lm"], cfg, tokens, mask, *departures, **flags)
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    err = np.abs(got - want)[valid].max()
    assert err < TOL if flipped is None else err > 0.05, (flipped, err)
    if flipped is None:
        pad = lambda a: np.pad(a, ((0, 0), (0, WIDTH - a.shape[1])))
        want = np.asarray(ref.logits(params["lm"], pad(tokens), pad(mask), sizes_of(cfg)))[:, :60]
        assert np.abs(np.asarray(logits) - want)[mask.astype(bool)].max() < 1e-4


def test_hf_config_keys_give_the_preset_and_other_equations_and_tensor_names_are_refused(tmp_path):
    keys = {k: v for k, v in RAW.items() if k != "bench"}
    keys.update(num_hidden_layers=48, gqa_layers=list(range(0, 48, 4)), n_routed_experts=320, vocab_size=196608)
    (tmp_path / "config.json").write_text(json.dumps(keys))
    assert hf_interop.config_from_hf(str(tmp_path)) == config_from_preset(
        "solar-open2-250b", 196608, hf_family="solar_open2")
    cfg = tiny_cfg(moe_local_experts=0)
    (tmp_path / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    assert hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32) == dataclasses.replace(
        cfg, hf_family="solar_open2")
    assert hf_interop.infer_family(cfg) == "solar_open2"
    with pytest.raises(NotImplementedError, match="no tensor-name mapping for HF family 'solar_open2'"):
        hf_interop.load_params_from_hf(str(tmp_path), cfg, {})
    with pytest.raises(NotImplementedError, match="no tensor-name mapping for HF family 'solar_open2'"):
        hf_interop.params_to_hf_state_dict({"lm": {}}, cfg)
    for key, value in (("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
                       ("first_k_dense_replace", 1), ("n_shared_experts", 2)):
        (tmp_path / "config.json").write_text(json.dumps({**keys, key: value}))
        with pytest.raises(NotImplementedError, match=f"solar_open2 with {key}="):
            hf_interop.config_from_hf(str(tmp_path))
    with pytest.raises(NotImplementedError, match="kda_use_full_proj true"):
        ref.logits({}, np.zeros((1, 4), np.int32), np.ones((1, 4), np.int32), sizes_of(cfg, kda_use_full_proj=True))
    for bad, match in ((dict(attn_gate="by_head"), "attn_gate must be"), (dict(kda_decay="relu"), "kda_decay must be"),
                       (dict(kda_beta_max=3.0), "kda_beta_max")):
        with pytest.raises(ValueError, match=match):
            tiny_cfg(**bad)
    with pytest.raises(NotImplementedError, match="latent_attention layers with attn_gate='elementwise'"):
        config_from_preset("ling-flash-tiny", VOCAB, attn_gate="elementwise")


def test_sampler_through_the_scalar_index_cache_matches_the_reference(policy):
    """`generate`: the prefill of left-padded prompts (K and V into the GQA
    layer's cache, chunks from an empty state and the tails that end at each
    row's last token into the others), then the fused decode loop."""
    cfg, params = policy
    model = CausalLMWithValueHead(cfg)
    tokens, mask = left_padded(np.random.default_rng(5), [20, 5, 1], 20)
    full = jitted_init(model)(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))["params"]
    full = {**full, "lm": params["lm"]}
    gen_cfg = GenerationConfig(max_new_tokens=24, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    generate = jax.jit(make_generate_fn(model, cfg, gen_cfg, capture=True))
    with jax.default_matmul_precision("highest"):
        out = generate(full, jnp.asarray(tokens), jnp.asarray(mask), jax.random.PRNGKey(0))
    want = reference_logprobs(params["lm"], cfg, out["samples"], out["samples_mask"])[:, 19:]
    assert np.abs(np.asarray(out["logprobs"]) - want).max() < TOL
    cache = init_kv_cache(cfg, 2, 8, jnp.bfloat16)["layers"]
    assert {k: v.shape for k, v in cache[0].items()} == {"k": (2, 8, 2, 16), "v": (2, 8, 2, 16)}
    assert {k: (v.shape, v.dtype) for k, v in cache[1].items()} == {
        "state": ((2, 4, 16, 16), jnp.float32), "tails": ((2, 3, 192), jnp.bfloat16)}


def make_engine(cfg, params, path, **kw):
    gen_cfg = GenerationConfig(max_new_tokens=24, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
    return InferenceEngine(CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=3, kv_paging=True, num_slots=3,
                           max_prompt_len=32, prompt_bucket=32, max_prefill_batch=1, kv_block_size=4,
                           decode_kernel=path, **kw)


@pytest.fixture(scope="module")
def engines(policy):
    """One engine a decode path, built once: one prefill program (a row of 32)
    and one decode program each."""
    cfg, params = policy
    with jax.default_matmul_precision("highest"):
        return {path: make_engine(cfg, params, path) for path in ("interpret", "xla")}


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
def test_engine_end_to_end_matches_the_reference_with_no_fallback(policy, engines, path, monkeypatch):
    """The fresh-prompt insert (right-padded rows: K and V into the GQA layer's
    blocks through the fused prefill, the chunked form's final state and the
    tails into each row's slot), then 24 decode steps with a step in flight:
    `paged_decode` over 2 query heads a K/V head and `kda_decode`, through the
    interpreter or the plain paths, rows of unequal length. Where kernels are
    interpreted (`TRLX_TPU_KERNELS`, the rule the insert's kernels ask) the
    prompt's recurrence is `kda_chunk_fwd` a span; else the XLA form."""
    from trlx_tpu.inference.engine import _prefill_state_form
    from trlx_tpu.ops.attention import KERNEL_PATHS

    (cfg, params), engine = policy, engines[path]
    if path == "interpret":
        monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    monkeypatch.setitem(KERNEL_PATHS, "kda_chunk_fwd", {})
    assert _prefill_state_form(cfg) == ("kernel" if path == "interpret" else "xla")
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (29, 5, 16)]
    with jax.default_matmul_precision("highest"):
        assert set(engine._pool["layers"][0]) == {"k", "v", "table"} or set(engine._pool["layers"][0]) == {"k", "v"}
        assert set(engine._pool["layers"][1]) == {"state", "tails"}
        engine.insert_requests([(p, 24) for p in prompts], [0, 1, 2])
        counters, walk = engine._slot_state_step(), engine._kv_walk()
        out, got = drain(engine, [0, 1, 2], 24)
        engine.release_slots([0, 1, 2])
    assert engine.decode_path == path
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["decode_steps_ahead_total"] > 0
    assert sorted(engine._paged_insert_fns) == [(1, 32, True)]
    # the insert program's three KDA layers: one row of 32 positions, 4 heads of 16, a span each
    assert KERNEL_PATHS["kda_chunk_fwd"] == ({"interpret": [(1, 32, 4, 16)]} if path == "interpret" else {})
    assert [len(got[s]) for s in range(3)] == [24] * 3
    assert max(engine_errors(cfg, params, prompts, [out[s] for s in range(3)], [got[s] for s in range(3)])) < TOL
    # what a slot holds beside the arena, and what a step does to it: 3 of the 4 layers, float32 tails here
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["slot_state_bytes_per_slot"] == per_slot and stats["slot_state_bytes"] == 3 * per_slot
    assert counters == {"steps": 1, "slots": 3, "live": 3, "layers": 3, "bytes": 2 * 3 * per_slot}
    # the arena: the ONE layer that caches, K and V of 2 heads of 16 a token
    assert stats["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert walk["layers"] == 1 and walk["walked_latent"] == walk["walked_window"] == 0 and walk["walked_full"] > 0
    assert walk["bytes"] == walk["walked_full"] * 2 * 2 * 16 * 4
    held = sum(a.nbytes for layer in engine._pool["layers"] for a in layer.values())
    assert stats["kv_pool_bytes"] + stats["slot_state_bytes"] == held
    assert stats["moe_dropped_tokens"] == 0.0 and 0 < stats["moe_local_assignment_share"] < 1


def test_a_reused_slot_and_a_cancelled_step_in_flight_touch_nobody_s_state(policy, engines):
    """Nothing clears a slot's state: an insert overwrites the whole row from
    an empty state, so a request in a slot that others have used reads what the
    reference reads (slow-decay leaves: an earlier request's state would show);
    and a request released with a step in flight (the step still moves the
    row) leaves its neighbour, and whoever gets the slot next, alone."""
    (cfg, params), engine = policy, engines["interpret"]
    rng = np.random.default_rng(13)
    first, second, other = (rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (30, 9, 14))
    with jax.default_matmul_precision("highest"):
        engine.insert_requests([(first, 24), (other, 24)], [0, 1])
        head_out, head_lp = drain(engine, [1], 2)  # a step is now in flight for both rows
        before = np.asarray(engine._pool["layers"][1]["state"][0])
        engine.release_slots([0])  # cancelled: the step in flight still decodes a token for it
        engine.insert_requests([(second, 24)], [0])
        assert not np.array_equal(before, np.asarray(engine._pool["layers"][1]["state"][0]))
        out, lp = drain(engine, [0, 1], 22)
        engine.release_slots([0, 1])
    out[1], lp[1] = head_out[1] + out[1], head_lp[1] + lp[1]
    errs = engine_errors(cfg, params, [second, other], [out[0], out[1]], [lp[0], lp[1]])
    assert max(errs) < TOL and len(lp[0]) >= 20 and len(lp[1]) == 24
    assert engine.kv_stats()["kv_kernel_fallbacks"] == {}


def test_the_prefill_state_counter_span_says_what_the_chunked_form_runs(policy, engines, monkeypatch):
    """`trlx:engine.prefill_state`, one an admission while a session listens:
    the tokens, the positions dispatched, the linear layers and a layer's chunks."""
    from trlx_tpu.observability import tracing

    engine, seen = engines["xla"], []
    monkeypatch.setattr(tracing, "active", lambda: True)
    monkeypatch.setattr(tracing, "counters", lambda name, **kw: seen.append((name, kw)))
    programs = engine._prefill_programs([(np.arange(1, 30, dtype=np.int32), 4), (np.arange(1, 6, dtype=np.int32), 4)])
    assert engine._count_admission(programs) == (2, 34, 64, 2)
    assert ("engine.prefill_state", dict(tokens=34, padded_tokens=64, linear_layers=3, chunks=2, form="xla")) in seen
    monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")  # as where kernels run: the span kernel takes the recurrence
    engine._count_admission(programs)
    assert seen[-1] == ("engine.prefill_state", dict(tokens=34, padded_tokens=64, linear_layers=3, chunks=2,
                                                     form="kernel"))


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """16 experts over 8 chips, 2 held a chip, the router scoring all 16 on
    every chip, the shared expert computed whole on each: the shares add up,
    the shared expert's part taken once, to the reference's uncut layer."""
    whole = tiny_cfg(moe_local_experts=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, whole.d_model))
    shapes = jax.eval_shape(lambda: SparseMoE(whole).init(jax.random.PRNGKey(0), x)["params"])
    params = weights.make_params(shapes, 9, jnp.float32)
    kw = dict(top_k=whole.moe_top_k, scaling=whole.moe_routed_scale, departs=(), int8=False)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn(x[0], params, offset=0, **kw)
        shared = ref.glu(x[0], *(params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")), False)
        total = shared
        for chip in range(8):
            cfg = tiny_cfg(moe_local_experts=2, moe_local_offset=2 * chip)
            share = {name: {"kernel": jnp.split(params[name]["kernel"], 8, axis=1)[chip]}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)[0]
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-2  # the routed part is no formality at this size


REFUSALS = [
    ("prefix_cache", dict(prefix_cache=True), "prefix_cache over slot state"),
    ("dense_slot_pool", dict(kv_paging=False), "dense slot pool .* over slot state"),
    ("int8_arena", dict(kv_cache_dtype="int8"), "int8 arena .* over slot state"),
]


@pytest.mark.parametrize("name,kw,match", REFUSALS, ids=[r[0] for r in REFUSALS])
def test_what_cannot_follow_slot_state_beside_a_by_head_arena_refuses_by_name(name, kw, match):
    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8,
                        **{"kv_paging": True, **kw})


def test_sessions_and_submit_n_refuse_slot_state_by_name(engines):
    engine = engines["xla"]
    with pytest.raises(NotImplementedError, match="sessions .* over slot state"):
        engine.enable_sessions()
    with pytest.raises(NotImplementedError, match="submit_n's shared prompt over slot state"):
        Scheduler(engine).submit_n(np.arange(1, 6, dtype=np.int32), 3)
