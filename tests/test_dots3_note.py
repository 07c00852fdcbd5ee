"""dots3-note-style stacks (latent attention of two shapes in one stack: full
layers on which a learned index chooses the positions attended to, banded
layers with a latent of their own width; both latents rescaled; a gate a
head; sigmoid-routed experts beside a shared one) against the benchmark's
plain reference `bench/reference/dots3_note.py`, at test size on the CPU, on
seeded weights. `dots3-note-tiny`: a window of 5 and an index that keeps 6,
both shorter than every test's prompt.

The reference decompresses per-head keys and values and masks the positions
not chosen; the program's cached steps run ABSORBED over the latents and, in
the paged engine, read the chosen latents by token address, so every cached
test here holds one form against the other.

Tolerances. Float32 program against float32 reference, both at `highest`:
1e-5 on a logprob (the readings are 2e-6 to 5e-6); every departure of
`test_the_reference_tells_each_departure` moves a logprob by 0.1 or more."""

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
from trlx_tpu.models import transformer as tr  # noqa: E402
from trlx_tpu.models.transformer import (  # noqa: E402
    PRESETS, LatentIndex, LatentSpec, SparseMoE, TransformerLM, init_kv_cache, init_paged_kv_arena)
from trlx_tpu.ops import sparse_attention as sparse  # noqa: E402

VOCAB = 96
TOL = 1e-5
FULL, SLIDING = "sparse_latent_attention", "sliding_latent_attention"
ref = load_module("reference/dots3_note.py")
plain = load_module("reference/plain_ops.py")
moe_ref = load_module("reference/pangu_ultra_moe.py")


def tiny_cfg(**kw):
    kw = {"dtype": jnp.float32, "moe_local_experts": 2, **kw}
    return config_from_preset("dots3-note-tiny", VOCAB, **kw)


def with_shapes(cfg, **by_kind):
    """`cfg` with fields of its kinds' `LatentSpec`s replaced: {kind: {field: value}}."""
    return dataclasses.replace(cfg, latent_kinds=tuple(
        (kind, dataclasses.replace(spec, **by_kind.get(kind, {}))) for kind, spec in cfg.latent_kinds))


def sizes_of(cfg):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "dots3_note"), "expert_offset": cfg.moe_local_offset}


def seeded_params(model, seed, *init_args):
    """Every leaf drawn from the seed, the norms' scales and biases and the
    selection bias too (a fresh init leaves them at one and at zero)."""
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


def reference_logprobs(lm_params, cfg, tokens, mask, departure=None):
    """The reference's [rows, width - 1] logprobs, every call padded (on the
    right, mask 0) to the one shape [ROWS, WIDTH], so that its jitted layers
    compile once a process and departure."""
    tokens, mask = np.asarray(tokens), np.asarray(mask)
    rows, width = tokens.shape
    pad = lambda a: np.pad(a, ((0, ROWS - rows), (0, WIDTH - width)))
    out = ref.logprobs(lm_params, pad(tokens), pad(mask), sizes_of(cfg), departure=departure)
    return np.asarray(out)[:rows, : width - 1]


def forward_logprobs(cfg, params, tokens, mask, program=jitted_forward):
    with jax.default_matmul_precision("highest"):
        logits = program(cfg)(params, tokens, mask)
    return np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))


def test_presets_state_every_published_size_and_what_a_token_caches():
    full = PRESETS["dots3-note-prev"]
    assert (full["d_model"], full["n_layers"], full["d_ff"], full["max_seq_len"]) == (5120, 46, 13824, 524288)
    kinds, ropes = dict(full["latent_kinds"]), dict(full["rope_kinds"])
    assert kinds[FULL] == LatentSpec(128, 1024, 512, 128, 64, 128, rescale=True, index_heads=64,
                                     index_head_dim=128, index_topk=2048)
    assert kinds[SLIDING] == LatentSpec(64, 1024, 1024, 192, 64, 128, rescale=True, window=513)
    assert (ropes[FULL].theta, ropes[SLIDING].theta) == (8e7, 5e4)
    assert [i for i, k in enumerate(full["layer_types"]) if k == FULL] == [0, 1, 5, 9, 13, 17, 21, 25, 29, 33, 37,
                                                                          41, 45]
    assert (full["moe_experts"], full["moe_top_k"], full["moe_d_ff"], full["moe_dense_layers"],
            full["moe_routed_scale"], full["moe_shared_d_ff"], full["attn_gate"]) == (256, 8, 1536, 1, 1.0, 1536,
                                                                                      "per_head")
    # the benchmark's cut: its parameters and its cache, counted from shapes
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        bench = json.load(f)["bench"]
    extra = dict(bench["program"]["model_extra_configs"])
    cut = config_from_preset("dots3-note-prev", extra.pop("vocab_size"), **extra)
    assert cut.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL) and cut.attention_kinds == (FULL, SLIDING)
    assert [cut.cache_planes(i) for i in range(5)] == [(576, 128), (1088,), (1088,), (1088,), (576, 128)]
    assert cut.cached_values_per_token == 4672 and cut.has_index_layers and cut.has_latent_layers
    assert (cut.window_of(FULL), cut.window_of(SLIDING)) == (None, 513)
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: CausalLMPolicy(cut).init(jax.random.PRNGKey(0), tokens, tokens)["params"])
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert held == bench["parameters_held"] == 4_087_154_176
    # the arena: a latent plane a layer, of its kind's width, and the index's keys beside a full layer's
    arena = jax.eval_shape(lambda: init_paged_kv_arena(cut, 12544, 32, jnp.bfloat16))
    assert [{k: v.shape for k, v in layer.items()} for layer in arena] == [
        {"latent": (12544, 16, 1152), "index_k": (12544, 32, 128)}, *[{"latent": (12544, 16, 2176)}] * 3,
        {"latent": (12544, 16, 1152), "index_k": (12544, 32, 128)}]
    assert sum(a.size * a.dtype.itemsize for layer in arena for a in layer.values()) == 12544 * 32 * 4672 * 2
    tiny = tiny_cfg()
    dense = init_kv_cache(tiny, 3, 20)
    assert [{k: v.shape for k, v in layer.items()} for layer in dense["layers"]] == [
        {"latent": (3, 20, 40), "index_k": (3, 20, 16)}, *[{"latent": (3, 20, 56)}] * 3,
        {"latent": (3, 20, 40), "index_k": (3, 20, 16)}]
    # what asks a kind: a latent kind's window is its own, a kind that keeps nothing a token has none
    laguna = config_from_preset("laguna-tiny", VOCAB)
    assert [laguna.window_of(k) for k in ("full_attention", "sliding_attention", None)] == [None, 8, 8]
    ling = config_from_preset("ling-flash-tiny", VOCAB, sliding_window=None)
    assert ling.window_of("latent_attention") is None and ling.window_of("linear_attention") is None
    assert dataclasses.replace(laguna, layer_types=("conv",) * 4).window_of("conv") is None


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


@pytest.fixture(scope="module")
def departure_case():
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(2), [32, 21, 9, 3], WIDTH)
    params = seeded_params(TransformerLM(cfg), 2, jnp.asarray(tokens), jnp.asarray(mask))
    return cfg, tokens, mask, params, reference_logprobs(params, cfg, tokens, mask)


@pytest.mark.parametrize("departure", ["no_index", "topk_less", "window_less", "no_relu", "no_rescale",
                                       "swa_theta_as_full"])
def test_the_reference_tells_each_departure(departure, departure_case, monkeypatch):
    """What the limit of `correct` has to refuse, at test size: a program
    with the departure is far from the sound reference, and within `TOL` of
    the reference WITH that departure (so each is the reference's to tell,
    not an accident of the program's)."""
    cfg, tokens, mask, params, want = departure_case
    valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
    full, swa = cfg.latent_of(FULL), cfg.latent_of(SLIDING)
    program = {
        "no_index": lambda: with_shapes(cfg, **{FULL: dict(index_topk=WIDTH)}),  # everything chosen
        "topk_less": lambda: with_shapes(cfg, **{FULL: dict(index_topk=full.index_topk - 1)}),
        "window_less": lambda: with_shapes(cfg, **{SLIDING: dict(window=swa.window - 1)}),
        "no_rescale": lambda: with_shapes(cfg, **{FULL: dict(rescale=False), SLIDING: dict(rescale=False)}),
        "swa_theta_as_full": lambda: dataclasses.replace(cfg, rope_kinds=tuple(
            (k, dict(cfg.rope_kinds)[FULL]) for k, _ in cfg.rope_kinds)),
        "no_relu": lambda: cfg,
    }[departure]()
    if departure == "no_relu":
        monkeypatch.setattr(sparse.jax.nn, "relu", lambda x: x)
    # a program traced anew: the patch above is read when the forward is traced
    got = forward_logprobs(program, params, tokens, mask, jitted_forward.__wrapped__)
    assert np.abs(got - want)[valid].max() > 0.1
    assert np.abs(got - reference_logprobs(params, cfg, tokens, mask, departure))[valid].max() < TOL


def test_the_sliding_layers_keep_their_own_shape():
    """The sliding layers run at the full layers' shape is no program of
    these weights: the kinds' leaves differ in every width."""
    cfg = tiny_cfg()
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens, tokens)["params"])
    full, swa = shapes["block_0"]["attn"], shapes["block_1"]["attn"]
    assert "indexer" in full and "indexer" not in swa
    assert {n: (full[n]["kernel"].shape, swa[n]["kernel"].shape) for n in ("q_b_proj", "kv_a_proj", "kv_b_proj",
                                                                           "o_proj", "gate_proj")} == {
        "q_b_proj": ((24, 4 * 24), (24, 2 * 32)), "kv_a_proj": ((64, 40), (64, 56)),
        "kv_b_proj": ((32, 4 * 28), (48, 2 * 36)), "o_proj": ((48, 64), (24, 64)), "gate_proj": ((64, 4), (64, 2))}
    with pytest.raises(ValueError, match="names a window and no index"):
        with_shapes(cfg, **{SLIDING: dict(window=None)})
    with pytest.raises(ValueError, match="need their LatentSpec"):
        dataclasses.replace(cfg, latent_kinds=cfg.latent_kinds[:1])


def test_the_programs_chosen_set_is_the_references_at_every_position():
    """Layer 0's index on seeded leaves: the program's scores
    (`LatentIndex`, `index_scores`) and its choice (`topk_mask`, and
    `topk_columns` as a decode step takes it) name, at every position of
    every row, exactly the set the reference's `chosen` names."""
    cfg = tiny_cfg()
    spec, rope = cfg.latent_of(FULL), cfg.rope_of(FULL)
    rng = np.random.default_rng(4)
    t = 24
    x = jnp.asarray(rng.normal(size=(2, t, cfg.d_model)), jnp.float32)
    c_q = jnp.asarray(rng.normal(size=(2, t, spec.q_lora_rank)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t), (2, t))
    index = LatentIndex(cfg, spec, rope)
    both = lambda m, c_q, x, pos: (m.queries(c_q, x, pos), m.keys(x, pos))
    variables = jax.jit(lambda k: index.init(k, c_q, x, positions, method=both))(jax.random.PRNGKey(4))
    leaves = {k: {n: jnp.asarray(rng.normal(size=v.shape) * (0.1 if n != "scale" else 1.0), jnp.float32)
                  for n, v in sub.items()} for k, sub in variables["params"].items()}
    with jax.default_matmul_precision("highest"):
        (q_i, w_i), k_i = jax.jit(lambda p: index.apply({"params": p}, c_q, x, positions, method=both))(leaves)
        scores = sparse.index_scores(q_i, w_i, k_i)
        causal = jnp.tril(jnp.ones((t, t), bool))
        masked = jnp.where(causal[None], scores, -jnp.inf)
        got = np.asarray(sparse.topk_mask(masked, spec.index_topk) & causal[None])
        cols, ok = sparse.topk_columns(masked, spec.index_topk)
        for r in range(2):
            want = np.asarray(ref.chosen(x[r], c_q[r], leaves, causal, positions[r], heads=spec.index_heads,
                                         topk=spec.index_topk, theta=rope.theta))
            np.testing.assert_array_equal(got[r], want)
            for i in range(t):
                assert set(np.asarray(cols[r, i])[np.asarray(ok[r, i])].tolist()) == set(np.flatnonzero(want[i]))
    assert got.sum(-1).tolist() == [[min(i + 1, spec.index_topk) for i in range(t)]] * 2


def test_prompts_shorter_than_the_index_keeps_are_dense_latent_attention():
    """While a row has no more positions than `index_topk` everything is
    chosen: the logits are those of the same weights with the index left
    out, to 1e-6, whatever the index's leaves hold."""
    cfg = tiny_cfg()
    tokens, mask = left_padded(np.random.default_rng(6), [6, 5, 2], 6)
    params = seeded_params(TransformerLM(cfg), 6, jnp.asarray(tokens), jnp.asarray(mask))
    dense = with_shapes(cfg, **{FULL: dict(index_topk=64)})
    with jax.default_matmul_precision("highest"):
        got = jitted_forward(cfg)(params, tokens, mask)
        want = jitted_forward(dense)(params, tokens, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_sampler_through_the_dense_cache_matches_the_reference():
    """`generate`: the prefill of left-padded prompts (`flash_prefill`: by
    query blocks, within the block), then the fused decode loop absorbed over
    the dense latent cache and its index keys (the band by the bias, the
    chosen by `topk_mask`), every captured logprob against the reference's
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


def test_a_prefill_per_head_and_the_absorbed_step_behind_it_match_the_reference(monkeypatch):
    """The two forms of a full layer held to each other through the model's
    own doors: a prefill into an empty cache of prompts longer than the index
    keeps (21 and 17 against 6), three query blocks of 8, two groups of two
    heads (`prefill_by_query_blocks`: per head over keys and values
    decompressed a group at a time), every position's logprob against the
    reference; then ONE decode step, absorbed over the latents and the index
    keys that prefill cached, against the reference at the next position."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(tr, "PREFILL_QUERY_BLOCK", 8)
    monkeypatch.setattr(tr, "PREFILL_HEAD_GROUP", 2)
    cfg = tiny_cfg(attn_impl="flash")
    model = TransformerLM(cfg)
    tokens, mask = left_padded(np.random.default_rng(13), [23, 19], 23)  # a prompt of 21 (17) and two tokens behind it
    params = seeded_params(model, 13, jnp.asarray(tokens), jnp.asarray(mask))
    step = lambda is_prefill: jax.jit(lambda p, x, cache, m: model.apply(
        {"params": p}, x, cache, m, is_prefill, method=TransformerLM.decode_step))
    with jax.default_matmul_precision("highest"):
        logits, _, cache = step(True)(params, tokens[:, :21], init_kv_cache(cfg, 2, 24), mask[:, :21])
        after, _, _ = step(False)(params, tokens[:, 21:22], cache, mask[:, 21:22])
    assert any(shape[1:3] == (8, 2) for shape in attention.KERNEL_PATHS["sparse_latent_fwd"]["xla"])
    # (the last column's logits are read by nobody: `logprobs_of_next` drops them)
    got = np.asarray(plain.logprobs_of_next(jnp.concatenate([logits, after, after], axis=1), jnp.asarray(tokens)))
    want = reference_logprobs(params, cfg, tokens, mask)
    live = mask[:, :-1] * mask[:, 1:] > 0
    assert np.abs(got - want)[live].max() < TOL
    assert live[:, 20:].all()  # the prompt's last position (the prefill's) and the one behind it (the step's)


def run_engine(cfg, params, prompts, max_new, engine=None, slots=None, **engine_kw):
    """Every prompt through a paged `InferenceEngine` to `max_new` tokens:
    per request its tokens and the logprobs the engine reports for them."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    if engine is None:
        gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True, eos_token_id=VOCAB + 1, pad_token_id=0)
        engine = InferenceEngine(CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=3, kv_paging=True,
                                 num_slots=len(prompts), max_prompt_len=32, max_prefill_batch=2, prompt_bucket=16,
                                 kv_block_size=4, **engine_kw)
    slots = list(range(len(prompts))) if slots is None else slots
    engine.insert_requests([(p, max_new) for p in prompts], slots)
    tokens, logprobs = {s: [] for s in slots}, {s: [] for s in slots}
    for _ in range(max_new):
        tok, lp, emitted, _ = engine.step()
        for s in slots:
            if emitted[s]:
                tokens[s].append(int(tok[s]))
                logprobs[s].append(float(lp[s]))
    return engine, [tokens[s] for s in slots], [logprobs[s] for s in slots]


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
    """Prefill through the fresh-prompt program (query blocks of 8, so a
    prompt of 21 is three: the band's block over the columns it reaches, the
    sparse block under the mask of the chosen; latents and index keys into
    the arena), then paged decode with a step in flight: the banded latent
    kernel, the index's scores through the table, the chosen latents by
    address (interpret; `env` interprets the prefill's kernels' dispatch
    too) and the gather path, across block boundaries (4), rows of unequal
    length, against the reference's unabsorbed full forward."""
    if path == "env":
        monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    monkeypatch.setattr(tr, "PREFILL_QUERY_BLOCK", 8)
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
    # the arena: two full layers of 40 + 16 values a token, three sliding ones of 56, float32 here
    assert stats["kv_bytes_per_token"] == (2 * 56 + 3 * 56) * 4
    assert stats["kv_pool_bytes"] == sum(a.nbytes for layer in engine._pool["layers"] for a in layer.values())
    assert stats["kv_pool_bytes"] == engine._n_blocks * 4 * stats["kv_bytes_per_token"]
    # the step's counters: what a step reads where the read is a choice and not a walk
    walk = engine._kv_walk()
    cols = np.asarray([len(p) + 11 + 1 for p in prompts])
    whole = -(-cols // 4) * 4
    assert walk["layers"] == 5 and walk["resident"] == 5 * cols.sum()
    assert (walk["index_attendable"], walk["index_chosen"]) == (2 * cols.sum(), 2 * np.minimum(cols, 6).sum())
    if path == "xla":  # the gather path reads every row's whole table in every plane
        table = 3 * engine._n_tbl * 4
        assert (walk["walked_latent"], walk["index_scored"]) == (5 * table, 2 * table)
        assert walk["bytes"] == table * (2 * 56 + 3 * 56) * 4
    else:
        band = (-(-cols // 4) - np.maximum(cols - 5, 0) // 4) * 4
        assert (walk["walked_latent"], walk["index_scored"]) == (3 * band.sum(), 2 * whole.sum())
        assert walk["bytes_full"] == 2 * (whole.sum() * 16 + np.minimum(cols, 6).sum() * 40) * 4
        assert walk["bytes"] == walk["bytes_full"] + 3 * band.sum() * 56 * 4
        assert walk["bytes_dense_full"] == 2 * whole.sum() * 40 * 4 and walk["bytes_full"] < walk["bytes_dense_full"]
    assert stats["moe_dropped_tokens"] == 0.0 and 0 < stats["moe_local_assignment_share"] < 1


def test_a_slot_reused_by_a_second_request_sees_none_of_the_firsts_index_keys():
    """A long request, then a short one in the same slot (its blocks freed and
    handed out again): the second one's logprobs are the reference's over its
    own tokens alone, so no index key, latent or mask bit of the first is read."""
    cfg = tiny_cfg(attn_impl="flash")
    model = CausalLMPolicy(cfg)
    rng = np.random.default_rng(12)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = seeded_params(model, 12, tokens, jnp.ones_like(tokens))
    first = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (29, 17)]
    second = [rng.integers(1, VOCAB, size=7).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        engine, _, _ = run_engine(cfg, params, first, 3, decode_kernel="interpret")
        engine.release_slots([0, 1])
        engine, out, got = run_engine(cfg, params, second, 3, engine=engine, slots=[0])
    assert max(engine_errors(cfg, params, second, out, got)) < TOL


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """Section 4 of the model-configs guide, for the deployment the cell
    states: what each of 8 chips' expert layers gives (4 of 32 experts held,
    the router scoring all 32 on every chip, the shared expert computed whole
    on each) adds up, the shared expert's part taken once, to the reference's
    uncut layer; and the reference given a share gives that share's part."""
    kw = dict(d_model=32, moe_d_ff=16, moe_shared_d_ff=16, moe_experts=32, moe_top_k=4)
    whole = tiny_cfg(moe_local_experts=0, **kw)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(1, 40, whole.d_model)), jnp.float32)
    params = seeded_params(SparseMoE(whole), 9, x)
    layer = lambda p, c: moe_ref.expert_ffn(x[0], p, top_k=c.moe_top_k, offset=c.moe_local_offset,
                                            scaling=c.moe_routed_scale, int8=False)
    with jax.default_matmul_precision("highest"):
        want = layer(params, whole)
        shared = moe_ref.glu(x[0], *(params[n]["kernel"] for n in ("shared_gate", "shared_up", "shared_down")), False)
        assert float(jnp.abs(shared).max()) > 0
        total = shared
        for chip in range(8):
            cfg = tiny_cfg(moe_local_experts=4, moe_local_offset=4 * chip, **kw)
            share = {name: {"kernel": jnp.concatenate(jnp.split(params[name]["kernel"], 32, axis=1)[4 * chip:4 * chip + 4],
                                                      axis=1)}
                     for name in ("expert_gate", "expert_up", "expert_down")}
            part = SparseMoE(cfg).apply({"params": {**params, **share}}, x)[0]
            np.testing.assert_allclose(np.asarray(part), np.asarray(layer({**params, **share}, cfg)), atol=5e-5)
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    # a long call routed a block of tokens at a time gives what the whole call gives: 40 tokens
    # in blocks of 16, 16 and 8, and 32 in two equal blocks, which are one traced body run twice
    blocks = dataclasses.replace(whole, moe_token_block=16)
    with jax.default_matmul_precision("highest"):
        for tokens in (x, x[:, :32]):
            got, stats = SparseMoE(blocks).apply({"params": params}, tokens, mutable=["moe_stats"])
            want, whole_stats = SparseMoE(whole).apply({"params": params}, tokens, mutable=["moe_stats"])
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
            assert jax.tree_util.tree_structure(stats) == jax.tree_util.tree_structure(whole_stats)
            for a, b in zip(jax.tree_util.tree_leaves(stats), jax.tree_util.tree_leaves(whole_stats)):
                assert a.shape == b.shape


def test_one_ppo_cycle_through_train_at_dots3_tiny(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=24, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3),
        model=dict(model_path="random:dots3-note-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=2)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["abcdefgha", "cdefg", "e", "ghabcdefghab"], eval_prompts=["ab", "cd"], config=config)
    assert trainer.iter_count == 1 and trainer.model_cfg.has_index_layers
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    # the unfrozen layers are a sliding one and a full one: both kinds' leaves move, the index's do not
    # (the choice is not differentiated: the gradient flows through the attention over the chosen)
    assert any("block_3" in k and "kv_b_proj" in k for k in train) and any("block_4" in k and "gate_proj" in k
                                                                          for k in train)
    still = [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))]
    assert still and all("indexer" in k for k in still)


def test_hf_config_keys_and_tensor_names_round_trip(tmp_path):
    """`model_type: dots3_note`: the benchmark file's published keys give the
    program's configuration and come back; a random state dict under the
    family's tensor names loads into the tree and goes out again letter for
    letter (unchecked against the published weights)."""
    with open(os.path.join(BENCH, "configs", "dots3-note-prev.json")) as f:
        bench = json.load(f)
    published = {k: v for k, v in bench.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(published))
    cfg = hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32)
    extra = dict(bench["bench"]["program"]["model_extra_configs"])
    extra.pop("moe_local_experts")  # the file's `n_routed_experts` is the experts held
    want = config_from_preset("dots3-note-prev", extra.pop("vocab_size"), **{**extra, "attn_impl": "xla"},
                              moe_experts=32, hf_family="dots3_note", dtype=jnp.float32)
    assert cfg == want
    again_dir = tmp_path / "again"
    again_dir.mkdir()
    (again_dir / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    assert hf_interop.config_from_hf(str(again_dir), dtype=jnp.float32) == cfg
    assert {k: v for k, v in hf_interop.config_to_hf(cfg).items() if k in published} == {
        k: v for k, v in published.items() if k in hf_interop.config_to_hf(cfg)}
    for key, value in (("rope_scaling", {"type": "yarn"}), ("n_group", 8), ("attention_gate_type", "elementwise"),
                       ("swa_attention_gate_type", "none")):
        with pytest.raises(NotImplementedError, match=f"dots3_note with {key}="):
            hf_interop._dots3_kwargs({**published, key: value})

    tiny = tiny_cfg(moe_local_experts=4, moe_local_offset=2, hf_family="dots3_note")
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jitted_init(CausalLMPolicy(tiny))(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    rng = np.random.default_rng(0)
    names = hf_interop.params_to_hf_state_dict(template, tiny)
    sd = {k: rng.normal(size=v.shape).astype(np.float32) for k, v in names.items()}
    full, swa = "model.layers.4.", "model.layers.2."
    attn = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight", "kv_a_proj_with_mqa.weight",
            "kv_a_layernorm.weight", "kv_b_proj.weight", "o_proj.weight", "gate_proj.weight")
    index = ("indexer.wq_b.weight", "indexer.wk.weight", "indexer.k_norm.weight", "indexer.k_norm.bias",
             "indexer.weights_proj.weight")
    assert {layer + "self_attn." + n for layer in (full, swa) for n in attn} <= set(sd)
    assert {full + "self_attn." + n for n in index} <= set(sd)
    assert not any(k.startswith(swa + "self_attn.indexer") for k in sd)
    assert {full + n for n in ("input_layernorm.weight", "post_attention_layernorm.weight", "mlp.gate.weight",
                               "mlp.gate.e_score_correction_bias", "mlp.experts.2.gate_proj.weight",
                               "mlp.experts.5.down_proj.weight", "mlp.shared_experts.up_proj.weight")} <= set(sd)
    assert {"model.layers.0.mlp.gate_proj.weight", "model.norm.weight", "lm_head.weight"} <= set(sd)
    assert full + "mlp.experts.1.gate_proj.weight" not in sd
    assert (sd[full + "self_attn.kv_b_proj.weight"].shape, sd[swa + "self_attn.kv_b_proj.weight"].shape) == (
        (4 * 28, 32), (2 * 36, 48))
    lm = hf_interop._load_dots3_note(sd, tiny)
    jax.tree_util.tree_map(lambda t, a: np.testing.assert_equal(t.shape, np.shape(a)), template["lm"], lm)
    back = hf_interop.params_to_hf_state_dict({"lm": lm}, tiny)
    assert sorted(back) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k])


REFUSED = [
    ("prefix_cache over a latent cache", dict(kv_paging=True, prefix_cache=True)),
    ("dense slot pool .* over a latent cache", dict()),
    ("int8 arena .* over a latent cache", dict(kv_paging=True, kv_cache_dtype="int8")),
]


@pytest.mark.parametrize("match,engine_kw", REFUSED, ids=[m.split(" ")[0] for m, _ in REFUSED])
def test_paths_that_cannot_follow_refuse_by_name(match, engine_kw):
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    with pytest.raises(NotImplementedError, match=match) as refusal:
        InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8, **engine_kw)
    assert f"{FULL} / {SLIDING} layers" in str(refusal.value)


def test_sessions_a_shared_prompt_and_an_int8_plane_refuse_by_name():
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    engine = InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8, kv_paging=True)
    with pytest.raises(NotImplementedError, match="sessions .* over a latent cache"):
        engine.enable_sessions()
    dense_ffn = dataclasses.replace(cfg, moe_experts=0, moe_router="softmax", moe_shared_d_ff=0, moe_local_experts=0)
    with pytest.raises(NotImplementedError, match="int8 latent arena"):
        init_paged_kv_arena(cfg, 4, 4, jnp.int8)
    with pytest.raises(NotImplementedError, match=f"{FULL} / {SLIDING} layers with lora_rank"):
        dataclasses.replace(dense_ffn, lora_rank=4)
    with pytest.raises(NotImplementedError, match="layers with sliding_window"):
        dataclasses.replace(cfg, sliding_window=4)  # a latent kind's band is its own


def test_flops_and_cache_bytes_count_both_kinds():
    from trlx_tpu.observability import flops, hbm

    cfg = tiny_cfg()
    d = cfg.d_model
    full = 2 * (d * 24 + 24 * 4 * 24 + d * 40 + 32 * 4 * 28 + 4 * 12 * d + 24 * 4 * 16 + d * (16 + 4)) + 2 * d * 4
    swa = 2 * (d * 24 + 24 * 2 * 32 + d * 56 + 48 * 2 * 36 + 2 * 12 * d) + 2 * d * 2
    dense = 6 * d * cfg.d_ff
    experts = 2 * d * 8 + (2 * 2 / 8) * 6 * d * cfg.expert_d_ff + 6 * d * cfg.moe_shared_d_ff
    assert [flops.layer_matmul_flops(cfg, i) for i in range(5)] == [full + dense, *[swa + experts] * 3,
                                                                    full + experts]
    # a band reads 5 keys, an index scores all 20 and the layer attends to the 6 it keeps
    assert flops.layer_attention_flops(cfg, 1, 20) == 2 * 5 * 2 * (32 + 12)
    assert flops.layer_attention_flops(cfg, 4, 20) == 2 * 20 * 4 * 16 + 2 * 6 * 4 * (24 + 12)
    arena = init_paged_kv_arena(cfg, 9, 4, jnp.bfloat16)
    assert hbm.paged_arena_bytes(cfg, 9, 4, "bfloat16") == sum(a.nbytes for layer in arena for a in layer.values())
    assert hbm.paged_arena_bytes(cfg, 9, 4, "bfloat16") == 9 * 4 * (2 * 56 + 3 * 56) * 2
    dense_cache = init_kv_cache(cfg, 3, 20, jnp.bfloat16)
    assert hbm.decode_state_bytes(cfg, 3, 20, "bfloat16") == sum(
        a.nbytes for layer in dense_cache["layers"] for a in layer.values())
