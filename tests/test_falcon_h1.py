"""Falcon-H1-style stacks (a Mamba-2 mixer keeping a recurrent matrix and
convolution tails a ROW beside GQA attention keeping K and V by head a TOKEN
in EVERY block, their outputs summed into one residual add; muP forward
multipliers) against the benchmark's plain reference
`bench/reference/falcon_h1.py`, at test size on the CPU, on seeded weights.

The leaves are the benchmark's (`bench/benchlib/weights.py`: every leaf from
the seed) with `A_log`, `dt_bias` and `D` of every layer set by the rule of the
family's published initialisation, as the cell's job sets them
(`bench/jobs/serve_parallel_hybrid.py:family_leaves`), at steps thirty times
the family's (dt in [0.3, 3], A in [-1, -0.1]: a memory of 0.3 to 30
positions). At the seed's own rule a state forgets in two positions and a
dropped or misplaced state would not show; at the family's own steps and a
state of 16 with B and C under multipliers of 0.18 and 0.5, what the state adds
to a mixer's output is a hundredth of the skip `D x` and shows no better. One policy, one engine a decode path and one prefill shape serve every
test of this file (tests/test_falcon_h1_train.py holds the PPO cycle, a file
and so a worker of its own).

Tolerances. The lm_head multiplier (1/128) on seeded weights makes every
logit a few hundredths, so a logprob is -log(vocabulary) to two digits and
float32 resolves it to 5e-7: logits are compared RELATIVE to the largest logit
wanted (float32 program against float32 reference, both at `highest`: 2e-6,
the readings are 2e-7 to 5e-7; each assumed item flipped in the reference
moves them by 3e-3 or more, the state rounded to bfloat16 by 2e-5), and the
engine's logprobs absolutely at 3e-6 (six float32 steps at 4.6), where a
dropped skip, gate order or norm grouping moves a logprob by 1e-3 or more and
a slot's state left as another request wrote it by 1e-4 or more."""

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
from trlx_tpu.models.transformer import (  # noqa: E402
    Attention, LayerKeeps, Mamba2Mixer, Multipliers, TransformerLM, init_kv_cache)
from trlx_tpu.observability import hbm  # noqa: E402
from trlx_tpu.ops import ssd  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn  # noqa: E402

VOCAB = 96
REL, TOL = 2e-6, 3e-6
STEPS = dict(dt_range=(0.3, 3.0), a_range=(0.1, 1.0))
ROWS, WIDTH = 3, 64
ref = load_module("reference/falcon_h1.py")
job = load_module("jobs/serve_parallel_hybrid.py")
plain = load_module("reference/plain_ops.py")
with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json")) as f:
    RAW = json.load(f)


def tiny_cfg(**kw):
    return config_from_preset("falcon-h1-tiny", VOCAB, **{"dtype": jnp.float32, **kw})


def sizes_of(cfg, *departures):
    """The published config keys the reference reads, for a program config."""
    return {**hf_interop.config_to_hf(cfg, "falcon_h1"), "departures": list(departures)}


@pytest.fixture(scope="module")
def policy():
    cfg = tiny_cfg(attn_impl="flash")
    tokens = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(CausalLMPolicy(cfg), tokens, jnp.ones_like(tokens))
    return cfg, job.family_leaves(weights.make_params(shapes, 43, jnp.float32), 43, **STEPS)


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


def off(got, want, where=None):
    """The largest difference over the largest entry wanted."""
    got, want = np.asarray(got), np.asarray(want)
    diff = np.abs(got - want)
    return float((diff if where is None else diff[where]).max() / np.abs(want).max())


def test_presets_state_every_published_size_and_the_cut_counts_what_the_file_says():
    published = config_from_preset("falcon-h1-34b", 261120)
    assert (published.n_layers, published.d_model, published.n_heads, published.kv_heads, published.head_dim,
            published.d_ff) == (72, 5120, 20, 4, 128, 21504)
    assert set(published.layer_types) == {"ssm_attention"} and published.rope_theta == 1e11
    assert (published.ssm_heads, published.ssm_head_dim, published.ssm_state, published.ssm_groups,
            published.ssm_conv_kernel, published.ssm_chunk, published.ssm_width) == (32, 128, 256, 2, 4, 128, 5120)
    m = published.multipliers
    assert (m.embedding, m.lm_head, m.attention_in, m.attention_out, m.key, m.ssm_in, m.ssm_out, *m.ssm, *m.mlp) == tuple(
        RAW[k] for k in ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
                         "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
    ) + tuple(RAW["ssm_multipliers"]) + tuple(RAW["mlp_multipliers"])
    # one kind, and it is in both "sets": it builds a bias AND reads the token mask AND keeps slot arrays
    assert published.attention_kinds == ("ssm_attention",) and published.slot_state_kinds == ("ssm_attention",)
    assert published.has_slot_state and published.blocks_read_token_mask and published.state_chunk == 128
    # every other family multiplies nothing, keeps what it kept and names the kinds it named
    llama, solar = config_from_preset("llama-tiny", VOCAB), config_from_preset("solar-open2-tiny", VOCAB)
    assert llama.multipliers == Multipliers() and not llama.has_slot_state and llama.attention_kinds == ()
    assert solar.attention_kinds == () and solar.slot_state_kinds == ("linear_attention",) and solar.state_chunk == 64
    assert config_from_preset("ling-flash-tiny", VOCAB).attention_kinds == ("latent_attention",)
    assert config_from_preset("lfm2-tiny", VOCAB).slot_state_kinds == ("conv",)

    extra = dict(RAW["bench"]["program"]["model_extra_configs"])
    cut = config_from_preset("falcon-h1-34b", extra.pop("vocab_size"), **extra, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
    assert cut.n_layers == RAW["num_hidden_layers"] == 4 and RAW["bench"]["reduced"] == ["num_hidden_layers"]
    # a token: K and V of 4 heads of 128; a slot: 4 MB of float32 matrix and 30,720 B of tails: BOTH, in every layer
    assert cut.layer_keeps(3) == LayerKeeps(
        token=(("k", (4, 128)), ("v", (4, 128))),
        slot=(("state", (32, 256, 128), jnp.float32), ("tails", (3, 5120), None)))
    assert cut.cache_planes(0) == (512, 512) and cut.cached_values_per_token == 4 * 1024
    assert cut.slot_state_bytes_per_slot(jnp.bfloat16) == 4 * (4_194_304 + 30_720)
    assert hbm.slot_state_bytes(cut, 128, "bfloat16") == 2_163_212_288
    assert hbm.paged_arena_bytes(cut, 6145, 32, "bfloat16") == 1_610_874_880
    precision = RAW["bench"]["precision"]["serve"]
    assert job.stated_pool_bytes(6144, 32, 128, RAW, precision) == 2_163_212_288 + 1_610_874_880
    assert ssd.decode_kernel_takes(cut.ssm_heads, cut.ssm_state, cut.ssm_head_dim)
    # the parameters held at the cut, recounted from shapes, against the issue's and the file's count
    t = jnp.zeros((1, 8), jnp.int32)
    shapes = weights.param_shapes(CausalLMPolicy(cut), t, jnp.ones_like(t))["lm"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    held = RAW["bench"]["parameters_held"]
    assert count(shapes) == held["total"] == 4_394_354_048
    assert count(shapes["block_0"]) == held["a_layer"] == 430_120_032
    assert count(shapes["block_0"]["attn"]) == held["attention_a_layer"] == 31_457_280
    assert count(shapes["block_0"]["ssm"]) == held["ssm_mixer_a_layer"] == 68_351_072
    assert count(shapes["block_0"]["mlp"]) == held["feed_forward_a_layer"] == 330_301_440
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == held["embedding_and_head"] == 2 * 261120 * 5120
    # every leaf ends in a name the benchmark's weight rule knows
    from flax.traverse_util import flatten_dict
    assert {path[-1] for path in flatten_dict(shapes)} == {"kernel", "bias", "scale", "embedding"}
    assert set(shapes["block_0"]["ssm"]) == {"in_proj", "conv1d", "a_log", "dt_bias", "d", "norm", "out_proj"}


@pytest.fixture(scope="module")
def forward(policy):
    """The forward without a cache, once: left-padded rows of unequal length
    (60 positions: three chunks of 16 and 12 more)."""
    cfg, params = policy
    tokens, mask = left_padded(np.random.default_rng(7), [60, 33, 5], 60)
    with jax.default_matmul_precision("highest"):
        logits = jitted_forward(cfg)(params["lm"], tokens, mask)
    return tokens, mask, logits


# every assumed item of the configuration file that changes the numbers (reference `departures`), and
# the precision control: at the family's leaves a state rounded to bfloat16 shows
DEPARTURES = {"no_d": 0.1, "norm_before_gate": 0.1, "ungrouped_norm": 3e-3, "state_bf16": 1e-5}


@pytest.mark.parametrize("departure", [None] + sorted(DEPARTURES))
def test_forward_matches_the_reference_and_each_assumed_item_departed_from_does_not(policy, forward, departure):
    """The forward without a cache (the chunked form, fused attention at a
    group of 5) against the reference (a scan, a head at a time)."""
    (cfg, params), (tokens, mask, logits) = policy, forward
    pad = lambda a: np.pad(a, ((0, 0), (0, WIDTH - a.shape[1])))
    want = np.asarray(ref.logits(params["lm"], pad(tokens), pad(mask),
                                 sizes_of(cfg, *([departure] if departure else []))))[:, :60]
    err = off(logits, want, mask.astype(bool))
    assert err < REL if departure is None else err > DEPARTURES[departure], (departure, err)
    if departure is None:
        got = np.asarray(plain.logprobs_of_next(logits, jnp.asarray(tokens)))
        valid = (mask[:, 1:] * mask[:, :-1]).astype(bool)
        assert np.abs(got - reference_logprobs(params["lm"], cfg, tokens, mask))[valid].max() < TOL


def test_each_branch_alone_matches_the_reference_s(policy, forward):
    """What the attention and the SSM mixer each add to the residual, before
    they are summed, in both blocks: at multipliers of 0.0375 and 0.088 a
    wrong branch could hide inside a tolerance on the sum."""
    (cfg, params), (tokens, mask, _) = policy, forward
    branch = lambda mdl, name: isinstance(mdl, (Attention, Mamba2Mixer)) and name == "__call__"
    with jax.default_matmul_precision("highest"):
        _, state = jax.jit(lambda p, t, m: TransformerLM(cfg).apply(
            {"params": p}, t, m, capture_intermediates=branch, mutable=["intermediates"]))(params["lm"], tokens, mask)
    real = mask.astype(bool)
    for layer in range(cfg.n_layers):
        caught = state["intermediates"][f"block_{layer}"]
        got_attn = np.asarray(caught["attn"]["__call__"][0][0]) * cfg.multipliers.attention_out  # `Block` applies it
        got_ssm = np.asarray(caught["ssm"]["__call__"][0][0])
        for row in range(tokens.shape[0]):
            want_attn, want_ssm = ref.branches(params["lm"], tokens[row], mask[row], sizes_of(cfg), layer=layer)
            assert off(got_attn[row][real[row]], np.asarray(want_attn)[real[row]]) < REL, (layer, row)
            assert off(got_ssm[row][real[row]], np.asarray(want_ssm)[real[row]]) < REL, (layer, row)
            # neither is a formality beside the other
            assert 0.1 < np.abs(want_ssm).max() / np.abs(want_attn).max() < 10


MOVED = ["embedding", "lm_head", "attention_in", "attention_out", "key", "ssm_in", "ssm_out",
         "ssm.0", "ssm.1", "ssm.2", "ssm.3", "ssm.4", "mlp.0", "mlp.1"]


@pytest.mark.parametrize("moved", MOVED)
def test_every_multiplier_moved_off_its_value_changes_the_output_as_the_reference_s(policy, forward, moved):
    """Each of the fourteen numbers times 1.5, one at a time: the program's
    logits move, and to where the reference's move."""
    (cfg, params), (tokens, mask, logits) = policy, forward
    name, _, index = moved.partition(".")
    value = getattr(cfg.multipliers, name)
    value = 1.5 * value if not index else tuple(v * (1.5 if i == int(index) else 1.0) for i, v in enumerate(value))
    other = dataclasses.replace(cfg, multipliers=dataclasses.replace(cfg.multipliers, **{name: value}))
    with jax.default_matmul_precision("highest"):
        got = jitted_forward(other)(params["lm"], tokens[:1], mask[:1])
    want = ref.logits(params["lm"], tokens[:1], mask[:1], sizes_of(other))
    assert off(got, want) < REL, moved
    assert off(got, np.asarray(logits)[:1]) > 1e-4, moved  # fifty times what program and reference differ by


def test_hf_config_keys_give_the_preset_names_round_trip_and_other_equations_are_refused(policy, tmp_path):
    keys = {k: v for k, v in RAW.items() if k != "bench"}
    keys["num_hidden_layers"] = 72
    (tmp_path / "config.json").write_text(json.dumps(keys))
    assert hf_interop.config_from_hf(str(tmp_path)) == config_from_preset(
        "falcon-h1-34b", 261120, hf_family="falcon_h1")
    cfg, params = policy
    (tmp_path / "config.json").write_text(json.dumps(hf_interop.config_to_hf(cfg)))
    assert hf_interop.config_from_hf(str(tmp_path), dtype=jnp.float32, attn_impl="flash") == dataclasses.replace(
        cfg, hf_family="falcon_h1")
    assert hf_interop.infer_family(cfg) == "falcon_h1"
    # parameter names both ways, as the family's checkpoint has them
    sd = hf_interop.params_to_hf_state_dict(params, cfg)
    p = "model.layers.1."
    assert {k[len(p):] for k in sd if k.startswith(p)} == {
        "input_layernorm.weight", "pre_ff_layernorm.weight",
        *(f"self_attn.{n}_proj.weight" for n in "qkvo"), *(f"feed_forward.{n}_proj.weight" for n in ("gate", "up", "down")),
        "mamba.in_proj.weight", "mamba.conv1d.weight", "mamba.conv1d.bias", "mamba.A_log", "mamba.D", "mamba.dt_bias",
        "mamba.norm.weight", "mamba.out_proj.weight"}
    assert {k for k in sd if not k.startswith("model.layers.")} == {
        "model.embed_tokens.weight", "model.final_layernorm.weight", "lm_head.weight"}
    assert sd[p + "mamba.conv1d.weight"].shape == (cfg.ssm_width, 1, 4) and sd[p + "mamba.in_proj.weight"].shape == (
        2 * 32 + 2 * 2 * 16 + 4, 64)
    back = hf_interop._load_falcon_h1(sd, cfg)
    flat = lambda tree: {"/".join(k): np.asarray(v) for k, v in __import__("flax").traverse_util.flatten_dict(tree).items()}
    want, got = flat(params["lm"]), flat(back)
    assert want.keys() == got.keys() and all(np.array_equal(want[k], got[k]) for k in want)
    for key, value in (("mamba_rms_norm", False), ("mamba_norm_before_gate", True), ("attn_layer_indices", [0, 2]),
                       ("attention_bias", True), ("mamba_proj_bias", True), ("mlp_bias", True)):
        (tmp_path / "config.json").write_text(json.dumps({**keys, key: value}))
        with pytest.raises(NotImplementedError, match=f"falcon_h1 with {key}="):
            hf_interop.config_from_hf(str(tmp_path))
        with pytest.raises(NotImplementedError, match=f"falcon_h1 with {key}="):
            ref.logits({}, np.zeros((1, 4), np.int32), np.ones((1, 4), np.int32), {**sizes_of(cfg), key: value})
    for bad, match in ((dict(ssm_heads=3), "whole groups"), (dict(ssm_state=0), "ssm_state 0"),
                       (dict(multipliers=dict(ssm=(1.0, 2.0))), "5 column groups")):
        with pytest.raises(ValueError, match=match):
            tiny_cfg(**bad)
    with pytest.raises(NotImplementedError, match="ssm_attention layers with lora_rank"):
        tiny_cfg(lora_rank=4)
    with pytest.raises(NotImplementedError, match="multipliers.mlp are a gated MLP's"):
        jitted_init(TransformerLM(tiny_cfg(glu=False)))(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
                                                        jnp.ones((1, 4), jnp.int32))


def test_sampler_through_the_scalar_index_cache_matches_the_reference(policy):
    """`generate`: the prefill of left-padded prompts (K and V into every
    layer's cache, chunks from an empty state and the tails that end at each
    row's last token beside them), then the fused decode loop."""
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
    assert {k: (v.shape, v.dtype) for k, v in cache[1].items()} == {
        "k": ((2, 8, 2, 8), jnp.bfloat16), "v": ((2, 8, 2, 8), jnp.bfloat16),
        "state": ((2, 4, 16, 8), jnp.float32), "tails": ((2, 3, 96), jnp.bfloat16)}


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
def test_engine_end_to_end_matches_the_reference_with_no_fallback(policy, engines, path):
    """The fresh-prompt insert (right-padded rows: from the SAME layer K and V
    go into the row's blocks through the fused prefill and the chunked form's
    final state and the tails into the row's slot), then 24 decode steps with
    a step in flight: `paged_decode` over 5 query heads a K/V head and
    `ssd_decode` in every layer, through the interpreter or the plain paths,
    rows of unequal length. The counters count every layer in both."""
    (cfg, params), engine = policy, engines[path]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (29, 5, 16)]
    with jax.default_matmul_precision("highest"):
        assert all(set(layer) == {"k", "v", "state", "tails"} for layer in engine._pool["layers"])
        engine.insert_requests([(p, 24) for p in prompts], [0, 1, 2])
        counters, walk = engine._slot_state_step(), engine._kv_walk()
        out, got = drain(engine, [0, 1, 2], 24)
        engine.release_slots([0, 1, 2])
    assert engine.decode_path == path
    stats = engine.kv_stats()
    assert stats["kv_kernel_fallbacks"] == {} and stats["decode_steps_ahead_total"] > 0
    assert sorted(engine._paged_insert_fns) == [(1, 32, True)]
    assert [len(got[s]) for s in range(3)] == [24] * 3
    assert max(engine_errors(cfg, params, prompts, [out[s] for s in range(3)], [got[s] for s in range(3)])) < TOL
    # what a slot holds beside the arena, and what a step does to it: BOTH layers, float32 tails here
    per_slot = 2 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    assert stats["slot_state_bytes_per_slot"] == per_slot and stats["slot_state_bytes"] == 3 * per_slot
    assert counters == {"steps": 1, "slots": 3, "live": 3, "layers": 2, "bytes": 2 * 3 * per_slot}
    # the arena: BOTH layers again, K and V of 2 heads of 8 a token
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 8 * 4
    assert walk["layers"] == 2 and walk["walked_latent"] == walk["walked_window"] == 0 and walk["walked_full"] > 0
    assert walk["bytes"] == walk["walked_full"] * 2 * 2 * 8 * 4
    held = sum(a.nbytes for layer in engine._pool["layers"] for a in layer.values())
    assert stats["kv_pool_bytes"] + stats["slot_state_bytes"] == held


def poison_slot(engine, slot):
    """Every layer's arrays of one slot set to 10: what another request might have left."""
    engine._pool = {**engine._pool, "layers": [
        {**layer, "state": layer["state"].at[slot].set(10.0), "tails": layer["tails"].at[slot].set(10.0)}
        for layer in engine._pool["layers"]]}


def test_a_reused_slot_starts_from_a_zero_state_and_a_cancelled_step_touches_nobody_s(policy, engines):
    """Nothing clears a slot's state: an insert overwrites the whole row from
    an empty state, so a request in a slot whose arrays hold anything at all
    (here 10 everywhere) reads what the reference reads; the comparison would
    have seen it (the same arrays set AFTER the insert move the logprobs); and
    a request released with a step in flight (the step still moves the row)
    leaves its neighbour, and whoever gets the slot next, alone."""
    (cfg, params), engine = policy, engines["interpret"]
    rng = np.random.default_rng(13)
    first, second, other = (rng.integers(1, VOCAB, size=n).astype(np.int32) for n in (30, 9, 14))
    with jax.default_matmul_precision("highest"):
        engine.insert_requests([(first, 24), (other, 24)], [0, 1])
        head_out, head_lp = drain(engine, [1], 2)  # a step is now in flight for both rows
        engine.release_slots([0])  # cancelled: the step in flight still decodes a token for it
        poison_slot(engine, 0)
        engine.insert_requests([(second, 24)], [0])
        assert float(jnp.abs(engine._pool["layers"][1]["state"][0]).max()) < 5.0
        out, lp = drain(engine, [0, 1], 22)
        engine.release_slots([0, 1])
        # the control: the slot's arrays as another request might have left them, behind the insert
        engine.insert_requests([(second, 24)], [0])
        poison_slot(engine, 0)
        stale_out, stale_lp = drain(engine, [0], 8)
        engine.release_slots([0])
    out[1], lp[1] = head_out[1] + out[1], head_lp[1] + lp[1]
    errs = engine_errors(cfg, params, [second, other], [out[0], out[1]], [lp[0], lp[1]])
    assert max(errs) < TOL and len(lp[0]) >= 20 and len(lp[1]) == 24
    assert engine_errors(cfg, params, [second], [stale_out[0]], [stale_lp[0]])[0] > 30 * TOL
    assert engine.kv_stats()["kv_kernel_fallbacks"] == {}


def test_the_prefill_state_counter_span_says_what_the_chunked_form_runs(policy, engines, monkeypatch):
    """`trlx:engine.prefill_state`, one an admission while a session listens:
    the tokens, the positions dispatched, the layers that run a chunked
    recurrence (both: each also keeps K/V) and a layer's chunks of 16."""
    from trlx_tpu.observability import tracing

    engine, seen = engines["xla"], []
    monkeypatch.setattr(tracing, "active", lambda: True)
    monkeypatch.setattr(tracing, "counters", lambda name, **kw: seen.append((name, kw)))
    programs = engine._prefill_programs([(np.arange(1, 30, dtype=np.int32), 4), (np.arange(1, 6, dtype=np.int32), 4)])
    assert engine._count_admission(programs) == (2, 34, 64, 2)
    assert ("engine.prefill_state", dict(tokens=34, padded_tokens=64, linear_layers=2, chunks=4, form="xla")) in seen


KEPT = "ssm_attention layers keep state, tails a slot"
REFUSALS = [
    ("prefix_cache", dict(prefix_cache=True), f"prefix_cache over slot state .{KEPT}"),
    ("dense_slot_pool", dict(kv_paging=False), f"dense slot pool .* over slot state .{KEPT}"),
    ("int8_arena", dict(kv_cache_dtype="int8"), f"int8 arena .* over slot state .{KEPT}"),
]


@pytest.mark.parametrize("name,kw,match", REFUSALS, ids=[r[0] for r in REFUSALS])
def test_what_cannot_follow_slot_state_refuses_a_layer_that_also_keeps_planes_by_name(name, kw, match):
    cfg = tiny_cfg()
    gen_cfg = GenerationConfig(max_new_tokens=4, eos_token_id=VOCAB + 1)
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngine(CausalLMPolicy(cfg), cfg, None, gen_cfg, num_slots=2, max_prompt_len=8,
                        **{"kv_paging": True, **kw})


def test_sessions_submit_n_and_a_slotless_pool_refuse_by_name(engines):
    engine = engines["xla"]
    with pytest.raises(NotImplementedError, match=f"sessions .* over slot state .{KEPT}"):
        engine.enable_sessions()
    with pytest.raises(NotImplementedError, match=f"submit_n's shared prompt over slot state .{KEPT}"):
        Scheduler(engine).submit_n(np.arange(1, 5, dtype=np.int32), 2, max_new_tokens=4)
    cfg = engine.model_cfg
    from trlx_tpu.models.transformer import init_paged_kv_arena
    with pytest.raises(NotImplementedError, match=f"a paged pool over slot state .{KEPT}. needs its number of slots"):
        init_paged_kv_arena(cfg, 8, 4)
    # the older kinds' refusals say what THEIR layers keep
    with pytest.raises(NotImplementedError, match="conv layers keep conv a slot"):
        init_paged_kv_arena(config_from_preset("lfm2-tiny", VOCAB), 8, 4)
    with pytest.raises(NotImplementedError, match="linear_attention layers keep state, tails a slot"):
        init_paged_kv_arena(config_from_preset("solar-open2-tiny", VOCAB), 8, 4)
