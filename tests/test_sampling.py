"""Sampling-engine tests: determinism, eos/mask semantics, top-k/top-p,
logit-mask transition constraints, ILQL advantage shift."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.models import build_model
from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn, process_logits


EOS, PAD = 63, 62


def make_lm(**kw):
    mc = ModelConfig(model_path="random:gpt2-tiny", model_extra_configs={"dtype": "float32"})
    return build_model(mc, vocab_size=64, **kw)


def gen_cfg(**kw):
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("eos_token_id", EOS)
    kw.setdefault("pad_token_id", PAD)
    return GenerationConfig(**kw)


def prompts():
    ids = jnp.asarray([[PAD, PAD, 5, 6, 7], [PAD, 1, 2, 3, 4]], dtype=jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1, 1], [0, 1, 1, 1, 1]], dtype=jnp.int32)
    return ids, mask


def test_greedy_deterministic():
    model, cfg, params = make_lm()
    ids, mask = prompts()
    fn = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=False)))
    out1 = fn(params, ids, mask, jax.random.PRNGKey(0))
    out2 = fn(params, ids, mask, jax.random.PRNGKey(123))
    np.testing.assert_array_equal(np.asarray(out1["response_tokens"]), np.asarray(out2["response_tokens"]))
    assert out1["samples"].shape == (2, 5 + 8)


def test_sampling_seeded_reproducible():
    model, cfg, params = make_lm()
    ids, mask = prompts()
    fn = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=True, temperature=0.9)))
    a = fn(params, ids, mask, jax.random.PRNGKey(7))
    b = fn(params, ids, mask, jax.random.PRNGKey(7))
    c = fn(params, ids, mask, jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a["response_tokens"]), np.asarray(b["response_tokens"]))
    assert not np.array_equal(np.asarray(a["response_tokens"]), np.asarray(c["response_tokens"]))


def test_eos_finishes_and_pads():
    """Force EOS as the only choice after 3 steps via a transition mask is
    hard; instead bias the model by masking everything but EOS with top_k=1
    on a crafted logit_mask: simpler — use logit_mask forbidding all
    transitions except to EOS from any token. Then every response is one
    EOS token followed by pads with mask 0."""
    model, cfg, params = make_lm()
    ids, mask = prompts()
    forbid = np.ones((64, 64), dtype=bool)
    forbid[:, EOS] = False  # only EOS allowed
    fn = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=False), logit_mask=forbid))
    out = fn(params, ids, mask, jax.random.PRNGKey(0))
    toks = np.asarray(out["response_tokens"])
    m = np.asarray(out["response_mask"])
    assert (toks[:, 0] == EOS).all()
    assert (toks[:, 1:] == PAD).all()
    # EOS token itself is valid, the rest not
    assert (m[:, 0] == 1).all() and (m[:, 1:] == 0).all()


def test_logit_mask_transitions_respected():
    """With an adjacency constraint, every generated transition must be an
    allowed edge (randomwalks-style)."""
    rng = np.random.RandomState(0)
    adj = rng.rand(64, 64) < 0.3
    adj[:, EOS] = True  # always allow eos so sequences can finish
    forbid = ~adj
    model, cfg, params = make_lm()
    ids, mask = prompts()
    fn = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=True), logit_mask=forbid))
    out = fn(params, ids, mask, jax.random.PRNGKey(3))
    toks = np.asarray(out["response_tokens"])
    ms = np.asarray(out["response_mask"])
    prev = np.asarray(ids[:, -1])
    for b in range(toks.shape[0]):
        p = prev[b]
        for t in range(toks.shape[1]):
            if ms[b, t] == 0:
                break
            assert adj[p, toks[b, t]], f"forbidden transition {p}->{toks[b, t]}"
            p = toks[b, t]


def test_top_k_restricts_support():
    model, cfg, params = make_lm()
    ids, mask = prompts()
    # top_k=1 sampling must equal greedy
    fn_k1 = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=True, top_k=1)))
    fn_greedy = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=False)))
    a = fn_k1(params, ids, mask, jax.random.PRNGKey(0))
    b = fn_greedy(params, ids, mask, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(a["response_tokens"]), np.asarray(b["response_tokens"]))


def test_top_p_processor():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    out = process_logits(logits, gen_cfg(do_sample=True, top_p=0.7, eos_token_id=3, pad_token_id=3), jnp.asarray(0))
    kept = np.isfinite(np.asarray(out))[0]
    # 0.5 + 0.3 >= 0.7 -> keep first two only
    assert kept.tolist() == [True, True, False, False]


def test_min_new_tokens_blocks_eos():
    model, cfg, params = make_lm()
    ids, mask = prompts()
    forbid = np.ones((64, 64), dtype=bool)
    forbid[:, EOS] = False
    forbid[:, 5] = False  # allow eos and token 5
    fn = jax.jit(
        make_generate_fn(model, cfg, gen_cfg(do_sample=False, min_new_tokens=4), logit_mask=forbid)
    )
    out = fn(params, ids, mask, jax.random.PRNGKey(0))
    toks = np.asarray(out["response_tokens"])
    assert (toks[:, :4] != EOS).all()


def test_ilql_generation_runs():
    model, cfg, params = make_lm(with_ilql_heads=True)
    ids, mask = prompts()
    fn = jax.jit(
        make_generate_fn(model, cfg, gen_cfg(do_sample=True, top_k=20, beta=2.0), mode="ilql")
    )
    out = fn(params, ids, mask, jax.random.PRNGKey(0))
    assert out["response_tokens"].shape == (2, 8)
    # valid ids
    toks = np.asarray(out["response_tokens"])
    assert ((0 <= toks) & (toks < 64)).all()


def test_repetition_penalty_processor_matches_hf():
    """process_logits repetition-penalty math == HF's
    RepetitionPenaltyLogitsProcessor (positive /= p, negative *= p on seen
    tokens)."""
    torch = pytest.importorskip("torch")
    from transformers import RepetitionPenaltyLogitsProcessor

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 64)).astype(np.float32) * 2
    input_ids = np.array([[1, 2, 3], [4, 5, 5]], dtype=np.int64)

    hf_out = (
        RepetitionPenaltyLogitsProcessor(1.7)(
            torch.tensor(input_ids), torch.tensor(logits)
        )
        .numpy()
    )

    seen = np.zeros((2, 64), bool)
    for r in range(2):
        seen[r, input_ids[r]] = True
    ours = process_logits(
        jnp.asarray(logits), gen_cfg(repetition_penalty=1.7), jnp.asarray(0),
        jnp.asarray(seen),
    )
    np.testing.assert_allclose(np.asarray(ours), hf_out, atol=1e-6)


def test_repetition_penalty_discourages_repeats():
    """Greedy decode with a huge penalty never repeats a token; the same
    model without the penalty produces repeats (tiny random model loops)."""
    model, cfg, params = make_lm()
    ids, mask = prompts()

    def run(penalty):
        fn = make_generate_fn(
            model, cfg, gen_cfg(do_sample=False, max_new_tokens=6,
                                repetition_penalty=penalty)
        )
        out = fn(params, ids, mask, jax.random.PRNGKey(0))
        return np.asarray(out["response_tokens"]), np.asarray(out["response_mask"])

    toks_plain, mask_plain = run(1.0)
    toks_pen, mask_pen = run(1e9)
    # with an effectively infinite penalty, generated valid tokens within a
    # row are pairwise distinct and also avoid the prompt tokens
    ids_np, m_np = np.asarray(ids), np.asarray(mask)
    for r in range(toks_pen.shape[0]):
        valid = toks_pen[r][mask_pen[r] > 0]
        assert len(set(valid.tolist())) == len(valid), valid
        prompt_toks = set(ids_np[r][m_np[r] > 0].tolist())
        assert not (set(valid.tolist()) & prompt_toks), (valid, prompt_toks)
    # sanity: the un-penalized greedy run differs (penalty actually engaged)
    assert not np.array_equal(toks_plain, toks_pen)


# ---------------------------------------------------------------------------
# One program whose work follows the chunk's longest prompt (BlockPlan)
# ---------------------------------------------------------------------------

BLOCK = 8


def left_padded(lengths, width, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.full((len(lengths), width), PAD, np.int32)
    mask = np.zeros((len(lengths), width), np.int32)
    for i, n in enumerate(lengths):
        ids[i, width - n:] = rng.integers(0, 60, size=n)
        mask[i, width - n:] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("preset, width, lengths, blocks_run, read, kw", [
    ("gpt2-tiny", 32, [3, 7, 5, 8], 1, 16, {}),               # the longest prompt fills one block of four
    ("gpt2-tiny", 32, [12, 16, 9, 2], 2, 24, {}),             # two
    ("gpt2-tiny", 32, [32, 1, 20, 5], 4, 40, {}),             # all: the whole cache is read
    ("gpt2-tiny", 32, [0, 14, 3, 9], 2, 24, {}),              # a row that is all padding
    ("gpt2-tiny", 32, [10, 4, 6, 2], 2, 24, dict(mode="ilql")),
    ("gpt2-tiny", 32, [17, 4, 6, 2], 3, 32, dict(capture=True)),
    # a width that is no whole number of blocks is left-padded to one inside
    # the program (30 -> 32): outputs keep the caller's columns
    ("gpt2-tiny", 30, [5, 2, 6, 4], 1, 16, dict(capture=True)),
    ("gpt2-tiny", 30, [30, 2, 11, 4], 4, 40, {}),
    ("gpt2-tiny", 32, [9, 4, 6, 2], 2, 24, dict(repetition_penalty=1.3)),
    ("llama-tiny", 32, [13, 4, 16, 2], 2, 24, dict(capture=True)),   # rope, 2 K/V heads under 4
    ("bloom-tiny", 32, [3, 4, 6, 2], 1, 16, {}),              # ALiBi: the bias is cut with the columns
    ("neox-tiny", 32, [20, 4, 6, 2], 3, 32, dict(capture=True)),     # the cells' family
])
def test_block_form_generate_matches_the_one_shot_program(preset, width, lengths, blocks_run, read, kw):
    """`generate` with the prompt prefilled by blocks of 8 from the first
    live one and the loop reading the cache's live suffix, against the
    one-shot program on the same params, prompts and key, in float32 at
    `highest`: the same tokens and masks, and the captured logprobs, values
    and split activations within 1e-5 on every live row."""
    from trlx_tpu.ops.sampling import block_plan, first_live_column

    mc = ModelConfig(model_path=f"random:{preset}", model_extra_configs={"dtype": "float32"})
    mode = kw.pop("mode", "lm")
    capture = kw.pop("capture", False)
    model, cfg, params = build_model(mc, vocab_size=64, with_ilql_heads=mode == "ilql")
    ids, mask = left_padded(lengths, width)
    g = gen_cfg(do_sample=True, temperature=0.9, **kw)
    plan = block_plan(cfg, g, width, BLOCK)
    first = int(first_live_column(np.asarray(mask)))
    assert (plan.blocks - plan.first_block(first), plan.read_columns(first)) == (blocks_run, read)
    assert plan.columns == plan.pad + width + 8 and plan.columns - read <= first + plan.pad

    def run(block):
        fn = jax.jit(make_generate_fn(model, cfg, g, mode=mode, capture=capture, capture_split=1,
                                      prefill_block=block))
        with jax.default_matmul_precision("highest"):
            return jax.device_get(fn(params, ids, mask, jax.random.PRNGKey(4)))

    one, blk = run(0), run(BLOCK)
    live = np.asarray(lengths) > 0  # a row of padding attends to whatever the cache holds
    assert set(one) == set(blk)
    for name in ("samples", "samples_mask", "response_tokens", "response_mask"):
        assert one[name].shape == blk[name].shape
        np.testing.assert_array_equal(one[name][live], blk[name][live], err_msg=name)
    if capture:
        assert one["h_split"].shape == blk["h_split"].shape == (len(lengths), width + 8, cfg.d_model)
        np.testing.assert_allclose(blk["logprobs"][live], one["logprobs"][live], atol=1e-5)
        np.testing.assert_allclose(blk["values"][live], one["values"][live], atol=1e-5)
        held = one["samples_mask"].astype(bool)[:, :-1]  # the last sampled token's row is never written
        np.testing.assert_allclose(blk["h_split"][:, :-1][held], one["h_split"][:, :-1][held], atol=1e-5)
        # the columns of the blocks not run keep their zeros
        skipped = (plan.blocks - blocks_run) * BLOCK - plan.pad
        assert not blk["h_split"][:, :max(skipped, 0)].any()


@pytest.mark.parametrize("preset, width, takes", [
    ("gpt2-tiny", 16, True),        # two blocks
    ("gpt2-tiny", 15, False),       # under two blocks: today's program
    ("lfm2-tiny", 32, False),       # convolution state a row, and the fused prefill
    ("ling-flash-tiny", 32, False),  # a recurrent state a row
])
def test_who_takes_the_block_form(preset, width, takes):
    from trlx_tpu.models import config_from_preset
    from trlx_tpu.ops.sampling import block_plan

    cfg = config_from_preset(preset, 64)
    assert (block_plan(cfg, gen_cfg(), width, BLOCK) is not None) is takes
    assert block_plan(cfg, gen_cfg(), width, 0) is None
    assert block_plan(cfg, gen_cfg(num_beams=2), width, BLOCK) is None
