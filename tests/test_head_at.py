"""`decode_step(..., head_at=i)`: the final norm and the head over the one new
position a row reads (an admission reads its prompt's last token), against the
full call's rows at `i`; and the engine's two prefill programs, which ask for
that position and so hold no `[rows, width, vocabulary]` logits any more.

A CPU run: values at test size, and counts of shapes in lowered programs."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from parity import jitted_init  # noqa: E402
from trlx_tpu.inference import InferenceEngine  # noqa: E402
from trlx_tpu.models import (  # noqa: E402
    CausalLMPolicy, CausalLMWithILQLHeads, CausalLMWithValueHead, config_from_preset)
from trlx_tpu.models.transformer import TransformerLM, init_kv_cache, prefill_fuses  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig  # noqa: E402

VOCAB, ROWS, WIDTH, CACHE = 97, 3, 24, 40
# (preset, module, what the step is asked beside the LM's arguments)
CASES = {
    # the dense pool's prefill: one shared write offset, left padding, every row's last column
    "index_left": ("gpt2-tiny", TransformerLM, {}),
    # the paged insert's: a write offset a row, right padding, ragged lengths
    "row_index_right_ragged": ("gpt2-tiny", TransformerLM, {}),
    # a Mamba-2 mixer beside attention: a recurrent state and convolution tails a row
    "slot_state_left": ("falcon-h1-tiny", TransformerLM, {}),
    "slot_state_right_ragged": ("falcon-h1-tiny", TransformerLM, {}),
    # the wrappers pass the argument through and read their heads at the same position
    "value_head": ("gpt2-tiny", CausalLMWithValueHead, {"with_value": True}),
    "ilql_heads": ("gpt2-tiny", CausalLMWithILQLHeads, {}),
}
LENS = (24, 7, 15)


def padded(lens, width, right: bool):
    rng = np.random.default_rng(5)
    tokens = rng.integers(1, VOCAB, size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[1] * n + [0] * (width - n) if right else [0] * (width - n) + [1] * n for n in lens], np.int32)
    return jnp.asarray(tokens * mask), jnp.asarray(mask)


@pytest.mark.parametrize("case", list(CASES))
def test_head_at_returns_the_full_steps_rows_at_that_position(case):
    preset, module, asked = CASES[case]
    right = "right" in case
    cfg = config_from_preset(preset, VOCAB, dtype=jnp.float32)
    model = module(cfg)
    tokens, mask = padded(LENS, WIDTH, right)
    params = jitted_init(model)(jax.random.PRNGKey(3), tokens, mask)["params"]
    cache = init_kv_cache(cfg, ROWS, CACHE)
    if right:  # a per-row cache, every row from column 0 (`engine._get_paged_insert` behind no prefix)
        cache = {"row_index": jnp.zeros((ROWS,), jnp.int32), **{k: cache[k] for k in ("mask", "pos", "layers")}}
    # the rows' last live columns under right padding; under left padding the
    # last column is every row's, and any other column reads the same way
    head_at = jnp.asarray(LENS, jnp.int32) - 1 if right else jnp.asarray([WIDTH - 1, 2, WIDTH - 1], jnp.int32)

    @jax.jit
    def step(params, head_at=None):
        extra = {} if head_at is None else {"head_at": head_at}
        return model.apply({"params": params}, tokens, cache, mask, not right,
                           method=type(model).decode_step, **asked, **extra)

    full, one = step(params), step(params, head_at)
    rows = np.arange(ROWS)
    checked = 0
    for i, (whole, got) in enumerate(zip(full[:-1], one[:-1])):
        for whole_leaf, got_leaf in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(got)):
            assert got_leaf.shape == (ROWS, 1, *whole_leaf.shape[2:]), (i, got_leaf.shape)
            np.testing.assert_allclose(np.asarray(got_leaf)[:, 0], np.asarray(whole_leaf)[rows, np.asarray(head_at)],
                                       rtol=2e-5, atol=2e-6)
            checked += 1
    # logits, h_final (the LM) | logits, values | logits, two Qs, two target Qs, V
    assert checked == {TransformerLM: 2, CausalLMWithValueHead: 2, CausalLMWithILQLHeads: 6}[module]
    assert np.abs(np.asarray(one[0])).max() > 0
    # the cache is moved on alike: what a row reads of the head changes nothing it writes
    for whole_leaf, got_leaf in zip(jax.tree_util.tree_leaves(full[-1]), jax.tree_util.tree_leaves(one[-1])):
        np.testing.assert_array_equal(np.asarray(whole_leaf), np.asarray(got_leaf))


def tensors(text: str, *last_two) -> int:
    """The values of a lowered program whose last two dimensions are these."""
    return len(re.findall(r"tensor<(?:\d+x)*%dx%dx[a-z]" % last_two, text))


@pytest.mark.parametrize("program", ["paged_insert", "paged_insert_fresh", "prefill"])
def test_a_prefill_program_holds_one_position_of_logits_a_row(program):
    """`engine.paged_insert[b2,p16]`, its `fresh` twin and the dense pool's
    `engine.prefill[b2,p16]`: no `[.., width, vocabulary]` array is left in
    the lowered text, and a `[.., 1, vocabulary]` one is there."""
    rows, width = 2, 16
    paged = program != "prefill"
    fresh = program == "paged_insert_fresh"  # the prompt attends within itself through the fused path
    cfg = config_from_preset("gpt2-tiny", VOCAB, dtype=jnp.float32,
                             **(dict(flash_prefill=True, attn_impl="flash") if fresh else {}))
    assert prefill_fuses(cfg, width) == fresh
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=VOCAB + 1, pad_token_id=0)
    engine = InferenceEngine(model, cfg, None, gen_cfg, num_slots=4, max_prompt_len=32, prompt_bucket=16,
                             kv_paging=paged, kv_block_size=8)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    if paged:
        n_tbl = engine._pool["table"].shape[1]
        jitted = engine._get_paged_insert(rows, width, fresh)
        args = (engine._pool, params, ints(rows, width), ints(rows, width), ints(rows, n_tbl),
                ints(rows), ints(rows), ints(rows))
    else:
        jitted = engine._get_prefill(rows, width)
        args = (params, ints(rows, width), ints(rows, width))
    text = jitted.trace(*args).lower().as_text()
    assert tensors(text, width, VOCAB) == 0
    assert tensors(text, 1, VOCAB) > 0
    # the count reads what it should: the blocks' own arrays are `width` wide
    assert tensors(text, width, cfg.d_model) > 0
