"""The two bodies every forward goes through: `TransformerLM.forward` (no
cache) and `TransformerLM.decode_step` (cached), and the policy classes'
versions of them. What is held here is the seam itself: any range of blocks
composes to the whole, a window is a slice, the per-row cache computes what
the scalar one does, and the refusals sit where they are said to sit.
Float32 and eager, so "equal" means bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models import (
    CausalLMPolicy,
    CausalLMWithValueHead,
    config_from_preset,
    init_kv_cache,
)
from trlx_tpu.models.transformer import TransformerLM

VOCAB, B, T = 97, 3, 12
PRESETS = {
    "dense": dict(name="gpt2-tiny", n_layers=3),
    # conv, conv, attention, conv, conv, conv; 2 dense ffns, then SparseMoE
    "lfm2": dict(name="lfm2-tiny"),
    # the same experts over attention layers only: a per-row cache can hold it
    "lfm2-attn": dict(name="lfm2-tiny", n_layers=3, layer_types=("attention",) * 3),
}
N_LAYERS = {"dense": 3, "lfm2": 6, "lfm2-attn": 3}


def _cfg(kind, **over):
    spec = dict(PRESETS[kind])
    return config_from_preset(spec.pop("name"), VOCAB, dtype=jnp.float32, **spec, **over)


@pytest.fixture(scope="module")
def lms():
    """kind -> (cfg, TransformerLM, params, tokens, mask); rows are left-padded
    by 0, 2 and 5 so that positions differ from column indices."""
    out = {}
    for i, kind in enumerate(PRESETS):
        cfg = _cfg(kind)
        model = TransformerLM(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(i), (B, T), 1, VOCAB)
        mask = (jnp.arange(T)[None, :] >= jnp.asarray([0, 2, 5])[:, None]).astype(jnp.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(10 + i), tokens, mask)["params"]
        out[kind] = (cfg, model, params, tokens, mask)
    return out


def _forward(model, params, *args, **kwargs):
    return model.apply({"params": params}, *args, method=type(model).forward, **kwargs)


def _step(model, params, *args, **kwargs):
    return model.apply({"params": params}, *args, method=type(model).decode_step, **kwargs)


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


SPLITS = [(kind, s) for kind in ("dense", "lfm2") for s in range(N_LAYERS[kind] + 1)]


@pytest.mark.parametrize("with_capture", [False, True], ids=["plain", "capture"])
@pytest.mark.parametrize("kind,split", SPLITS, ids=[f"{k}-{s}" for k, s in SPLITS])
def test_blocks_up_to_a_split_then_from_it_are_the_whole_forward(lms, kind, split, with_capture):
    cfg, model, params, tokens, mask = lms[kind]
    n = cfg.n_layers
    logits, h_final, _ = _forward(model, params, tokens, mask)
    # captures inside each half: the layer before the split and the one after
    lo_cap = (max(split - 1, 0),) if with_capture else ()
    hi_cap = (min(split + 1, n),) if with_capture else ()
    none, h, caps = _forward(model, params, tokens, mask, stop=split, capture=lo_cap)
    assert none is None  # `stop` given, n_layers too: no head
    if split == 0:
        # no block has run: h is the embedding's output, and `start=0` means token ids
        _same(h, _forward(model, params, tokens, mask, capture=(0,))[2][0])
        h = tokens
    got, got_final, hi = _forward(model, params, h, mask, start=split, capture=hi_cap)
    caps = {**caps, **hi}
    _same(got, logits)
    _same(got_final, h_final)
    if with_capture:
        whole = _forward(model, params, tokens, mask, capture=tuple(sorted({*lo_cap, *hi_cap})))[2]
        assert sorted(caps) == sorted(whole)
        for layer in whole:
            _same(caps[layer], whole[layer])
        # and `__call__` is the thin default over it
        _, h_split, _ = model.apply({"params": params}, tokens, mask, None, lo_cap[0])
        _same(h_split, whole[lo_cap[0]])


@pytest.mark.parametrize("window", [(0, T), (4, 5), (T - 1, 1)], ids=str)
@pytest.mark.parametrize("kind", ["dense", "lfm2"])
def test_window_is_the_slice_of_the_full_width_head(lms, kind, window):
    cfg, model, params, tokens, mask = lms[kind]
    logits, h_final, _ = _forward(model, params, tokens, mask)
    first, length = window
    got, got_final, _ = _forward(model, params, tokens, mask, window=window)
    _same(got, logits[:, first:first + length])
    _same(got_final, h_final[:, first:first + length])
    # from a hidden state too (the trunk-cache train path, the reference suffix)
    _, h, _ = _forward(model, params, tokens, mask, stop=1)
    got, _, _ = _forward(model, params, h, mask, start=1, window=window)
    _same(got, logits[:, first:first + length])


def test_capture_outside_the_blocks_run_is_refused(lms):
    _, model, params, tokens, mask = lms["dense"]
    with pytest.raises(ValueError, match="capture"):
        _forward(model, params, tokens, mask, stop=1, capture=(2,))


def _row_cache(cache):
    """The scalar cache as a per-row one: every row at the shared offset."""
    b = cache["mask"].shape[0]
    return {"row_index": jnp.full((b,), cache["index"], jnp.int32),
            **{k: cache[k] for k in ("mask", "pos", "layers")}}


def _same_cache(rows, scalar):
    _same(rows["mask"], scalar["mask"])
    _same(rows["pos"], scalar["pos"])
    _same(rows["row_index"], jnp.full_like(rows["row_index"], scalar["index"]))
    for got, want in zip(rows["layers"], scalar["layers"]):
        for name in want:
            _same(got[name], want[name])


@pytest.mark.parametrize("t", [1, 4], ids=["t1", "t4"])
@pytest.mark.parametrize("kind", ["dense", "lfm2-attn"])
def test_per_row_step_is_the_scalar_step_on_an_aligned_batch(lms, kind, t):
    """t == 1: a decode step behind a prefilled prompt. t > 1: a prefill of
    every row at its own offset (0 for all: an aligned batch has no pads)."""
    cfg, model, params, tokens, _ = lms[kind]
    ones = jnp.ones((B, T), jnp.int32)
    empty = init_kv_cache(cfg, B, T + 4)
    if t == 1:
        _, _, cache = _step(model, params, tokens, empty, ones, True)
        x, m, prefill = tokens[:, :1], ones[:, :1], False
    else:
        cache, x, m, prefill = empty, tokens[:, :t], ones[:, :t], True
    logits, h_final, new_cache = _step(model, params, x, cache, m, prefill)
    got, got_final, got_cache = _step(model, params, x, _row_cache(cache), m)
    _same(got, logits)
    _same(got_final, h_final)
    _same_cache(got_cache, new_cache)


@pytest.mark.parametrize("t", [1, 4], ids=["decode", "prefill"])
def test_conv_state_goes_through_the_per_row_branch(lms, t):
    """A per-row cache carries a `conv` layer's state as the scalar one does
    (the engine's pool holds it a slot)."""
    cfg, model, params, tokens, _ = lms["lfm2"]
    ones = jnp.ones((B, t), jnp.int32)
    scalar = init_kv_cache(cfg, B, T)
    x = tokens[:, :t]
    want = _step(model, params, x, scalar, ones, t > 1)
    got = _step(model, params, x, _row_cache(scalar), ones)
    _same(got[0], want[0])
    _same_cache(got[2], want[2])
    assert sorted(want[2]["layers"][0]) == ["conv"]


def test_policy_rules_sit_once_each(lms):
    cfg, _, _, tokens, mask = lms["dense"]
    branch = CausalLMWithValueHead(cfg, num_value_layers=1)
    params = jax.jit(branch.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    with pytest.raises(NotImplementedError, match="value branch"):
        _forward(branch, params, tokens, mask, window=(2, 3))
    cache = init_kv_cache(cfg, B, T)
    with pytest.raises(NotImplementedError, match="value branch"):
        _step(branch, params, tokens, cache, mask, True, with_value=True)
    logits, values, caps = _forward(branch, params, tokens, mask)
    want = branch.apply({"params": params}, tokens, mask)
    _same(logits, want[0])
    _same(values, want[1])
    # from a cached trunk activation below the branch's tap: the same values
    _, h, _ = _forward(branch, params, tokens, mask, stop=1)
    again = _forward(branch, params, h, mask, start=1)
    _same(again[1], values)

    critic_free = CausalLMPolicy(cfg)
    params = jax.jit(critic_free.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    assert "v_head" not in params
    assert _forward(critic_free, params, tokens, mask, window=(2, 3))[1] is None
    assert _step(critic_free, params, tokens, cache, mask, True)[1] is None
    with pytest.raises(NotImplementedError, match="no value head"):
        _step(critic_free, params, tokens, cache, mask, True, with_value=True)


@pytest.mark.parametrize("kwargs", [dict(window=(2, 3)), dict(stop=1)], ids=["window", "stop"])
def test_prompt_tuning_refuses_what_the_soft_prompt_would_shift(kwargs):
    cfg = _cfg("dense", prompt_tokens=2)
    model = TransformerLM(cfg)
    tokens, mask = jnp.ones((B, T), jnp.int32), jnp.ones((B, T), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    with pytest.raises(NotImplementedError, match="prompt tuning"):
        _forward(model, params, tokens, mask, **kwargs)
    assert _forward(model, params, tokens, mask)[0].shape == (B, T, VOCAB)
