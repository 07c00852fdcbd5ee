"""Frozen-trunk activation cache (method.cache_trunk_activations): the
hydra trunk below the split is entirely frozen, so its output for a
chunk's tokens is invariant across all PPO inner epochs — capture it once
and train the suffix from it.

Exactness contract pinned here:
- f32 cache, eager evaluation: the cached-suffix loss AND gradients are
  BITWISE equal to the full-forward loss path (the resumed suffix runs
  the identical op sequence; padded cache rows are attention-masked and
  exp(-1e9) underflows to exactly 0.0, so zero-filled collation padding
  contributes nothing).
- bf16 cache: one rounding of h_split (~8e-3 relative per value) through
  the suffix; loss agrees to ~1e-4 relative at this scale, pinned with
  an order of magnitude of headroom.
- The end-to-end jitted path (store -> collate -> scan) is additionally
  subject to XLA fusion drift between the jitted trunk pass and the
  in-loss trunk, so e2e checks are finite/parity, not bitwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.models import CausalLMWithValueHead
from trlx_tpu.models.transformer import position_ids
from trlx_tpu.ops.ppo import get_advantages_and_returns
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.ppo_trainer import PPOTrainer

MAX_NEW = 6
SUPPRESS = [i for i in range(259) if not (32 <= i < 127 or i == 258)]


def _make_trainer(tmp_path, **method):
    method = {
        "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 2,
        "cache_trunk_activations": True, "trunk_cache_dtype": "float32",
        "gen_kwargs": dict(max_new_tokens=MAX_NEW, do_sample=True,
                           suppress_tokens=SUPPRESS),
        **method,
    }
    config = default_ppo_config().evolve(
        # float32 end to end so the f32-cache test can assert BITWISE
        # equality (bf16 rounding would mask the exactness claim)
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=4, tracker=None,
                   checkpoint_dir=str(tmp_path), seed=11),
        method=dict(**method),
    )
    trainer = PPOTrainer(
        config,
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
    )
    pipeline = PromptPipeline(["hello world", "jax tpu", "ppo", "fast"] * 2,
                              max_prompt_length=8, tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    """Shared trainer (classic sampler, cache gate on, f32 cache) with one
    collected store — the loss-level tests all read the same batch."""
    tr = _make_trainer(tmp_path_factory.mktemp("trunk_cache"))
    tr.make_experience(8)
    return tr


@pytest.fixture(scope="module")
def chunk(trainer):
    """One collated device batch from the store (h_split attached by the
    loader's trunk-cache collation)."""
    batch = next(iter(trainer.create_train_dataloader()))
    assert batch.h_split is not None
    assert batch.h_split.shape[:2] == (
        batch.query_tensors.shape[0],
        batch.query_tensors.shape[1] + batch.response_tensors.shape[1],
    )
    return jax.tree_util.tree_map(jnp.asarray, batch)


def _eager_trunk(trainer, chunk):
    """h_split recomputed EAGERLY with the exact op sequence the full
    forward runs — the bitwise-equality reference (the store's cache went
    through a jitted pass, which XLA may fuse differently)."""
    params = merge_params(trainer.train_params, trainer.frozen_params)
    pad = trainer.tokenizer.pad_token_id
    tokens = jnp.concatenate([chunk.query_tensors, chunk.response_tensors], axis=1)
    amask = (tokens != pad).astype(jnp.int32)
    return trainer.model.apply(
        {"params": params}, tokens, amask, position_ids(amask), stop=trainer.split,
        method=CausalLMWithValueHead.forward,
    )[1]


def _grads(trainer, loss_fn, batch):
    return jax.grad(
        lambda p: loss_fn(p, trainer.frozen_params, batch)[0]
    )(trainer.train_params)


def test_f32_cache_loss_and_grads_exact(trainer, chunk):
    """f32 cache: cached-suffix loss and EVERY gradient leaf bitwise equal
    to the full-forward path (eager evaluation on both sides)."""
    loss_fn = trainer.make_loss_fn()
    h = _eager_trunk(trainer, chunk)
    cached = chunk.replace(h_split=h)
    full = chunk.replace(h_split=None)
    l_c, _ = loss_fn(trainer.train_params, trainer.frozen_params, cached)
    l_f, _ = loss_fn(trainer.train_params, trainer.frozen_params, full)
    np.testing.assert_array_equal(np.asarray(l_c), np.asarray(l_f))
    g_c = _grads(trainer, loss_fn, cached)
    g_f = _grads(trainer, loss_fn, full)
    for a, b in zip(jax.tree_util.tree_leaves(g_c), jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_cache_within_tolerance(trainer, chunk):
    """bf16 cache: one rounding of h_split through the suffix. Measured
    loss deviation ~1e-4 relative at this scale; pinned at 2e-3 (10x
    headroom). Gradients within a loose atol relative to their scale."""
    loss_fn = trainer.make_loss_fn()
    h = _eager_trunk(trainer, chunk).astype(jnp.bfloat16)
    cached = chunk.replace(h_split=h)
    full = chunk.replace(h_split=None)
    l_c, _ = loss_fn(trainer.train_params, trainer.frozen_params, cached)
    l_f, _ = loss_fn(trainer.train_params, trainer.frozen_params, full)
    np.testing.assert_allclose(float(l_c), float(l_f), rtol=2e-3)
    g_c = _grads(trainer, loss_fn, cached)
    g_f = _grads(trainer, loss_fn, full)
    for a, b in zip(jax.tree_util.tree_leaves(g_c), jax.tree_util.tree_leaves(g_f)):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(float(np.abs(b).max()), 1e-3)
        np.testing.assert_allclose(a, b, atol=5e-2 * scale)


def test_flag_off_bit_identity(trainer, chunk):
    """cache_trunk_activations off -> the loss graph is unchanged: the
    flag never enters loss_fn (only whether h_split rides on the batch
    does), so the flag-off loss on the same data is bitwise identical."""
    full = chunk.replace(h_split=None)
    loss_on, _ = trainer.make_loss_fn()(
        trainer.train_params, trainer.frozen_params, full
    )
    on_config = trainer.config
    try:
        trainer.config = trainer.config.evolve(
            method=dict(cache_trunk_activations=False)
        )
        assert not trainer._trunk_cache_available()
        loss_off, _ = trainer.make_loss_fn()(
            trainer.train_params, trainer.frozen_params, full
        )
    finally:
        trainer.config = on_config
    np.testing.assert_array_equal(np.asarray(loss_on), np.asarray(loss_off))


def test_gate_refusals(trainer):
    """Gate mirrors _fast_rollout_available's geometry: refuses MoE,
    split == 0, a value branch below the split, seq2seq, and flag off."""
    assert trainer._trunk_cache_available()
    on_config = trainer.config
    model_cfg = trainer.model_cfg
    try:
        trainer.config = on_config.evolve(
            method=dict(cache_trunk_activations=False)
        )
        assert not trainer._trunk_cache_available()
        trainer.config = on_config

        # MoE: routing recomputes the aux loss from the full forward
        trainer.model_cfg = dataclasses.replace(model_cfg, moe_experts=2)
        assert not trainer._trunk_cache_available()
        trainer.model_cfg = model_cfg

        # split 0 (e.g. num_layers_unfrozen=-1 / LoRA): nothing is frozen
        split = trainer.split
        trainer.split = 0
        assert not trainer._trunk_cache_available()
        trainer.split = split

        # value branch tapping BELOW the split (n_layers=2, split=1,
        # 2 value layers -> tap at layer 0 < split): h_split can't feed it
        trainer.config = on_config.evolve(
            method=dict(num_value_layers_unfrozen=2)
        )
        assert not trainer._trunk_cache_available()
        trainer.config = on_config

        trainer.seq2seq = True
        assert not trainer._trunk_cache_available()
        trainer.seq2seq = False
    finally:
        trainer.config = on_config
        trainer.model_cfg = model_cfg
        trainer.seq2seq = False
    assert trainer._trunk_cache_available()


def test_whiten_with_mask_both_behaviors(trainer, chunk):
    """Satellite: method.whiten_with_mask. Default OFF keeps the
    reference's unmasked whitening (advantage mean ~0 over ALL positions
    including padding); ON whitens over real response tokens only
    (mean ~0 over the mask). Both pinned at the GAE level and the loss
    level (toggling the flag changes the loss on a padded batch)."""
    method = trainer.config.method
    pad = trainer.tokenizer.pad_token_id
    # sampling with suppress_tokens tends to fill every response to
    # max_new_tokens, so synthesize ragged rows: truncate half the batch
    # two tokens early (pad_id -> mask 0 inside loss_fn too)
    resp = np.asarray(chunk.response_tensors).copy()
    resp[: resp.shape[0] // 2, -2:] = pad
    chunk = chunk.replace(response_tensors=jnp.asarray(resp))
    mask = (chunk.response_tensors != pad).astype(jnp.float32)
    assert float(mask.sum()) < mask.size

    adv_u, _ = get_advantages_and_returns(
        chunk.values, chunk.rewards, method.gamma, method.lam
    )
    adv_m, _ = get_advantages_and_returns(
        chunk.values, chunk.rewards, method.gamma, method.lam, mask=mask
    )
    assert abs(float(adv_u.mean())) < 1e-5
    masked_mean = float((adv_m * mask).sum() / mask.sum())
    assert abs(masked_mean) < 1e-5
    assert not np.allclose(np.asarray(adv_u), np.asarray(adv_m))

    full = chunk.replace(h_split=None)
    loss_off, _ = trainer.make_loss_fn()(
        trainer.train_params, trainer.frozen_params, full
    )
    on_config = trainer.config
    try:
        trainer.config = on_config.evolve(method=dict(whiten_with_mask=True))
        loss_on, _ = trainer.make_loss_fn()(
            trainer.train_params, trainer.frozen_params, full
        )
    finally:
        trainer.config = on_config
    assert float(loss_on) != float(loss_off)


def test_store_path_trains_from_cache(trainer):
    """Classic store path end to end: make_experience attached h_split to
    every element, the loader collated it, and the fused scan train path
    consumes the extended batch (finite loss, params move)."""
    assert all(e.h_split is not None for e in trainer.store.history)
    batch = next(iter(trainer.create_train_dataloader()))
    chunk = jax.tree_util.tree_map(jnp.asarray, batch)
    p0 = jax.device_get(next(iter(trainer.train_params.values())))
    stats = trainer.train_epochs_from_chunk(chunk, 2)
    loss = float(np.asarray(stats["losses"]["total_loss"]))
    assert np.isfinite(loss)
    p1 = jax.device_get(next(iter(trainer.train_params.values())))
    assert not np.allclose(p0, p1)


def test_pipelined_cycle_with_capture_reuses_h_split(tmp_path_factory):
    """2-cycle end-to-end PPO with the cache on + the rollout fast path:
    the sampler's captured h_split is handed to the trunk cache (the cast
    fn compiles; the trunk recompute fn never does), losses are finite,
    and training moves the params."""
    tr = _make_trainer(tmp_path_factory.mktemp("tc_fast"),
                       capture_rollout_stats=True)
    assert tr._fast_rollout_available() and tr._trunk_cache_available()
    p0 = jax.device_get(next(iter(tr.train_params.values())))
    loss0, pending = tr.pipelined_cycle()
    assert loss0 is None
    loss1, pending = tr.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert np.isfinite(float(np.asarray(pending[2][0])))
    # zero extra forwards: the captured activations fed the cache
    assert tr._cache_cast_fn is not None
    assert tr._trunk_cache_fn is None
    assert getattr(tr, "spec_fallbacks", 0) == 0
    p1 = jax.device_get(next(iter(tr.train_params.values())))
    assert not np.allclose(p0, p1)


def test_pipelined_cycle_classic_computes_trunk(tmp_path_factory):
    """2-cycle end-to-end with the cache on but NO capture: the cycle
    fills the cache with the jitted trunk pass instead."""
    tr = _make_trainer(tmp_path_factory.mktemp("tc_classic"))
    assert not tr._fast_rollout_available() and tr._trunk_cache_available()
    loss0, pending = tr.pipelined_cycle()
    assert loss0 is None
    loss1, pending = tr.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert tr._trunk_cache_fn is not None
