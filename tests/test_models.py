"""Model-layer tests (counterpart of reference tests/test_models.py):
forward/decode consistency, hydra frozen-branch equivalence, freeze masks,
ILQL heads, Polyak sync, param sharding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelConfig, ParallelConfig
from trlx_tpu.models import (
    CausalLMWithILQLHeads,
    CausalLMWithValueHead,
    build_model,
    forward_policy_and_ref,
    init_kv_cache,
    ref_param_subtree,
    resolve_split,
    sync_target_q_heads,
    target_q_mask,
    trainable_mask,
)
from trlx_tpu.parallel import MeshRuntime, infer_param_shardings


def tiny_model(num_layers_unfrozen=-1, preset="gpt2-tiny", f32=True, **kw):
    extra = {"dtype": "float32"} if f32 else {}
    mc = ModelConfig(
        model_path=f"random:{preset}", num_layers_unfrozen=num_layers_unfrozen,
        model_extra_configs=extra,
    )
    return mc, *build_model(mc, vocab_size=64, **kw)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "llama-tiny"])
def test_forward_shapes(preset):
    _, model, cfg, params = tiny_model(preset=preset)
    tokens = jnp.zeros((2, 8), dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    logits, values, h = jax.jit(model.apply)({"params": params}, tokens, mask)
    assert logits.shape == (2, 8, 64)
    assert values.shape == (2, 8)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "llama-tiny"])
def test_decode_matches_forward(preset):
    """KV-cache decode (prefill + steps) must equal the full forward."""
    _, model, cfg, params = tiny_model(preset=preset)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (2, 10)), dtype=jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1, 1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 1, 1, 1, 1, 1, 1]], jnp.int32)

    cache = init_kv_cache(cfg, 2, 12)
    step = jax.jit(lambda t, c, m, pre: model.apply(
        {"params": params}, t, c, m, is_prefill=pre, method=type(model).decode_step
    ), static_argnums=3)
    lg, _, cache = step(tokens[:, :6], cache, mask[:, :6], True)
    outs = [lg[:, -1]]
    for i in range(6, 10):
        lg, _, cache = step(tokens[:, i : i + 1], cache, mask[:, i : i + 1], False)
        outs.append(lg[:, 0])
    stepwise = jnp.stack(outs, 1)
    full, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    np.testing.assert_allclose(np.asarray(stepwise), np.asarray(full[:, 5:10]), atol=2e-4)


@pytest.mark.parametrize("nlu", [-1, 0, 2])
def test_hydra_equivalence_at_init(nlu):
    """Before any training, the frozen reference branch must produce exactly
    the policy logits (reference tests/test_models.py:109-128)."""
    _, model, cfg, params = tiny_model(num_layers_unfrozen=nlu)
    split = resolve_split(cfg, nlu)
    ref = ref_param_subtree(params, cfg, split)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6]], dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    logits, values, ref_logits, _ = forward_policy_and_ref(model, params, ref, tokens, mask, split)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)


def test_hydra_diverges_after_update():
    """Mutating trainable params changes policy logits but not ref logits."""
    _, model, cfg, params = tiny_model(num_layers_unfrozen=1)
    split = resolve_split(cfg, 1)
    ref = ref_param_subtree(params, cfg, split)
    tokens = jnp.asarray([[1, 2, 3, 4]], dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    _, _, ref_logits0, _ = forward_policy_and_ref(model, params, ref, tokens, mask, split)

    mutated = jax.tree_util.tree_map(lambda x: x, params)
    tm = trainable_mask(params, cfg, 1)
    mutated = jax.tree_util.tree_map(
        lambda p, m: p + 0.01 if m else p, mutated, tm
    )
    logits1, _, ref_logits1, _ = forward_policy_and_ref(model, mutated, ref, tokens, mask, split)
    np.testing.assert_allclose(np.asarray(ref_logits0), np.asarray(ref_logits1), atol=1e-5)
    assert float(jnp.abs(logits1 - ref_logits1).max()) > 1e-3


def test_trainable_mask_semantics():
    _, model, cfg, params = tiny_model()

    def lm_trainable(nlu):
        tm = trainable_mask(params, cfg, nlu)
        flat = jax.tree_util.tree_flatten_with_path(tm)[0]
        return sorted(
            {
                str(kp[1].key)
                for kp, v in flat
                if str(kp[0].key) == "lm" and v
            }
        )

    assert "embed_tokens" in lm_trainable(-1)
    assert lm_trainable(0) == []
    assert lm_trainable(1) == ["block_1", "ln_f"]
    # heads always trainable
    tm0 = trainable_mask(params, cfg, 0)
    assert all(jax.tree_util.tree_leaves(tm0["v_head"]))


def test_ilql_heads_and_polyak_sync():
    mc = ModelConfig(model_path="random:gpt2-tiny", model_extra_configs={"dtype": "float32"})
    model, cfg, params = build_model(mc, vocab_size=64, with_ilql_heads=True)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6]], dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    actions_ixs = jnp.asarray([[0, 2, 4]])
    states_ixs = jnp.asarray([[0, 2, 4, 5]])
    logits, qs, tqs, vs, _ = model.apply(
        {"params": params}, tokens, mask, states_ixs=states_ixs, actions_ixs=actions_ixs
    )
    assert len(qs) == 2 and qs[0].shape == (1, 3, 64)
    assert vs.shape == (1, 4, 1)

    # Polyak sync: alpha=1 copies q -> target exactly
    heads = params["ilql_heads"]
    synced = sync_target_q_heads(heads, alpha=1.0)
    for i in range(2):
        q = jax.tree_util.tree_leaves(synced[f"q_head_{i}"])
        t = jax.tree_util.tree_leaves(synced[f"target_q_head_{i}"])
        for a, b in zip(q, t):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # alpha=0.5 moves halfway
    half = sync_target_q_heads(heads, alpha=0.5)
    q0 = heads["q_head_0"]["dense_in"]["kernel"]
    t0 = heads["target_q_head_0"]["dense_in"]["kernel"]
    np.testing.assert_allclose(
        np.asarray(half["target_q_head_0"]["dense_in"]["kernel"]),
        0.5 * np.asarray(q0) + 0.5 * np.asarray(t0),
        rtol=1e-6,
    )
    # target-q mask excludes exactly the target heads
    tqm = target_q_mask(params)
    assert all(jax.tree_util.tree_leaves(tqm["ilql_heads"]["target_q_head_0"]))
    assert not any(jax.tree_util.tree_leaves(tqm["ilql_heads"]["q_head_0"]))
    assert not any(jax.tree_util.tree_leaves(tqm["lm"]))


def test_sharded_forward_on_mesh():
    """Params placed by the rule table + batch-sharded forward on a 2x2x2
    virtual mesh must match the single-device forward."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    _, model, cfg, params = tiny_model()
    runtime = MeshRuntime.from_config(ParallelConfig(data=2, fsdp=2, tensor=2))
    shardings = infer_param_shardings(runtime.mesh, params)
    sharded = jax.tree_util.tree_map(jax.device_put, params, shardings)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (8, 8)), dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    logits_single, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    f = jax.jit(lambda p, t, m: model.apply({"params": p}, t, m)[0])
    logits_sharded = f(sharded, runtime.shard_batch(tokens), runtime.shard_batch(mask))
    np.testing.assert_allclose(np.asarray(logits_sharded), np.asarray(logits_single), atol=2e-4)


def test_value_branch_model():
    """num_value_layers_unfrozen > 0: deeper value branch (reference
    make_value_branch, modeling_ppo.py:255-263) — branch weights start as
    clones of the top trunk blocks, logits are unaffected by the branch,
    and gradients flow into branch params."""
    mc, model, cfg, params = tiny_model(num_value_layers=1)
    # clone invariant: branch block 0 == top trunk block, branch ln == ln_f
    top = params["lm"][f"block_{cfg.n_layers - 1}"]
    flat_b = dict(jax.tree_util.tree_leaves_with_path(params["value_branch"]["block_0"]))
    flat_t = dict(jax.tree_util.tree_leaves_with_path(top))
    for k in flat_t:
        np.testing.assert_array_equal(np.asarray(flat_b[k]), np.asarray(flat_t[k]))

    tokens = jnp.asarray(np.arange(32).reshape(2, 16) % 64, jnp.int32)
    mask = jnp.ones_like(tokens)
    logits, values, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    assert values.shape == tokens.shape

    # logits identical to the plain value-head model on the same lm params
    _, m0, _, p0 = tiny_model()
    logits0, _, _ = jax.jit(m0.apply)({"params": {**p0, "lm": params["lm"]}}, tokens, mask)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits0), atol=1e-5)

    # value gradients reach the branch
    g = jax.jit(jax.grad(lambda p: jnp.sum(model.apply({"params": p}, tokens, mask)[1] ** 2)))(params)
    gn = sum(float(np.abs(np.asarray(x)).sum())
             for x in jax.tree_util.tree_leaves(g["value_branch"]))
    assert gn > 0

    # hydra composition still works
    split = resolve_split(cfg, 1)
    ref = ref_param_subtree(params, cfg, split)
    lg, vals, rlg, _ = forward_policy_and_ref(model, params, ref, tokens, mask, split)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(rlg), atol=1e-5)

    # trainable mask: whole branch trains
    tm = trainable_mask(params, cfg, 1)
    assert all(jax.tree_util.tree_leaves(tm["value_branch"]))


def test_value_branch_rejected_for_ilql_and_seq2seq():
    with pytest.raises(NotImplementedError):
        tiny_model(num_value_layers=1, with_ilql_heads=True)
    mc = ModelConfig(model_path="random:t5-tiny", model_arch_type="seq2seq",
                     num_layers_unfrozen=-1)
    with pytest.raises(NotImplementedError):
        build_model(mc, vocab_size=64, num_value_layers=1)
