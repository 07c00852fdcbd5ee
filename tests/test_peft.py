"""LoRA/peft parity tests, mirroring the reference's tests/test_peft.py
invariants: adapter-only training, adapter-disabled (reference) forward
equivalence, merge-and-unload export, checkpoint shape, and the full PPO
path with a peft_config.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from flax import traverse_util  # noqa: E402

from trlx_tpu.data.configs import ModelConfig  # noqa: E402
from trlx_tpu.data.default_configs import default_ppo_config  # noqa: E402
from trlx_tpu.models import (  # noqa: E402
    CausalLMWithValueHead,
    build_model,
    config_from_preset,
    forward_policy_and_ref,
    ref_param_subtree,
    resolve_split,
    trainable_mask,
)
from trlx_tpu.models.lora import (  # noqa: E402
    lora_overrides_from_peft_config,
    merge_lora_into_params,
    split_lora,
    zero_lora,
)

PEFT_CONFIG = {"peft_type": "LORA", "r": 4, "lora_alpha": 16}


def _build(lora=True):
    overrides = lora_overrides_from_peft_config(PEFT_CONFIG) if lora else {}
    cfg = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32, **overrides)
    model = CausalLMWithValueHead(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 12)), jnp.int32)
    mask = jnp.ones_like(tokens)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    return cfg, model, params, tokens, mask


def _perturb_lora(params, scale=0.3):
    """Give the adapters nonzero weights (as training would)."""

    def bump(path, x):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if "_lora_" in name:
            import zlib

            key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(name.encode()))
            return x + scale * jax.random.normal(key, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(bump, params)


def test_overrides_translation():
    ov = lora_overrides_from_peft_config(PEFT_CONFIG)
    assert ov == {"lora_rank": 4, "lora_alpha": 16.0}
    ov = lora_overrides_from_peft_config(
        {"peft_type": "LORA", "r": 2, "target_modules": ["q_proj", "o_proj"]}
    )
    assert ov["lora_targets"] == ("q_proj", "o_proj")
    assert lora_overrides_from_peft_config(
        {"peft_type": "PREFIX_TUNING", "num_virtual_tokens": 6}
    ) == {"prefix_tokens": 6}
    # user-supplied attn_impl must not collide with the override dict
    mc = ModelConfig(model_path="random:gpt2-tiny",
                     model_extra_configs={"attn_impl": "xla"},
                     peft_config={"peft_type": "PREFIX_TUNING", "num_virtual_tokens": 2})
    _, cfg, _ = build_model(mc, vocab_size=64)
    assert cfg.prefix_tokens == 2
    with pytest.raises(ValueError):
        lora_overrides_from_peft_config({"peft_type": "IA3"})


def test_adapter_params_exist_and_only_adapters_train():
    cfg, model, params, *_ = _build()
    lora_leaves, base_leaves = split_lora(params)
    # default targets q_proj+v_proj, 2 layers, a+b each
    assert len(lora_leaves) == 2 * 2 * 2
    for k, v in lora_leaves.items():
        assert 4 in v.shape  # rank dim

    mask = trainable_mask(params, cfg, num_layers_unfrozen=-1)
    flat_mask = traverse_util.flatten_dict(mask)
    for k, m in flat_mask.items():
        if any("_lora_" in str(p) for p in k):
            assert m, k
        elif str(k[0]) == "lm":
            assert not m, k  # all base LM weights frozen under peft
        else:
            assert m, k  # v_head stays trainable


def test_init_is_identity_and_zero_lora_equivalence():
    """B=0 at init => lora model == base model; zero_lora == disabling."""
    cfg, model, params, tokens, mask = _build()
    logits, values, _ = jax.jit(model.apply)({"params": params}, tokens, mask)

    perturbed = _perturb_lora(params)
    logits_pert, *_ = jax.jit(model.apply)({"params": perturbed}, tokens, mask)
    assert not np.allclose(np.asarray(logits), np.asarray(logits_pert), atol=1e-5)

    disabled = zero_lora(perturbed)
    logits_dis, *_ = jax.jit(model.apply)({"params": disabled}, tokens, mask)
    np.testing.assert_allclose(np.asarray(logits_dis), np.asarray(logits), atol=1e-6)


def test_ref_logits_are_adapter_disabled():
    """The hydra replacement under peft: split forced to 0 and ref logits
    equal the base model's even after adapter updates."""
    cfg, model, params, tokens, mask = _build()
    assert resolve_split(cfg, 2) == 0

    perturbed = _perturb_lora(params)
    ref = ref_param_subtree({"lm": perturbed["lm"], "v_head": perturbed["v_head"]}, cfg, 0)
    logits, values, ref_logits, _ = forward_policy_and_ref(
        model, perturbed, ref, tokens, mask, split=0
    )
    base_logits, *_ = model.apply({"params": zero_lora(perturbed)}, tokens, mask)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(base_logits), atol=1e-6)
    assert not np.allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)


def test_merge_and_unload():
    cfg, model, params, tokens, mask = _build()
    perturbed = _perturb_lora(params)
    merged = merge_lora_into_params(perturbed, cfg)
    assert not any("_lora_" in str(p) for p in
                   (p for k in traverse_util.flatten_dict(merged) for p in k))

    logits_lora, *_ = model.apply({"params": perturbed}, tokens, mask)
    # merged params must run on a lora-free config (same module graph minus
    # adapters)
    cfg_plain = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32)
    model_plain = CausalLMWithValueHead(cfg_plain)
    logits_merged, *_ = model_plain.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, merged)}, tokens, mask
    )
    np.testing.assert_allclose(
        np.asarray(logits_merged), np.asarray(logits_lora), atol=1e-5
    )


def test_adapter_checkpoint_round_trip_is_bitwise(tmp_path):
    """Serving-side adapter round trip: split_lora -> adapter checkpoint
    on disk -> AdapterStore -> gathered `lora_rows` forward is BITWISE
    the param-path forward, and the zero adapter (stack slot 0) is
    bitwise the zero_lora base — the invariant multi-tenant serving
    rests on (one heterogeneous batch == N single-adapter models)."""
    import os

    import orbax.checkpoint as ocp

    from trlx_tpu import resilience
    from trlx_tpu.inference.adapters import AdapterStore

    cfg, model, params, tokens, mask = _build()
    perturbed = _perturb_lora(params)
    lora_flat, _ = split_lora(perturbed)
    adapter_dir = tmp_path / "adapters"
    d = str(adapter_dir / "t1")
    ocp.PyTreeCheckpointer().save(
        os.path.join(d, "state"),
        {"train_params": {str(k): np.asarray(v) for k, v in lora_flat.items()}},
        force=True,
    )
    resilience.write_manifest(d, step=1)

    store = AdapterStore(params, adapter_dir=str(adapter_dir), max_resident=2)
    slot = store.acquire("t1")
    assert slot == 1
    stack = store.stacked()

    def gather(index):
        idx = jnp.full((tokens.shape[0],), index, jnp.int32)
        return jax.tree_util.tree_map(lambda s: s[idx], stack)

    logits_rows, *_ = model.apply(
        {"params": params, "lora_rows": gather(slot)}, tokens, mask
    )
    logits_param, *_ = model.apply({"params": perturbed}, tokens, mask)
    np.testing.assert_array_equal(np.asarray(logits_rows), np.asarray(logits_param))

    logits_zero, *_ = model.apply(
        {"params": perturbed, "lora_rows": gather(0)}, tokens, mask
    )
    logits_base, *_ = model.apply({"params": zero_lora(perturbed)}, tokens, mask)
    np.testing.assert_array_equal(np.asarray(logits_zero), np.asarray(logits_base))

    store.release("t1")
    assert store.refcount("t1") == 0


def test_build_model_with_peft_config():
    mc = ModelConfig(model_path="random:gpt2-tiny", peft_config=PEFT_CONFIG,
                     model_extra_configs={"dtype": "float32"})
    model, cfg, params = build_model(mc, vocab_size=64)
    assert cfg.lora_rank == 4
    lora_leaves, _ = split_lora(params)
    assert lora_leaves


def test_hf_load_with_lora_template(tmp_path):
    """Loading an HF checkpoint into a LoRA-enabled model keeps the freshly
    initialized adapters and fills only the base weights."""
    torch = pytest.importorskip("torch")
    import transformers as tf

    from trlx_tpu.models import hf_interop

    torch.manual_seed(0)
    hf_model = tf.GPT2LMHeadModel(
        tf.GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=2, n_head=2)
    )
    hf_model.eval()
    path = str(tmp_path / "gpt2")
    hf_model.save_pretrained(path, safe_serialization=True)

    cfg = hf_interop.config_from_hf(path, dtype=jnp.float32, lora_rank=4)
    model = CausalLMWithValueHead(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    params = hf_interop.load_params_from_hf(path, cfg, template)

    lora_leaves, _ = split_lora(params)
    assert lora_leaves
    with torch.no_grad():
        ref = hf_model(input_ids=torch.zeros((1, 8), dtype=torch.long)).logits.numpy()
    logits, *_ = model.apply({"params": params}, tokens, jnp.ones_like(tokens))
    np.testing.assert_allclose(np.asarray(logits)[0], ref[0], atol=2e-3)


def test_ppo_trainer_with_peft(tmp_path):
    """End-to-end: trainer trains only adapters+heads; a train step leaves
    base weights untouched; orbax checkpoint holds the small tree."""
    from trlx_tpu.data import PPORLElement
    from trlx_tpu.pipeline import MiniBatchIterator
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", peft_config=PEFT_CONFIG),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=4, tracker=None,
                   checkpoint_dir=str(tmp_path)),
        method=dict(gen_kwargs=dict(max_new_tokens=8, do_sample=True)),
    )
    trainer = PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples))

    # trainable tree is adapters + v_head only
    for k in trainer.train_params:
        assert any("_lora_" in str(p) for p in k) or str(k[0]) == "v_head", k

    base_before = {k: np.asarray(v).copy() for k, v in trainer.frozen_params.items()}

    rng = np.random.default_rng(0)
    for _ in range(4):
        trainer.store.push([
            PPORLElement(
                query_tensor=rng.integers(3, 60, size=6).astype(np.int32),
                response_tensor=rng.integers(3, 60, size=6).astype(np.int32),
                logprobs=rng.normal(size=6).astype(np.float32),
                values=rng.normal(size=6).astype(np.float32),
                rewards=rng.normal(size=6).astype(np.float32),
            )
        ])
    loader = trainer.store.create_loader(4, shuffle=False)
    for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
        trainer.train_minibatch(minibatch)
        break

    for k, v in trainer.frozen_params.items():
        np.testing.assert_array_equal(np.asarray(v), base_before[k], err_msg=str(k))

    lora_changed = any(
        not np.allclose(np.asarray(v), 0.0)
        for k, v in trainer.train_params.items()
        if str(k[-1]).endswith("_lora_b")
    )
    assert lora_changed, "adapter B matrices still zero after a train step"

    trainer.save(str(tmp_path / "ckpt"))
    trainer.load(str(tmp_path / "ckpt"))


# ---------------------------------------------------------------------------
# Prompt tuning (peft PROMPT_TUNING — reference prompt-adapter handling,
# modeling_ppo.py:314-327)
# ---------------------------------------------------------------------------

PROMPT_CONFIG = {"peft_type": "PROMPT_TUNING", "num_virtual_tokens": 4}


def _build_prompt():
    overrides = lora_overrides_from_peft_config(PROMPT_CONFIG)
    cfg = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32, **overrides)
    model = CausalLMWithValueHead(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 12)), jnp.int32)
    mask = np.ones((2, 12), np.int32)
    mask[0, :3] = 0  # left padding
    mask = jnp.asarray(mask)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    return cfg, model, params, tokens, mask


def test_prompt_tuning_translation_and_param():
    assert lora_overrides_from_peft_config(PROMPT_CONFIG) == {"prompt_tokens": 4}
    cfg, model, params, tokens, mask = _build_prompt()
    assert params["lm"]["soft_prompt"].shape == (4, cfg.d_model)
    logits, values, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    assert logits.shape == (2, 12, 64)  # caller-visible length unchanged
    assert values.shape == (2, 12)


def test_prompt_tuning_only_soft_prompt_trains():
    cfg, model, params, *_ = _build_prompt()
    tm = trainable_mask(params, cfg, -1)
    flat = traverse_util.flatten_dict(tm)
    for k, v in flat.items():
        if k[0] != "lm":
            assert v, k
        else:
            assert v == ("soft_prompt" in k), k


def test_prompt_tuning_ref_is_prompt_free():
    """The full reference forward skips the soft prompt: equals a prompt-free model
    on the same base weights, and differs from the prompted forward."""
    cfg, model, params, tokens, mask = _build_prompt()
    logits, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    ref = ref_param_subtree(params, cfg, resolve_split(cfg, 2))
    assert resolve_split(cfg, 2) == 0  # prompt forces full-ref mode
    ref_logits, _, _ = model.apply(
        {"params": {"lm": ref}}, tokens, mask, use_prompt=False, with_value=False,
        method=CausalLMWithValueHead.forward,
    )
    assert not np.allclose(np.asarray(logits), np.asarray(ref_logits))

    cfg0 = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32)
    m0 = CausalLMWithValueHead(cfg0)
    p0 = jax.jit(m0.init)(jax.random.PRNGKey(1), tokens, mask)["params"]
    lm0 = {k: v for k, v in params["lm"].items() if k != "soft_prompt"}
    l0, _, _ = jax.jit(m0.apply)({"params": {**p0, "lm": lm0}}, tokens, mask)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(l0), atol=1e-5)


def test_prompt_tuning_decode_matches_forward():
    from trlx_tpu.models import init_kv_cache

    cfg, model, params, tokens, mask = _build_prompt()
    logits, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    cache = init_kv_cache(cfg, 2, 12)  # prompt slots reserved internally
    dl, _, _ = model.apply(
        {"params": params}, tokens, cache, mask, True,
        method=CausalLMWithValueHead.decode_step,
    )
    np.testing.assert_allclose(np.asarray(dl[:, -1]), np.asarray(logits[:, -1]), atol=1e-4)


def test_ppo_trainer_with_prompt_tuning(tmp_path):
    """Full PPO cycle under prompt tuning: generation, scoring with a
    prompt-free reference, and a train step that moves only the soft
    prompt + heads."""
    from trlx_tpu.pipeline import MiniBatchIterator
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   peft_config=PROMPT_CONFIG),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, tracker=None,
                   checkpoint_dir=str(tmp_path)),
        method=dict(num_rollouts=8, chunk_size=8,
                    gen_kwargs=dict(max_new_tokens=8, do_sample=True)),
    )
    trainer = PPOTrainer(
        config, reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs]
    )
    for k in trainer.train_params:
        assert "soft_prompt" in k or str(k[0]) == "v_head", k
    trainer.add_prompt_pipeline(
        PromptPipeline(["abcdefgh"] * 16, max_prompt_length=8, tokenizer=trainer.tokenizer)
    )
    trainer.make_experience(8)
    loader = trainer.create_train_dataloader()
    before = np.asarray(
        trainer.train_params[next(k for k in trainer.train_params if "soft_prompt" in k)]
    ).copy()
    for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
        stats = trainer.train_minibatch(minibatch)
        break
    assert np.isfinite(float(np.asarray(stats["losses"]["total_loss"])))
    after = np.asarray(
        trainer.train_params[next(k for k in trainer.train_params if "soft_prompt" in k)]
    )
    assert not np.allclose(before, after), "soft prompt did not move"

    # second experience pass AFTER a train step: the jitted step donates the
    # trainable soft prompt, so ref_params must not alias it (a stale alias
    # crashes here with "Array has been deleted")
    trainer.store.clear_history()
    trainer.make_experience(8)


def test_prompt_tuning_learned_pos_budget_guard(tmp_path):
    """Soft prompt + learned positions: seq_length must leave room in the
    position table (silent embedding clamp otherwise)."""
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   peft_config=PROMPT_CONFIG),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=256, batch_size=4, tracker=None,  # == max_seq_len
                   checkpoint_dir=str(tmp_path)),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
    )
    with pytest.raises(ValueError, match="learned-position table"):
        PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples))


def test_prompt_tuning_export_includes_soft_prompt(tmp_path):
    """save_pretrained writes the trained soft prompt alongside the base
    checkpoint (HF layout has no slot for it)."""
    import os

    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   peft_config=PROMPT_CONFIG),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=4, tracker=None,
                   checkpoint_dir=str(tmp_path)),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
    )
    trainer = PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    out = str(tmp_path / "hf")
    trainer.save_pretrained(out)
    assert os.path.exists(os.path.join(out, "soft_prompt.npy"))
    sp = np.load(os.path.join(out, "soft_prompt.npy"))
    assert sp.shape == (4, trainer.model_cfg.d_model)


# ---------------------------------------------------------------------------
# Prefix tuning (peft PREFIX_TUNING — per-layer trainable K/V prefixes,
# reference prefix bypass modeling_ppo.py:314-327)
# ---------------------------------------------------------------------------

PREFIX_CONFIG = {"peft_type": "PREFIX_TUNING", "num_virtual_tokens": 4}


def _build_prefix():
    overrides = lora_overrides_from_peft_config(PREFIX_CONFIG)
    cfg = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32, **overrides)
    model = CausalLMWithValueHead(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 12)), jnp.int32)
    mask = np.ones((2, 12), np.int32)
    mask[0, :3] = 0
    mask = jnp.asarray(mask)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    return cfg, model, params, tokens, mask


def test_prefix_tuning_params_and_masking():
    cfg, model, params, tokens, mask = _build_prefix()
    assert params["lm"]["block_0"]["attn"]["prefix_k"].shape == (
        4, cfg.kv_heads, cfg.head_dim,
    )
    tm = traverse_util.flatten_dict(trainable_mask(params, cfg, -1))
    for k, v in tm.items():
        if k[0] == "lm":
            assert v == (k[-1] in ("prefix_k", "prefix_v")), k
        else:
            assert v, k


def test_prefix_tuning_ref_is_prefix_free():
    cfg, model, params, tokens, mask = _build_prefix()
    logits, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    assert resolve_split(cfg, 2) == 0
    ref = ref_param_subtree(params, cfg, 0)
    ref_logits, _, _ = model.apply(
        {"params": {"lm": ref}}, tokens, mask, use_prompt=False, with_value=False,
        method=CausalLMWithValueHead.forward,
    )
    assert not np.allclose(np.asarray(logits), np.asarray(ref_logits))

    def strip(d):
        if isinstance(d, dict):
            return {k: strip(v) for k, v in d.items()
                    if k not in ("prefix_k", "prefix_v")}
        return d

    cfg0 = config_from_preset("gpt2-tiny", vocab_size=64, dtype=jnp.float32)
    m0 = CausalLMWithValueHead(cfg0)
    p0 = jax.jit(m0.init)(jax.random.PRNGKey(1), tokens, mask)["params"]
    l0, _, _ = jax.jit(m0.apply)({"params": {**p0, "lm": strip(params["lm"])}}, tokens, mask)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(l0), atol=1e-5)


def test_prefix_tuning_decode_matches_forward():
    from trlx_tpu.models import init_kv_cache

    cfg, model, params, tokens, mask = _build_prefix()
    logits, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)
    cache = init_kv_cache(cfg, 2, 16)
    dl, _, cache = model.apply(
        {"params": params}, tokens, cache, mask, True,
        method=CausalLMWithValueHead.decode_step,
    )
    np.testing.assert_allclose(np.asarray(dl[:, -1]), np.asarray(logits[:, -1]), atol=1e-4)
    # a cached single step after prefill also sees the prefixes: same
    # logits as a fresh forward over the extended sequence
    nxt = jnp.asarray([[7], [9]], jnp.int32)
    dl2, _, _ = model.apply(
        {"params": params}, nxt, cache, jnp.ones((2, 1), jnp.int32), False,
        method=CausalLMWithValueHead.decode_step,
    )
    full = jnp.concatenate([tokens, nxt], axis=1)
    fmask = jnp.concatenate([mask, jnp.ones((2, 1), jnp.int32)], axis=1)
    fl, _, _ = jax.jit(model.apply)({"params": params}, full, fmask)
    np.testing.assert_allclose(np.asarray(dl2[:, -1]), np.asarray(fl[:, -1]), atol=1e-4)


def test_ppo_trainer_with_prefix_tuning(tmp_path):
    from trlx_tpu.pipeline import MiniBatchIterator
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   peft_config=PREFIX_CONFIG),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, tracker=None,
                   checkpoint_dir=str(tmp_path)),
        method=dict(num_rollouts=8, chunk_size=8,
                    gen_kwargs=dict(max_new_tokens=8, do_sample=True)),
    )
    trainer = PPOTrainer(
        config, reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs]
    )
    for k in trainer.train_params:
        assert str(k[-1]) in ("prefix_k", "prefix_v") or str(k[0]) == "v_head", k
    trainer.add_prompt_pipeline(
        PromptPipeline(["abcdefgh"] * 16, max_prompt_length=8, tokenizer=trainer.tokenizer)
    )
    trainer.make_experience(8)
    loader = trainer.create_train_dataloader()
    for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
        stats = trainer.train_minibatch(minibatch)
        break
    assert np.isfinite(float(np.asarray(stats["losses"]["total_loss"])))
    # second experience pass after the donating train step (ref aliasing)
    trainer.store.clear_history()
    trainer.make_experience(8)

    # export writes the prefix adapter alongside the base checkpoint
    import os

    out = str(tmp_path / "hf")
    trainer.save_pretrained(out)
    assert os.path.exists(os.path.join(out, "prefix_kv.npz"))
