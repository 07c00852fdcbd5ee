"""Trainer integration tests (counterpart of reference tests/test_trainers.py):
full train loops with tiny from-scratch models on the virtual CPU mesh,
checkpoint layout, and per-method wiring."""

import os

import numpy as np
import pytest

import trlx_tpu as trlx
from trlx_tpu.data.configs import (
    ModelConfig,
    OptimizerConfig,
    ParallelConfig,
    SchedulerConfig,
    TokenizerConfig,
    TrainConfig,
    TRLConfig,
)
from trlx_tpu.trainer.ilql_trainer import ILQLConfig
from trlx_tpu.trainer.ppo_trainer import PPOConfig
from trlx_tpu.trainer.sft_trainer import SFTConfig


def ppo_config(tmp_path, **train_overrides):
    train = dict(
        seq_length=16,
        epochs=2,
        total_steps=4,
        batch_size=8,
        checkpoint_interval=4,
        eval_interval=2,
        pipeline="PromptPipeline",
        trainer="PPOTrainer",
        tracker=None,
        checkpoint_dir=str(tmp_path / "ckpts"),
        seed=7,
    )
    train.update(train_overrides)
    return TRLConfig(
        train=TrainConfig(**train),
        model=ModelConfig(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=TokenizerConfig(tokenizer_path="char:abcdefgh"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="constant"),
        method=PPOConfig(
            name="PPOConfig",
            num_rollouts=16,
            chunk_size=8,
            ppo_epochs=2,
            init_kl_coef=0.01,
            target=None,
            horizon=1000,
            gamma=1.0,
            lam=0.95,
            cliprange=0.2,
            cliprange_value=0.2,
            vf_coef=1.0,
            scale_reward=None,
            ref_mean=None,
            ref_std=None,
            cliprange_reward=10,
            gen_kwargs=dict(max_new_tokens=6, top_k=0, top_p=1.0, do_sample=True),
        ),
        parallel=ParallelConfig(data=2, fsdp=2, tensor=2),
    )


def count_letters_reward(samples, **kwargs):
    # how many 'a's appear in each sample
    return [float(s.count("a")) for s in samples]


def test_ppo_trainer_full_loop(tmp_path):
    config = ppo_config(tmp_path)
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["ab", "cd", "ef", "gh"] * 2,
        eval_prompts=["ab", "cd"] * 4,
        config=config,
    )
    assert trainer.iter_count == 4
    ckpt_dir = config.train.checkpoint_dir
    dirs = os.listdir(ckpt_dir)
    assert "best_checkpoint" in dirs
    assert any(d.startswith("checkpoint_") for d in dirs)
    # hf export exists
    step_dirs = [d for d in dirs if d.startswith("checkpoint_")]
    assert os.path.exists(os.path.join(ckpt_dir, step_dirs[0], "hf_model", "pytorch_model.bin"))


def test_ppo_checkpoint_resume(tmp_path):
    config = ppo_config(tmp_path)
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["ab", "cd"] * 4,
        eval_prompts=["ab"] * 8,
        config=config,
    )
    # resume from the saved checkpoint
    step_dir = [
        d for d in os.listdir(config.train.checkpoint_dir) if d.startswith("checkpoint_")
    ][0]
    path = os.path.join(config.train.checkpoint_dir, step_dir)
    params_before = trainer.train_params
    trainer.load(path)
    assert trainer.iter_count == 4
    # params restored to saved values (same tree structure)
    import jax

    assert jax.tree_util.tree_structure(params_before) == jax.tree_util.tree_structure(
        trainer.train_params
    )


def test_ppo_rewards_affect_training(tmp_path):
    """Hydra KL: after a few updates policy logits differ from ref logits."""
    import jax.numpy as jnp

    config = ppo_config(tmp_path)
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["ab", "cd"] * 4,
        eval_prompts=["ab"] * 8,
        config=config,
    )
    tokens = jnp.asarray([[0, 1, 2, 3]], dtype=jnp.int32)
    mask = jnp.ones_like(tokens)
    from trlx_tpu.models import forward_policy_and_ref

    logits, _, ref_logits, _ = forward_policy_and_ref(
        trainer.model, trainer.params, trainer.ref_params, tokens, mask, trainer.split
    )
    assert float(jnp.abs(logits - ref_logits).max()) > 1e-4


def test_sft_trainer(tmp_path):
    config = TRLConfig(
        train=TrainConfig(
            seq_length=24, epochs=2, total_steps=4, batch_size=4,
            checkpoint_interval=100, eval_interval=4, pipeline="PromptPipeline",
            trainer="SFTTrainer", tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
        ),
        model=ModelConfig(model_path="random:gpt2-tiny"),
        tokenizer=TokenizerConfig(tokenizer_path="byte"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="constant"),
        method=SFTConfig(name="sftconfig", gen_kwargs=dict(max_new_tokens=4, do_sample=False)),
    )
    trainer = trlx.train(
        samples=["hello world", "foo bar baz", "lorem ipsum", "a b c"],
        eval_prompts=["hello", "foo"],
        config=config,
    )
    # 4 samples / batch 4 = 1 batch per epoch x 2 epochs
    assert trainer.iter_count == 2


def test_sft_dialog_pairs(tmp_path):
    config = TRLConfig(
        train=TrainConfig(
            seq_length=24, epochs=1, total_steps=2, batch_size=2,
            checkpoint_interval=100, eval_interval=2, pipeline="PromptPipeline",
            trainer="SFTTrainer", tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
        ),
        model=ModelConfig(model_path="random:gpt2-tiny"),
        tokenizer=TokenizerConfig(tokenizer_path="byte"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="constant"),
        method=SFTConfig(name="sftconfig", gen_kwargs=dict(max_new_tokens=4, do_sample=False)),
    )
    trainer = trlx.train(
        samples=[("q: hi", " a: hello"), ("q: yo", " a: hey")],
        eval_prompts=["q: hi"],
        config=config,
    )
    # 2 samples / batch 2 = 1 batch per epoch x 1 epoch
    assert trainer.iter_count == 1


def test_ilql_trainer(tmp_path):
    config = TRLConfig(
        train=TrainConfig(
            seq_length=24, epochs=2, total_steps=4, batch_size=4,
            checkpoint_interval=100, eval_interval=4, pipeline="PromptPipeline",
            trainer="ILQLTrainer", tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
        ),
        model=ModelConfig(model_path="random:gpt2-tiny"),
        tokenizer=TokenizerConfig(tokenizer_path="byte"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="constant"),
        method=ILQLConfig(
            name="ilqlconfig", tau=0.7, gamma=0.99, cql_scale=0.1, awac_scale=1.0,
            alpha=1.0, beta=0.0, steps_for_target_q_sync=2, two_qs=True,
            gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0, temperature=1.0),
        ),
    )
    trainer = trlx.train(
        samples=[("ask", " yes"), ("ask", " no"), ("q", " maybe"), ("q", " sure")],
        rewards=[1.0, -1.0, 0.5, 0.2],
        eval_prompts=["ask", "q"],
        config=config,
    )
    assert trainer.iter_count == 2
    # target heads synced with alpha=1 -> equal q heads
    import jax

    heads = trainer.params["ilql_heads"]
    q = jax.tree_util.tree_leaves(heads["q_head_0"])
    t = jax.tree_util.tree_leaves(heads["target_q_head_0"])
    for a, b in zip(q, t):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_inner_epoch_matches_stepwise(tmp_path):
    """fuse_inner_epoch=True (one lax.scan dispatch per inner epoch) must
    produce the same parameters as per-step dispatch: same minibatch
    order, one optimizer update per minibatch."""
    import jax
    from trlx_tpu.data import PPORLElement
    from trlx_tpu.pipeline import MiniBatchIterator
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    def make_trainer():
        config = ppo_config(tmp_path)
        trainer = PPOTrainer(config, reward_fn=count_letters_reward)
        rng = np.random.default_rng(3)
        for _ in range(16):
            n = 5
            trainer.store.push([
                PPORLElement(
                    query_tensor=rng.integers(3, 8, size=4).astype(np.int32),
                    response_tensor=rng.integers(3, 8, size=n).astype(np.int32),
                    logprobs=rng.normal(size=n).astype(np.float32),
                    values=rng.normal(size=n).astype(np.float32),
                    rewards=rng.normal(size=n).astype(np.float32),
                )
            ])
        return trainer

    t_step = make_trainer()
    loader = t_step.store.create_loader(8, shuffle=True, seed=42)
    for minibatch in MiniBatchIterator(loader, t_step.mb_size, t_step.num_mb):
        t_step.train_minibatch(minibatch)

    t_fused = make_trainer()
    loader = t_fused.store.create_loader(8, shuffle=True, seed=42)
    _, n_steps = t_fused.train_inner_epoch_fused(loader)
    assert n_steps == 2  # 16 rollouts / batch 8

    flat_a = t_step.train_params
    flat_b = t_fused.train_params
    assert set(flat_a) == set(flat_b)
    for k in flat_a:
        np.testing.assert_allclose(
            np.asarray(flat_a[k]), np.asarray(flat_b[k]), atol=1e-5, err_msg=str(k)
        )


def test_fused_learn_loop_end_to_end(tmp_path):
    """Full learn() with fuse_inner_epoch=True: intervals use crossing
    semantics (stride n_steps), checkpoints and eval still fire."""
    config = ppo_config(tmp_path, total_steps=4, checkpoint_interval=3, eval_interval=2)
    config.train.fuse_inner_epoch = True
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["abcd", "bcda", "cdab", "dabc"] * 2,
        config=config,
    )
    assert trainer.iter_count >= 4
    ckpts = os.listdir(str(tmp_path / "ckpts"))
    assert any(c.startswith("checkpoint_") for c in ckpts), ckpts


def test_nan_guard_aborts_on_divergence(tmp_path):
    """Failure detection: consecutive non-finite losses abort with a
    clear FloatingPointError instead of training on garbage."""
    config = ppo_config(tmp_path, total_steps=10)
    config.train.nan_guard_patience = 2
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    t = PPOTrainer(config, reward_fn=count_letters_reward)
    t.total_steps = 10
    t.iter_count = 1
    # one bad step: warns, doesn't raise
    t._check_divergence({"losses/total_loss": float("nan")})
    assert t._nan_streak == 1
    # recovery resets the streak
    t._check_divergence({"losses/total_loss": 1.0})
    assert t._nan_streak == 0
    # patience exceeded: abort
    t._check_divergence({"losses/total_loss": float("inf")})
    with pytest.raises(FloatingPointError, match="diverged"):
        t._check_divergence({"losses/total_loss": float("nan")})


def test_fuse_all_inner_epochs_matches_per_epoch(tmp_path):
    """fuse_all_inner_epochs (all PPO epochs in one dispatch) produces the
    same parameters as per-epoch fused dispatch with identical shuffles."""
    from trlx_tpu.data import PPORLElement
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    def make_trainer(sub):
        config = ppo_config(tmp_path / sub)
        trainer = PPOTrainer(config, reward_fn=count_letters_reward)
        rng = np.random.default_rng(3)
        for _ in range(16):
            n = 5
            trainer.store.push([
                PPORLElement(
                    query_tensor=rng.integers(3, 8, size=4).astype(np.int32),
                    response_tensor=rng.integers(3, 8, size=n).astype(np.int32),
                    logprobs=rng.normal(size=n).astype(np.float32),
                    values=rng.normal(size=n).astype(np.float32),
                    rewards=rng.normal(size=n).astype(np.float32),
                )
            ])
        return trainer

    t_per = make_trainer("per")
    for i in range(2):
        t_per.train_inner_epoch_fused(t_per.create_train_dataloader(seed_offset=i))

    t_all = make_trainer("all")
    loaders = [t_all.create_train_dataloader(seed_offset=i) for i in range(2)]
    _, n_steps = t_all.train_inner_epochs_fused(loaders)
    assert n_steps == 4  # 2 epochs x (16 rollouts / batch 8)

    for k in t_per.train_params:
        np.testing.assert_allclose(
            np.asarray(t_per.train_params[k]), np.asarray(t_all.train_params[k]),
            atol=1e-5, err_msg=str(k),
        )


def test_ppo_value_branch_full_loop(tmp_path):
    """num_value_layers_unfrozen > 0 through the full PPO loop (reference
    make_value_branch, modeling_ppo.py:255-263): the deeper value branch
    trains end-to-end."""
    config = ppo_config(tmp_path, total_steps=2)
    config.method.num_value_layers_unfrozen = 1
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["ab", "cd", "ef", "gh"] * 2,
        eval_prompts=["ab", "cd"] * 4,
        config=config,
    )
    assert trainer.iter_count == 2
    assert any("value_branch" in str(k) for k in trainer.train_params)


def test_ppo_windowed_loss_equals_full_forward(tmp_path):
    """The r5 windowed-head train loss (`forward(window=...)`: trunk full-width,
    50k-vocab unembed + CE + value head over the response window only)
    must produce the SAME loss and stats as the full-forward + slice
    path on identical params and chunk — the windowing is a pure
    dead-compute elimination, never a numerics change."""
    import jax
    import jax.numpy as jnp

    config = ppo_config(tmp_path)
    config = config.evolve(model=dict(model_extra_configs=dict(dtype="float32")))
    trainer = trlx.train(
        reward_fn=count_letters_reward,
        prompts=["ab", "cd", "ef", "gh"] * 2,
        eval_prompts=["ab", "cd"],
        config=config.evolve(train=dict(total_steps=1, eval_interval=100)),
    )
    assert trainer._window_loss_ok()
    loss_windowed = trainer.make_loss_fn()

    # force the full-forward path on the same trainer
    trainer._window_loss_ok = lambda: False
    loss_full = trainer.make_loss_fn()

    loader = trainer.store.create_loader(8, shuffle=False)
    chunk = jax.tree_util.tree_map(jnp.asarray, next(iter(loader)))
    lw, sw = jax.jit(loss_windowed)(trainer.train_params, trainer.frozen_params, chunk)
    lf, sf = jax.jit(loss_full)(trainer.train_params, trainer.frozen_params, chunk)
    np.testing.assert_allclose(np.asarray(lw), np.asarray(lf), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        sw, sf,
    )
