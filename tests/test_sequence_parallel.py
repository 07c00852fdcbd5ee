"""Sequence-parallel (context-parallel) SFT trainer: ring attention over
the `sequence` mesh axis end-to-end through the public train() API, with
loss parity against the plain single-program SFT trainer. The reference
has no context parallelism at all (SURVEY.md §2.7/§5.7)."""

import numpy as np
import pytest

import jax

import trlx_tpu as trlx
from trlx_tpu.data.default_configs import default_sft_config
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.sft_trainer import SFTTrainer

from parity import assert_loss_parity


SP_SAMPLES = ["long context sequence parallel training sample " * 2,
              "short sample", "medium length training sample here",
              "another long context training sample with more words " * 2] * 2


def assert_sp_loss_parity(trainer, plain, batch):
    """SP-vs-plain loss parity on identical params/batch: the plain trainer
    splits trainable from frozen as the sequence-parallel one does."""
    assert_loss_parity(
        trainer.make_loss_fn(),
        (trainer.train_params, trainer.frozen_params, trainer.batch_to_device(batch)),
        plain.make_loss_fn(), (trainer.train_params, trainer.frozen_params, batch),
    )


def assert_sft_loss_parity(trainer, plain_cfg):
    plain = SFTTrainer(plain_cfg, devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(4, shuffle=False)))
    assert_sp_loss_parity(trainer, plain, batch)


def sp_config(tmp_path):
    return default_sft_config().evolve(
        model=dict(model_path="random:llama-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte", padding_side="right"),
        train=dict(seq_length=64, batch_size=4, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="SequenceParallelSFTTrainer",
                   checkpoint_dir=str(tmp_path), seed=3),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=2, fsdp=1, sequence=4),
    )


def test_sequence_parallel_sft_end_to_end_and_loss_parity(tmp_path):
    config = sp_config(tmp_path)
    # ragged lengths: right padding + the seq-divisibility pad both engage
    trainer = trlx.train(samples=SP_SAMPLES, eval_prompts=["long context"],
                         config=config)
    assert trainer.iter_count == 2
    assert trainer.model_cfg.attn_impl == "ring"

    assert_sft_loss_parity(trainer, config.evolve(
        train=dict(trainer="SFTTrainer"),
        parallel=dict(data=1, sequence=1),
        model=dict(model_extra_configs=dict(dtype="float32", attn_impl="xla")),
    ))


def test_sequence_parallel_validation(tmp_path):
    from trlx_tpu.trainer.sequence_parallel_sft_trainer import SequenceParallelSFTTrainer

    cfg = sp_config(tmp_path)
    cfg.parallel.sequence = 1
    with pytest.raises(ValueError, match="sequence > 1"):
        SequenceParallelSFTTrainer(cfg)

    cfg = sp_config(tmp_path)
    cfg.train.seq_length = 62  # not divisible by 4
    with pytest.raises(ValueError, match="divide"):
        SequenceParallelSFTTrainer(cfg)

    cfg = sp_config(tmp_path)
    cfg.model.model_extra_configs = dict(dtype="float32", attn_impl="flash")
    with pytest.raises(ValueError, match="ring"):
        SequenceParallelSFTTrainer(cfg)

    cfg = sp_config(tmp_path)
    cfg.tokenizer.padding_side = "left"
    with pytest.raises(ValueError, match="padding_side"):
        SequenceParallelSFTTrainer(cfg)

    cfg = sp_config(tmp_path)
    cfg.parallel.pipeline = 2
    cfg.parallel.sequence = 2
    cfg.parallel.data = 2
    with pytest.raises(NotImplementedError, match="pipeline"):
        SequenceParallelSFTTrainer(cfg)


def test_sequence_parallel_composes_with_tp_fsdp(tmp_path):
    """SP x TP and SP x FSDP (VERDICT r1 missing #2): the fsdp/tensor axes
    stay GSPMD-auto inside the SP shard_map, so tensor-sharded params work
    under the sequence program — loss parity vs the plain trainer, and
    params actually sharded over the composed axis."""
    for axis in ("tensor", "fsdp"):
        config = sp_config(tmp_path).evolve(
            train=dict(checkpoint_dir=str(tmp_path / axis)),
            parallel={"data": 2, "sequence": 2, axis: 2},
        )
        trainer = trlx.train(samples=SP_SAMPLES, eval_prompts=["long context"],
                             config=config)
        assert trainer.iter_count == 2

        # at least one matrix param is sharded over the composed axis
        sharded = any(
            axis in jax.tree_util.tree_leaves([list(v.sharding.spec)])
            for v in trainer.train_params.values()
            if hasattr(v, "sharding") and v.ndim >= 2
        )
        assert sharded, f"no param sharded over {axis} under SP x {axis}"

        assert_sft_loss_parity(trainer, config.evolve(
            train=dict(trainer="SFTTrainer"),
            parallel={"data": 1, "sequence": 1, axis: 1},
            model=dict(model_extra_configs=dict(dtype="float32", attn_impl="xla")),
        ))


def test_sequence_parallel_ppo_end_to_end_and_loss_parity(tmp_path):
    """Context-parallel PPO: full train loop through trlx.train, then
    exact loss parity against the plain PPOTrainer on identical params
    and rollout batch (left-padded ragged queries included)."""
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:llama-tiny", num_layers_unfrozen=1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, batch_size=4, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="SequenceParallelPPOTrainer",
                   checkpoint_dir=str(tmp_path), seed=5),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=9, do_sample=True)),
        parallel=dict(data=2, fsdp=1, sequence=4),
    )
    reward_fn = lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs]
    prompts = ["abcdefghijk"[:4 + i % 5] for i in range(16)]  # ragged -> left pad
    trainer = trlx.train(reward_fn=reward_fn, prompts=prompts,
                         eval_prompts=prompts[:4], config=config)
    assert trainer.iter_count >= 2
    assert trainer.model_cfg.attn_impl == "ring"

    batch = next(iter(trainer.store.create_loader(4, shuffle=False)))
    plain_cfg = config.evolve(
        train=dict(trainer="PPOTrainer"),
        parallel=dict(data=1, sequence=1),
        model=dict(model_extra_configs=dict(dtype="float32", attn_impl="xla")),
    )
    plain = PPOTrainer(plain_cfg, reward_fn=reward_fn, devices=jax.devices()[:1])
    assert_sp_loss_parity(trainer, plain, batch)


def test_sequence_parallel_ppo_composes_with_tp(tmp_path):
    """SP x TP through the PPO trainer: the full cycle (generate on
    tensor-sharded params, the double-duty score shard_map incl. the
    hydra ref branch, the SP train loss) on data=2 x sequence=2 x
    tensor=2, with loss parity vs the plain PPOTrainer."""
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:llama-tiny", num_layers_unfrozen=1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, batch_size=4, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="SequenceParallelPPOTrainer",
                   checkpoint_dir=str(tmp_path), seed=5),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=9, do_sample=True)),
        parallel=dict(data=2, sequence=2, tensor=2),
    )
    reward_fn = lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs]
    prompts = ["abcdefghijk"[:4 + i % 5] for i in range(16)]
    trainer = trlx.train(reward_fn=reward_fn, prompts=prompts,
                         eval_prompts=prompts[:4], config=config)
    assert trainer.iter_count >= 2

    batch = next(iter(trainer.store.create_loader(4, shuffle=False)))
    plain_cfg = config.evolve(
        train=dict(trainer="PPOTrainer"),
        parallel=dict(data=1, sequence=1, tensor=1),
        model=dict(model_extra_configs=dict(dtype="float32", attn_impl="xla")),
    )
    plain = PPOTrainer(plain_cfg, reward_fn=reward_fn, devices=jax.devices()[:1])
    assert_sp_loss_parity(trainer, plain, batch)


def test_sequence_parallel_ilql_end_to_end_and_loss_parity(tmp_path):
    """Context-parallel ILQL (the reference's NeMo-ILQL-under-Megatron-SP
    role, modeling_nemo_ilql.py:612-683): offline RL end-to-end through
    trlx.train on a data x sequence mesh, target-Q Polyak sync on the
    sharded layout, and exact loss parity vs the plain ILQLTrainer on
    identical params/batch."""
    from trlx_tpu.data.default_configs import default_ilql_config
    from trlx_tpu.trainer.ilql_trainer import ILQLTrainer

    config = default_ilql_config().evolve(
        model=dict(model_path="random:llama-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte", padding_side="right"),
        train=dict(seq_length=64, batch_size=4, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="SequenceParallelILQLTrainer",
                   checkpoint_dir=str(tmp_path), seed=5),
        method=dict(steps_for_target_q_sync=1, alpha=1.0,
                    gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0,
                                    temperature=1.0)),
        parallel=dict(data=2, sequence=4),
    )
    samples = [("ask", " yes sir"), ("ask", " no sir"),
               ("question", " maybe so"), ("question", " sure thing")] * 4
    rewards = [1.0, -1.0, 0.5, 0.2] * 4
    trainer = trlx.train(samples=samples, rewards=rewards,
                         eval_prompts=["ask", "question"], config=config)
    assert trainer.iter_count >= 2
    assert trainer.model_cfg.attn_impl == "ring"

    # target heads synced (alpha=1 + sync every step => equal to q heads)
    heads = merge_params(trainer.train_params, trainer.frozen_params)["ilql_heads"]
    for a, b in zip(
        jax.tree_util.tree_leaves(heads["q_head_0"]),
        jax.tree_util.tree_leaves(heads["target_q_head_0"]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    batch = next(iter(trainer.store.create_loader(4, shuffle=False, drop_last=True)))
    plain_cfg = config.evolve(
        train=dict(trainer="ILQLTrainer"),
        parallel=dict(data=1, sequence=1),
        model=dict(model_extra_configs=dict(dtype="float32", attn_impl="xla")),
    )
    plain = ILQLTrainer(plain_cfg, devices=jax.devices()[:1])
    assert_sp_loss_parity(trainer, plain, batch)


def test_sequence_parallel_ppo_validation(tmp_path):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.sequence_parallel_ppo_trainer import SequenceParallelPPOTrainer

    cfg = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny"),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, batch_size=4, tracker=None,
                   checkpoint_dir=str(tmp_path)),
        parallel=dict(data=8, sequence=1),
    )
    with pytest.raises(ValueError, match="sequence > 1"):
        SequenceParallelPPOTrainer(cfg, reward_fn=lambda s, **kw: [0.0] * len(s))
