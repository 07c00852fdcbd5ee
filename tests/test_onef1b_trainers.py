"""The pipelined trainers under `parallel.pipeline_schedule="1f1b"`: each
trains end to end through the public API, and its hand-scheduled grad_fn
matches autodiff of the GPipe loss on identical params and batch. The
schedule itself (grad parity against the GPipe engine, the memory bound)
is `test_onef1b.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np


def _flat_close(a, b, rtol=1e-4, atol=1e-6):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(fa) == len(fb)
    for p, la in fa:
        np.testing.assert_allclose(
            np.asarray(jax.device_get(la)), np.asarray(jax.device_get(fb[p])),
            rtol=rtol, atol=atol, err_msg=str(p),
        )


def _scheduled_and_autodiff(trainer, **loader_kw):
    """(loss, stats, grads) of the store's first batch of 8, from the trainer's
    hand-scheduled `grad_fn` and from autodiff of its GPipe loss."""
    batch = trainer.batch_to_device(next(iter(trainer.store.create_loader(8, shuffle=False, **loader_kw))))
    loss_fn = trainer.make_loss_fn()

    def ref(train_params, frozen_params, batch):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(train_params, frozen_params, batch)
        return loss, stats, grads

    args = (trainer.train_params, trainer.frozen_params, batch)
    return jax.jit(trainer.make_grad_fn())(*args), jax.jit(ref)(*args)


def test_pipelined_sft_trainer_1f1b(tmp_path):
    """PipelinedSFTTrainer with parallel.pipeline_schedule='1f1b': trains
    end-to-end through the public API, and its hand-scheduled grad_fn
    matches autodiff-of-the-GPipe-loss on identical params/batch."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedSFTTrainer",
                   checkpoint_dir=str(tmp_path / "pp1f1b"), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2,
                      pipeline_schedule="1f1b"),
    )
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, s0, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(
        float(s1["loss"]), float(s0["loss"]), rtol=1e-5
    )
    _flat_close(g1, g0)


def test_pipelined_sft_trainer_1f1b_lora(tmp_path):
    """LoRA through the 1F1B schedule: adapters are separate stacked
    leaves, the pipeline must not stop_gradient anything (LoRA split-0 is
    a hydra concern, not a freeze boundary), and the train-key grads
    (adapter leaves only) match autodiff of the GPipe loss."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   peft_config=dict(peft_type="LORA", r=4, lora_alpha=8,
                                    target_modules=["q_proj", "v_proj"]),
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedSFTTrainer",
                   checkpoint_dir=str(tmp_path / "lora1f1b"), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2,
                      pipeline_schedule="1f1b"),
    )
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2
    # adapter-only training partition
    assert all(
        "lora" in "/".join(map(str, k)).lower() for k in trainer.train_params
    )

    (l1, _, g1), (l0, _, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    _flat_close(g1, g0)
    # gradients actually reach the adapters (B starts at zero, so A-grads
    # would vanish if the adapter path were dead — check the B side)
    assert any(
        float(jnp.abs(v).max()) > 0
        for k, v in g1.items() if "lora_b" in "/".join(map(str, k)).lower()
    )


def test_pipelined_ppo_trainer_1f1b(tmp_path):
    """PipelinedPPOTrainer under the 1F1B schedule: full PPO cycle
    end-to-end, plus grad AND stats parity of the per-microbatch
    decomposed ppo_loss against the batch-level one."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedPPOTrainer",
                   checkpoint_dir=str(tmp_path / "ppo1f1b"), seed=3),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=6, do_sample=True)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2,
                      pipeline_schedule="1f1b"),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world", "jax tpu", "pipe line", "ppo test"] * 2,
        config=config,
    )
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, s0, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    _flat_close(s1, s0, rtol=2e-4, atol=1e-5)
    _flat_close(g1, g0, rtol=2e-4, atol=1e-5)


def test_pipelined_ilql_trainer_1f1b(tmp_path):
    """PipelinedILQLTrainer under the 1F1B schedule: offline RL
    end-to-end (incl. Polyak target sync on the stacked layout), plus
    grad AND stats parity of the decomposed ilql_loss — Q-target fit,
    expectile V, CQL, AWAC and the per-head tensor stats all match the
    batch-level computation."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ilql_config

    config = default_ilql_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedILQLTrainer",
                   checkpoint_dir=str(tmp_path / "ilql1f1b"), seed=5),
        method=dict(steps_for_target_q_sync=1, alpha=1.0,
                    gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0,
                                    temperature=1.0)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2,
                      pipeline_schedule="1f1b"),
    )
    samples = [("ask", " yes"), ("ask", " no"), ("q", " maybe"), ("q", " sure")] * 4
    rewards = [1.0, -1.0, 0.5, 0.2] * 4
    trainer = trlx.train(
        samples=samples, rewards=rewards, eval_prompts=["ask", "q"],
        config=config,
    )
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, s0, g0) = _scheduled_and_autodiff(trainer, drop_last=True)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    _flat_close(s1, s0, rtol=2e-4, atol=1e-5)
    _flat_close(g1, g0, rtol=2e-4, atol=1e-5)


def test_pipelined_sft_trainer_1f1b_sequence(tmp_path):
    """PipelinedSFTTrainer on pipe=2 x sequence=2 under the 1F1B
    schedule (the reference's PP x SP 65B layout with the memory
    schedule): trains end-to-end, grad parity vs the GPipe-autodiff loss
    on identical params/batch. seq_length 30 also exercises the
    sequence-divisibility zero-padding (30 % 2 = 0 at full width but
    prompts bucket to ragged widths)."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte", padding_side="right"),
        train=dict(seq_length=30, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedSFTTrainer",
                   checkpoint_dir=str(tmp_path / "pp_sp_1f1b"), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=2, fsdp=1, tensor=1, pipeline=2, sequence=2,
                      pipeline_schedule="1f1b"),
    )
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, _, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    _flat_close(g1, g0)


def test_pipelined_ppo_trainer_1f1b_sequence(tmp_path):
    """PipelinedPPOTrainer on pipe=2 x sequence=2 under the 1F1B schedule
    (r4: the full-token-width loss decomposition — response windows
    preshift to their predicting positions in prepare(), so no shard reads
    a neighbor's window): full PPO cycle end-to-end plus grad AND stats
    parity against the batch-level ppo_loss. This is the deep-model
    long-context RL layout the reference runs as TP x PP x DP + SP
    (megatron_65b.yaml:49-50,:80)."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedPPOTrainer",
                   checkpoint_dir=str(tmp_path / "ppo1f1bsp"), seed=3),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=6, do_sample=True)),
        parallel=dict(data=2, fsdp=1, tensor=1, pipeline=2, sequence=2,
                      pipeline_schedule="1f1b"),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world", "jax tpu", "pipe line", "ppo test"] * 2,
        config=config,
    )
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, s0, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    _flat_close(s1, s0, rtol=2e-4, atol=1e-5)
    _flat_close(g1, g0, rtol=2e-4, atol=1e-5)


def test_pipelined_ilql_trainer_1f1b_sequence(tmp_path):
    """PipelinedILQLTrainer on pipe=2 x sequence=2 under the 1F1B schedule
    (r4: the full-width decomposition of ops/ilql.py — indices preshifted
    to action positions, heads at every position, V all-gathered over the
    sequence axis for the cross-shard state pairings): offline RL
    end-to-end plus grad AND stats parity against the batch-level
    ilql_loss."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ilql_config

    config = default_ilql_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedILQLTrainer",
                   checkpoint_dir=str(tmp_path / "ilql1f1bsp"), seed=5),
        method=dict(steps_for_target_q_sync=1, alpha=1.0,
                    gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0,
                                    temperature=1.0)),
        parallel=dict(data=2, fsdp=1, tensor=1, pipeline=2, sequence=2,
                      pipeline_schedule="1f1b"),
    )
    samples = [("ask", " yes"), ("ask", " no"), ("q", " maybe"), ("q", " sure")] * 4
    rewards = [1.0, -1.0, 0.5, 0.2] * 4
    trainer = trlx.train(
        samples=samples, rewards=rewards, eval_prompts=["ask", "q"],
        config=config,
    )
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, s0, g0) = _scheduled_and_autodiff(trainer, drop_last=True)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)
    _flat_close(s1, s0, rtol=2e-4, atol=1e-5)
    _flat_close(g1, g0, rtol=2e-4, atol=1e-5)


def test_pipelined_sft_trainer_interleaved_1f1b(tmp_path):
    """PipelinedSFTTrainer with pipeline_interleave=2 x
    pipeline_schedule='1f1b' end-to-end, plus grad parity vs the
    interleaved-GPipe loss on identical params/batch — the composition the
    reference ships as virtual-PP buckets through its Apex 1F1B engine
    (modeling_nemo_ppo.py:573-585 + :713-731)."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32", n_layers=4)),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedSFTTrainer",
                   checkpoint_dir=str(tmp_path / "inter1f1b"), seed=5),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2,
                      pipeline_interleave=2, pipeline_schedule="1f1b"),
    )
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2

    (l1, s1, g1), (l0, _, g0) = _scheduled_and_autodiff(trainer)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    _flat_close(g1, g0)
