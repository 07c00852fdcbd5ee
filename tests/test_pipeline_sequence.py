"""PP x SP composition (VERDICT r2 missing #2).

The reference's 65B layout is TP=8 x PP=4 *with* sequence_parallel: True
(configs/nemo_configs/megatron_65b.yaml:49-50, :80 — Megatron SP shards
activations within a TP group). Here the pipe mesh carries a manual
"sequence" axis and every GPipe stage runs ring attention over it
(trlx_tpu/parallel/pipeline.py), so long-context x deep-model configs
have a path — and context length scales with chips, beyond what Megatron
SP can do. Parity tests pin float32 (XLA:CPU bf16 partial-manual
limitation, parallel/context.py) and compare against the plain
single-program trainers on identical params.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

import trlx_tpu as trlx
from trlx_tpu.data.default_configs import default_ppo_config, default_sft_config

from parity import assert_pipelined_loss_parity


def _sft_config(tmp_path, trainer, parallel, sub, padding_side="right"):
    return default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32", n_layers=4)),
        tokenizer=dict(tokenizer_path="byte", padding_side=padding_side),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100, trainer=trainer,
                   checkpoint_dir=str(tmp_path / sub), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=parallel,
    )


def test_pipe_mesh_has_sequence_axis():
    from trlx_tpu.parallel.pipeline import make_pipe_mesh

    mesh = make_pipe_mesh(2, sequence=2)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert sizes == {"data": 2, "pipe": 2, "fsdp": 1, "tensor": 1, "sequence": 2}


def test_sft_left_padding_refused(tmp_path):
    from trlx_tpu.trainer.pipelined_sft_trainer import PipelinedSFTTrainer

    config = _sft_config(tmp_path, "PipelinedSFTTrainer",
                         dict(data=2, pipeline=2, sequence=2), "lp",
                         padding_side="left")
    with pytest.raises(ValueError, match="padding_side"):
        PipelinedSFTTrainer(config)


def test_sp_pins_ring(tmp_path):
    from trlx_tpu.trainer.pipelined_sft_trainer import PipelinedSFTTrainer

    config = _sft_config(tmp_path, "PipelinedSFTTrainer",
                         dict(data=2, pipeline=2, sequence=2), "ring")
    trainer = PipelinedSFTTrainer(config)
    assert trainer.model_cfg.attn_impl == "ring"


def test_pipelined_sft_sp_parity(tmp_path):
    """PipelinedSFTTrainer on data=2 x pipe=2 x sequence=2: trains
    end-to-end; loss parity vs the plain SFT trainer on identical params.
    Sample lengths force an odd batch width so the transparent pad-up
    wrapper engages."""
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = _sft_config(tmp_path, "PipelinedSFTTrainer",
                         dict(data=2, pipeline=2, sequence=2), "pp")
    # 25/23 chars -> odd max width in the batch (pad-up wrapper engages)
    samples = ["hello world this is texts", "another training sample"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2

    plain = SFTTrainer(
        _sft_config(tmp_path, "SFTTrainer", dict(data=1, pipeline=1), "plain"),
        devices=jax.devices()[:1],
    )
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert np.asarray(batch["input_ids"]).shape[1] % 2 == 1
    assert_pipelined_loss_parity(trainer, plain, batch)


def test_decode_view_under_tp_sp(tmp_path):
    """standard_params + generate on a pipe=2 x tensor=2 x sequence=2 mesh:
    the decode mesh must keep the training mesh's flat device order
    (adjacent-axis merge), or the jitted rebuild fails with a device
    assignment mismatch."""
    from trlx_tpu.trainer.pipelined_sft_trainer import PipelinedSFTTrainer

    config = _sft_config(tmp_path, "PipelinedSFTTrainer",
                         dict(data=1, pipeline=2, tensor=2, sequence=2), "tpsp")
    trainer = PipelinedSFTTrainer(config)
    sizes = dict(zip(trainer.runtime.decode_mesh.axis_names,
                     trainer.runtime.decode_mesh.devices.shape))
    assert sizes == {"data": 1, "fsdp": 2, "tensor": 4}
    std = trainer.standard_params()
    for kp, leaf in jax.tree_util.tree_leaves_with_path(std):
        if leaf.ndim >= 2 and leaf.size >= 4096:
            assert not leaf.sharding.is_fully_replicated, kp
    out = trainer.generate(np.full((4, 8), 104, np.int32),
                           np.ones((4, 8), np.int32))
    assert np.asarray(out["response_tokens"]).shape == (4, 4)


def test_pipelined_ppo_sp_parity(tmp_path):
    """PipelinedPPOTrainer on pipe=2 x sequence=2 (left-padded queries —
    PPO only consumes logits at valid positions): rollouts + training
    end-to-end, then loss AND double-score-pass parity vs the plain PPO
    trainer."""
    from trlx_tpu.parallel.pipeline import unstack_block_params
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    def make_config(trainer, parallel, sub):
        return default_ppo_config().evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32", n_layers=4)),
            tokenizer=dict(tokenizer_path="byte"),
            train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                       eval_interval=10, checkpoint_interval=100, trainer=trainer,
                       checkpoint_dir=str(tmp_path / sub), seed=3),
            method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                        gen_kwargs=dict(max_new_tokens=6, do_sample=True)),
            parallel=parallel,
        )

    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world", "jax tpu", "pipe line", "ppo test"] * 2,
        config=make_config(
            "PipelinedPPOTrainer", dict(data=2, pipeline=2, sequence=2), "pp"
        ),
    )
    assert trainer.iter_count >= 2

    plain = PPOTrainer(
        make_config("PPOTrainer", dict(data=1, pipeline=1), "plain"),
        reward_fn=lambda samples, **kw: [0.0] * len(samples),
        devices=jax.devices()[:1],
    )
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch)

    trainer._build_score_fn()
    all_tokens = jnp.concatenate(
        [jnp.asarray(batch.query_tensors), jnp.asarray(batch.response_tensors)],
        axis=1,
    )
    lp_pp, _, _, kl_pp, _ = jax.device_get(trainer._score_fn(
        traverse_util.flatten_dict(dict(trainer.params)), {},
        trainer.ref_params, all_tokens,
    ))
    plain._build_score_fn()
    ref_std = unstack_block_params(
        trainer.ref_params["lm_stacked"], trainer.ref_params["lm_rest"],
        trainer.model_cfg.n_layers,
    )
    std_host = jax.tree_util.tree_map(np.asarray, trainer.standard_params())
    lp_pl, _, _, kl_pl, _ = jax.device_get(plain._score_fn(
        traverse_util.flatten_dict(std_host), {}, ref_std, all_tokens,
    ))
    # mask pad-position entries: under left padding the logit feeding a
    # pad-position logprob has no valid context (see PipelinedCausalMixin
    # docstring); PPO itself never consumes those entries
    mask = (np.asarray(all_tokens) != trainer.tokenizer.pad_token_id)[:, :-1]
    np.testing.assert_allclose(lp_pp * mask, lp_pl * mask, atol=1e-4)
    np.testing.assert_allclose(float(kl_pp), float(kl_pl), rtol=1e-4, atol=1e-6)


def test_pipelined_ilql_sp_parity(tmp_path):
    """PipelinedILQLTrainer on pipe=2 x sequence=2: offline RL through the
    GPipe x ring-attention program end-to-end (the ILQL gathers run on the
    replicated final hidden state OUTSIDE the shard_map, so state/action
    index selects never cross sequence shards), with loss parity vs the
    plain ILQL trainer on identical params/batch."""
    from trlx_tpu.data.default_configs import default_ilql_config
    from trlx_tpu.trainer.ilql_trainer import ILQLTrainer

    def make_config(trainer, parallel, sub):
        return default_ilql_config().evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32", n_layers=4)),
            tokenizer=dict(tokenizer_path="byte"),
            train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                       eval_interval=10, checkpoint_interval=100, trainer=trainer,
                       checkpoint_dir=str(tmp_path / sub), seed=5),
            method=dict(steps_for_target_q_sync=1, alpha=1.0,
                        gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0,
                                        temperature=1.0)),
            parallel=parallel,
        )

    samples = [("ask", " yes"), ("ask", " no"), ("q", " maybe"), ("q", " sure")] * 4
    rewards = [1.0, -1.0, 0.5, 0.2] * 4
    trainer = trlx.train(
        samples=samples, rewards=rewards, eval_prompts=["ask", "q"],
        config=make_config(
            "PipelinedILQLTrainer", dict(data=2, pipeline=2, sequence=2), "pp"
        ),
    )
    assert trainer.iter_count >= 2

    plain = ILQLTrainer(
        make_config("ILQLTrainer", dict(data=1, pipeline=1), "plain"),
        devices=jax.devices()[:1],
    )
    batch = next(iter(trainer.store.create_loader(8, shuffle=False, drop_last=True)))
    assert_pipelined_loss_parity(trainer, plain, batch)
