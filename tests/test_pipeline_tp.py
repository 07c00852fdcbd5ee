"""TP x PP and FSDP x PP composition (VERDICT r1 missing #1 / next #4).

The reference's large-model layout is TP=8 x PP=4 x DP simultaneously
(megatron_65b.yaml:49-50, Apex parallel heads inside the pipeline engine,
modeling_nemo_ppo.py:93-121). Here the pipeline mesh carries fsdp/tensor
axes that stay GSPMD-auto INSIDE the GPipe shard_map program
(trlx_tpu/parallel/pipeline.py partial_shard_map): stacked stage params
shard their matrix dims per the TP rule table, and XLA inserts the
Megatron-style collectives. Parity tests pin float32 — bf16 collectives
under partially-manual meshes crash XLA:CPU (see partial_shard_map), and
exact comparisons want f32 anyway.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from trlx_tpu.parallel.pipeline import (
    make_pipe_mesh,
    stack_block_params,
    stacked_param_shardings,
)

from parity import assert_pipelined_loss_parity


def test_pipe_mesh_axes():
    mesh = make_pipe_mesh(2, tensor=2)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert sizes == {"data": 2, "pipe": 2, "fsdp": 1, "tensor": 2, "sequence": 1}
    mesh = make_pipe_mesh(2, fsdp=2)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert sizes == {"data": 2, "pipe": 2, "fsdp": 2, "tensor": 1, "sequence": 1}


def test_stacked_param_shardings_rules():
    """dim 0 rides "pipe"; matrix dims get the TP rule table's splits."""
    from trlx_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=4, n_heads=4,
                            d_ff=128, max_seq_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))
    stacked, _ = stack_block_params(params, cfg.n_layers, 2)
    mesh = make_pipe_mesh(2, tensor=2)
    shardings = stacked_param_shardings(mesh, stacked, n_lead=2)
    flat = {"/".join(str(getattr(k, "key", k)) for k in kp): s
            for kp, s in jax.tree_util.tree_leaves_with_path(shardings)}
    q = flat["attn/q_proj/kernel"].spec
    assert q[0] == "pipe" and q[-1] == "tensor"
    o = flat["attn/o_proj/kernel"].spec
    assert o[0] == "pipe" and o[-2] == "tensor"
    ln = flat["ln_attn/scale"].spec
    assert ln[0] == "pipe" and all(a is None for a in ln[1:])


def _sft_config(tmp_path, trainer, parallel, sub):
    from trlx_tpu.data.default_configs import default_sft_config

    return default_sft_config().evolve(
        # d_model 64 / heads 4 / d_ff 256 all divide tensor=2; f32 for
        # exact parity and the XLA:CPU bf16 partial-manual limitation
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32")),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100, trainer=trainer,
                   checkpoint_dir=str(tmp_path / sub), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=parallel,
    )


@pytest.mark.parametrize("axis", ["tensor", "fsdp"])
def test_pipelined_sft_trainer_tp_fsdp(tmp_path, axis):
    """PipelinedSFTTrainer on a data=2 x pipe=2 x {tensor|fsdp}=2 mesh:
    trains end-to-end via the public API; loss parity vs the plain SFT
    trainer on identical params/batch; stage matrices actually sharded."""
    import trlx_tpu as trlx
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    parallel = dict(data=2, pipeline=2, fsdp=1, tensor=1)
    parallel[axis] = 2
    config = _sft_config(tmp_path, "PipelinedSFTTrainer", parallel, "pp")
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2

    # the stage params really live sharded over the extra axis
    q_kernel = trainer.params["lm_stacked"]["attn"]["q_proj"]["kernel"]
    assert axis in jax.tree_util.tree_leaves(
        [list(q_kernel.sharding.spec)]
    ), f"q_proj not sharded over {axis}: {q_kernel.sharding.spec}"

    plain_cfg = _sft_config(
        tmp_path, "SFTTrainer", dict(data=1, pipeline=1), "plain"
    )
    plain = SFTTrainer(plain_cfg, devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch)


def test_pipelined_ppo_trainer_tp(tmp_path):
    """PipelinedPPOTrainer (train loss + double score pass incl. the
    stacked frozen reference) on data=2 x pipe=2 x tensor=2, with loss AND
    score parity vs the plain PPO trainer."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    def make_config(trainer, parallel, sub):
        return default_ppo_config().evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32")),
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
            "PipelinedPPOTrainer", dict(data=2, pipeline=2, tensor=2), "pp"
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

    # double score pass (policy + stacked frozen ref) parity under TP x PP
    from trlx_tpu.parallel.pipeline import unstack_block_params

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
    lp_pl, _, _, kl_pl, _ = jax.device_get(plain._score_fn(
        traverse_util.flatten_dict(trainer.standard_params()), {},
        ref_std, all_tokens,
    ))
    np.testing.assert_allclose(lp_pp, lp_pl, atol=1e-4)
    np.testing.assert_allclose(float(kl_pp), float(kl_pl), rtol=1e-4, atol=1e-6)
