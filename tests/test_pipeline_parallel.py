"""GPipe pipeline parallelism vs the sequential forward.

The reference's PP correctness is untested in its CI (SURVEY.md §4: NeMo
never installed); here the pipeline schedule is validated exactly against
the single-program forward on the virtual CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.parallel.pipeline import (
    make_gpipe_forward,
    make_pipe_mesh,
    stack_block_params,
)

from parity import assert_pipelined_loss_parity


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=8, n_heads=4, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.arange(8 * 16).reshape(8, 16) % 89, jnp.int32)
    mask = np.ones((8, 16), np.int32)
    mask[3, -5:] = 0  # right padding on one row
    mask = jnp.asarray(mask)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)
    return cfg, model, params, tokens, mask


def test_stack_block_params_roundtrip(setup):
    cfg, model, params, *_ = setup
    stacked, rest = stack_block_params(params, cfg.n_layers, 2)
    leaf = jax.tree_util.tree_leaves(stacked)[0]
    assert leaf.shape[:2] == (2, cfg.n_layers // 2)
    assert "embed_tokens" in rest and not any(k.startswith("block_") for k in rest)


@pytest.mark.parametrize("n_stages,n_mb", [(4, 4), (2, 2), (8, 2)])
def test_gpipe_matches_sequential(setup, n_stages, n_mb):
    cfg, model, params, tokens, mask = setup
    if cfg.n_layers % n_stages != 0:
        pytest.skip("layers not divisible")
    mesh = make_pipe_mesh(n_stages)
    fwd = jax.jit(make_gpipe_forward(model, cfg, mesh, n_stages, n_mb))
    logits_pp = fwd(params, tokens, mask)
    logits_seq, _, _ = jax.jit(model.apply)(params, tokens, mask)
    valid = np.asarray(mask)[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, np.asarray(logits_pp), 0),
        np.where(valid, np.asarray(logits_seq), 0),
        atol=1e-4, rtol=1e-4,
    )


def test_gpipe_fused_attention_matches_sequential(setup):
    """The pipeline stage must forward attn_mask so fused (flash) attention
    engages instead of silently falling back to the O(t^2) dense path."""
    cfg, model, params, tokens, mask = setup
    from dataclasses import replace

    fcfg = replace(cfg, attn_impl="flash")
    fmodel = TransformerLM(fcfg)
    mesh = make_pipe_mesh(4)
    fwd = jax.jit(make_gpipe_forward(fmodel, fcfg, mesh, 4, 4))
    logits_pp = fwd(params, tokens, mask)
    logits_seq, _, _ = jax.jit(model.apply)(params, tokens, mask)
    valid = np.asarray(mask)[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, np.asarray(logits_pp), 0),
        np.where(valid, np.asarray(logits_seq), 0),
        atol=1e-4, rtol=1e-4,
    )


def test_gpipe_gradients_match_sequential(setup):
    """Autodiff through the pipeline (reverse schedule via ppermute
    transpose) produces the same parameter gradients."""
    cfg, model, params, tokens, mask = setup
    mesh = make_pipe_mesh(4)
    fwd = make_gpipe_forward(model, cfg, mesh, 4, 4)

    def loss_pp(p):
        return jnp.mean(fwd(p, tokens, mask) ** 2)

    def loss_seq(p):
        return jnp.mean(model.apply(p, tokens, mask)[0] ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    flat_pp = jax.tree_util.tree_leaves_with_path(g_pp)
    flat_seq = dict(jax.tree_util.tree_leaves_with_path(g_seq))
    assert len(flat_pp) == len(flat_seq)
    for path, leaf in flat_pp:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_seq[path]), atol=1e-4, rtol=1e-4,
            err_msg=str(path),
        )


def test_pipelined_sft_trainer(tmp_path):
    """PipelinedSFTTrainer: GPipe train step through the registered
    trainer family on a (data=2, pipe=2) mesh — runs end-to-end via the
    public train() API, matches the plain SFT trainer's loss on identical
    params/batch, and exports the standard HF layout."""

    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config

    def make_config(trainer, pipeline, tmp_sub):
        return default_sft_config().evolve(
            # f32 so the loss-parity check is exact (bf16 accumulation
            # order differs between microbatch sizes)
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32")),
            tokenizer=dict(tokenizer_path="byte"),
            train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                       eval_interval=10, checkpoint_interval=100, trainer=trainer,
                       checkpoint_dir=str(tmp_path / tmp_sub), seed=11),
            method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
            # data x pipeline must cover the full 8-device CPU mesh
            parallel=dict(data=8 // pipeline if pipeline > 1 else 2,
                          fsdp=1, tensor=1, pipeline=pipeline),
        )

    samples = ["hello world this is text", "another training sample here"] * 8

    trainer = trlx.train(
        samples=samples,
        eval_prompts=["hello", "another"],
        config=make_config("PipelinedSFTTrainer", 2, "pp"),
    )
    assert trainer.iter_count >= 2

    # loss parity on identical params/batch: pipelined loss == plain loss
    plain_cfg = make_config("SFTTrainer", 1, "plain")
    plain_cfg.parallel.data = 1
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    plain = SFTTrainer(plain_cfg, devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch)

    # HF export goes through the standard layout
    trainer.save_pretrained(str(tmp_path / "hf"))
    import os

    assert os.path.exists(str(tmp_path / "hf" / "pytorch_model.bin"))


def test_pipelined_ilql_trainer(tmp_path):
    """PipelinedILQLTrainer: offline RL through the GPipe program (the
    NeMo ILQL role) — runs end-to-end via the public train() API,
    matches the plain ILQL trainer's loss on identical params/batch,
    target-Q Polyak sync works on the stacked layout."""
    import numpy as np

    import jax
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ilql_config

    def make_config(trainer, pipeline, sub):
        return default_ilql_config().evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32")),
            tokenizer=dict(tokenizer_path="byte"),
            train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                       eval_interval=10, checkpoint_interval=100, trainer=trainer,
                       checkpoint_dir=str(tmp_path / sub), seed=5),
            method=dict(steps_for_target_q_sync=1, alpha=1.0,
                        gen_kwargs=dict(max_new_tokens=4, top_k=4, beta=1.0,
                                        temperature=1.0)),
            parallel=dict(data=8 // pipeline if pipeline > 1 else 1,
                          fsdp=1, tensor=1, pipeline=pipeline),
        )

    samples = [("ask", " yes"), ("ask", " no"), ("q", " maybe"), ("q", " sure")] * 4
    rewards = [1.0, -1.0, 0.5, 0.2] * 4

    trainer = trlx.train(
        samples=samples, rewards=rewards, eval_prompts=["ask", "q"],
        config=make_config("PipelinedILQLTrainer", 2, "pp"),
    )
    assert trainer.iter_count >= 2

    # target heads synced (alpha=1 + sync every step => equal to q heads)
    heads = trainer.params["ilql_heads"]
    for a, b in zip(
        jax.tree_util.tree_leaves(heads["q_head_0"]),
        jax.tree_util.tree_leaves(heads["target_q_head_0"]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    # loss parity vs the plain trainer on identical params/batch
    from trlx_tpu.trainer.ilql_trainer import ILQLTrainer

    plain = ILQLTrainer(make_config("ILQLTrainer", 1, "plain"),
                        devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(8, shuffle=False, drop_last=True)))
    assert_pipelined_loss_parity(trainer, plain, batch)


def test_pipelined_ppo_trainer(tmp_path):
    """PipelinedPPOTrainer: the full PPO cycle (generate -> score via a
    DOUBLE pipelined pass incl. the stacked frozen reference -> optimize
    through the GPipe loss) end-to-end via the public train() API — the
    NeMo PPO role. Loss parity vs the plain PPO trainer on identical
    params/batch."""
    import numpy as np

    import jax
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    def make_config(trainer, pipeline, sub):
        return default_ppo_config().evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                       model_extra_configs=dict(dtype="float32")),
            tokenizer=dict(tokenizer_path="byte"),
            train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                       eval_interval=10, checkpoint_interval=100, trainer=trainer,
                       checkpoint_dir=str(tmp_path / sub), seed=3),
            method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                        gen_kwargs=dict(max_new_tokens=6, do_sample=True)),
            parallel=dict(data=8 // pipeline if pipeline > 1 else 1,
                          fsdp=1, tensor=1, pipeline=pipeline),
        )

    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world", "jax tpu", "pipe line", "ppo test"] * 2,
        config=make_config("PipelinedPPOTrainer", 2, "pp"),
    )
    assert trainer.iter_count >= 2

    # loss parity vs the plain PPO trainer on identical params/batch
    from flax import traverse_util
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    plain = PPOTrainer(make_config("PPOTrainer", 1, "plain"),
                       reward_fn=lambda samples, **kw: [0.0] * len(samples),
                       devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch)

    # score-fn parity incl. the KL stat ORDER (regression: a swapped
    # (mean_kl, mean_kl_per_token) pair feeds the adaptive KL controller
    # a value ~seq_len too small)
    import jax.numpy as jnp

    trainer._build_score_fn()
    all_tokens = jnp.concatenate(
        [jnp.asarray(batch.query_tensors), jnp.asarray(batch.response_tensors)], axis=1
    )
    lp_pp, _, _, kl_pp, klt_pp = jax.device_get(trainer._score_fn(
        traverse_util.flatten_dict(dict(trainer.params)), {},
        trainer.ref_params, all_tokens,
    ))
    plain._build_score_fn()
    std = trainer.standard_params()
    from trlx_tpu.parallel.pipeline import unstack_block_params

    ref_std = unstack_block_params(
        trainer.ref_params["lm_stacked"], trainer.ref_params["lm_rest"],
        trainer.model_cfg.n_layers,
    )
    lp_pl, _, _, kl_pl, klt_pl = jax.device_get(plain._score_fn(
        traverse_util.flatten_dict(std), {}, ref_std, all_tokens,
    ))
    np.testing.assert_allclose(lp_pp, lp_pl, atol=1e-4)
    np.testing.assert_allclose(float(kl_pp), float(kl_pl), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(klt_pp), float(klt_pl), rtol=1e-4, atol=1e-6)


def test_pipelined_rft_trainer(tmp_path):
    """PipelinedRFTTrainer: rejection-sampling fine-tuning with the CE
    loss through the GPipe program, end-to-end via the public API."""
    import trlx_tpu as trlx
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.rft_trainer import RFTConfig

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedRFTTrainer",
                   checkpoint_dir=str(tmp_path)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2),
    )
    config.method = RFTConfig(
        name="RFTConfig", n_generations_per_prompt=2, start_percentile=0.4,
        end_percentile=0.9, n_improve_steps=1,
        gen_kwargs=dict(max_new_tokens=4, do_sample=True),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        prompts=["hello world", "jax tpu", "pipe line", "rft test"] * 4,
        config=config,
    )
    # real optimizer steps ran (an empty drop_last loader would silently
    # train nothing)
    assert trainer.iter_count >= 1

    # loss parity vs the plain RFT trainer on identical params/batch
    from trlx_tpu.trainer.rft_trainer import RFTTrainer

    plain_cfg = config.evolve(train=dict(trainer="RFTTrainer"),
                              parallel=dict(data=1, pipeline=1))
    plain = RFTTrainer(plain_cfg, reward_fn=lambda s, **kw: [0.0] * len(s),
                       devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(
        min(trainer.config.train.batch_size, len(trainer.store)), shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch, rtol=2e-3)


# ---------------------------------------------------------------------------
# Interleaved (virtual-stage) schedule
# ---------------------------------------------------------------------------


def test_interleaved_stack_roundtrip(setup):
    from trlx_tpu.parallel.pipeline import (
        stack_block_params_interleaved,
        unstack_block_params,
        unstack_block_params_interleaved,
    )

    cfg, model, params, *_ = setup
    stacked, rest = stack_block_params_interleaved(params, cfg.n_layers, 2, 2)
    leaf = jax.tree_util.tree_leaves(stacked)[0]
    assert leaf.shape[:3] == (2, 2, cfg.n_layers // 4)
    rebuilt = unstack_block_params_interleaved(stacked, rest, cfg.n_layers, 2)
    ref = params["params"] if "params" in params else params
    flat_a = dict(jax.tree_util.tree_leaves_with_path(rebuilt))
    flat_b = dict(jax.tree_util.tree_leaves_with_path(dict(ref)))
    assert flat_a.keys() == flat_b.keys()
    for k in flat_a:
        np.testing.assert_array_equal(np.asarray(flat_a[k]), np.asarray(flat_b[k]))


@pytest.mark.parametrize("n_stages,n_mb,n_virtual", [(4, 4, 2), (2, 2, 4), (4, 2, 2)])
def test_interleaved_matches_sequential(setup, n_stages, n_mb, n_virtual):
    """The interleaved schedule (each device holds n_virtual round-robin
    chunks; microbatches loop the ring n_virtual times) is numerically the
    same forward as the single-program model."""
    cfg, model, params, tokens, mask = setup
    mesh = make_pipe_mesh(n_stages)
    fwd = jax.jit(make_gpipe_forward(model, cfg, mesh, n_stages, n_mb, n_virtual=n_virtual))
    logits_pp = fwd(params, tokens, mask)
    logits_seq, _, _ = jax.jit(model.apply)(params, tokens, mask)
    valid = np.asarray(mask)[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, np.asarray(logits_pp), 0),
        np.where(valid, np.asarray(logits_seq), 0),
        atol=1e-4, rtol=1e-4,
    )


def test_interleaved_gradients_match_sequential(setup):
    cfg, model, params, tokens, mask = setup
    mesh = make_pipe_mesh(4)
    fwd = make_gpipe_forward(model, cfg, mesh, 4, 4, n_virtual=2)

    def loss_pp(p):
        return jnp.mean(fwd(p, tokens, mask) ** 2)

    def loss_seq(p):
        return jnp.mean(model.apply(p, tokens, mask)[0] ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    flat_pp = jax.tree_util.tree_leaves_with_path(g_pp)
    flat_seq = dict(jax.tree_util.tree_leaves_with_path(g_seq))
    assert len(flat_pp) == len(flat_seq)
    for path, leaf in flat_pp:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_seq[path]), atol=1e-4, rtol=1e-4,
            err_msg=str(path),
        )


def test_pipelined_sft_trainer_interleaved(tmp_path):
    """End-to-end: PipelinedSFTTrainer with pipeline_interleave=2 trains
    through the public API and its loss matches the plain SFT trainer on
    the unstacked param view."""
    import trlx_tpu as trlx
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        # 4 layers so 2 stages x 2 virtual chunks divide evenly
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=-1,
                   model_extra_configs=dict(dtype="float32", n_layers=4)),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=2, tracker=None,
                   eval_interval=10, checkpoint_interval=100,
                   trainer="PipelinedSFTTrainer",
                   checkpoint_dir=str(tmp_path), seed=11),
        method=dict(gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
        parallel=dict(data=4, fsdp=1, tensor=1, pipeline=2, pipeline_interleave=2),
    )
    samples = ["hello world this is text", "another training sample here"] * 8
    trainer = trlx.train(samples=samples, eval_prompts=["hello"], config=config)
    assert trainer.iter_count >= 2
    assert trainer._n_virtual == 2

    plain_cfg = config.evolve(train=dict(trainer="SFTTrainer"),
                              parallel=dict(data=1, pipeline=1, pipeline_interleave=1))
    plain = SFTTrainer(plain_cfg, devices=jax.devices()[:1])
    batch = next(iter(trainer.store.create_loader(8, shuffle=False)))
    assert_pipelined_loss_parity(trainer, plain, batch)
