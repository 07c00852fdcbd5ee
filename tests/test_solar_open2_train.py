"""One PPO cycle through `trlx_tpu.train` at the Solar-Open2 test preset: the
trainers' sampler (the scalar-index cache: K and V by head for the GQA layer,
a recurrent matrix and convolution tails a row for the Kimi-delta layers), the
scorer and a train step through the chunked form's gradient. A file of its own
beside tests/test_solar_open2.py, so that `--dist loadfile` may give it to
another worker."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def test_one_ppo_cycle_through_train_at_solar_tiny(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=16, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3, save_best=False),
        model=dict(model_path="random:solar-open2-tiny", num_layers_unfrozen=2,
                   model_extra_configs=dict(moe_local_experts=4)),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"], eval_prompts=["ab", "cdefg", "e", "ghab"], config=config)
    cfg = trainer.model_cfg
    assert trainer.iter_count == 1 and cfg.has_linear_layers and cfg.has_slot_state and not cfg.has_latent_layers
    assert (cfg.pos_embed, cfg.kda_decay, cfg.kda_beta_max, cfg.attn_gate) == ("none", "softplus", 2.0, "elementwise")
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    # the two blocks that train are Kimi-delta ones: their low-rank pairs move, their selection bias does not
    assert any("f_a_proj" in k for k in train) and any("g_b_proj" in k for k in train)
    frozen_by_design = ("expert_bias",)  # steers the selection, moved by no gradient
    assert [k for k, v in train.items() if not bool(jnp.any(start[k[1:]] != v))
            and not any(n in k for n in frozen_by_design)] == []
