"""One PPO cycle through `trlx_tpu.train` at the Falcon-H1 test preset: the
trainers' sampler (the scalar-index cache: in every layer K and V by head AND
a recurrent matrix and convolution tails a row), the scorer and a train step
through `ssd_chunked`'s gradient and the forward multipliers. No cell trains
this family (no cut of it fits a chip at 16 bytes a parameter). A file of its
own beside tests/test_falcon_h1.py, so that `--dist loadfile` may give it to
another worker."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def test_one_ppo_cycle_through_train_at_falcon_h1_tiny(tmp_path):
    import trlx_tpu as trlx
    from flax.traverse_util import flatten_dict

    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=16, epochs=1, total_steps=1, batch_size=4, checkpoint_interval=100,
                   eval_interval=100, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), seed=3, save_best=False),
        model=dict(model_path="random:falcon-h1-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="char:abcdefgh"),
        optimizer=dict(name="adamw", kwargs=dict(lr=1e-2)),
        method=dict(num_rollouts=4, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cdefg", "e", "ghab"], eval_prompts=["ab", "cdefg", "e", "ghab"], config=config)
    cfg = trainer.model_cfg
    assert trainer.iter_count == 1 and cfg.has_ssm_layers and cfg.has_slot_state
    assert cfg.attention_kinds == ("ssm_attention",) and cfg.multipliers.lm_head == 0.0078125
    start = flatten_dict(trainer.ref_params)
    train = {k: v for k, v in trainer.train_params.items() if k[1:] in start}
    # the block that trains: both mixers' leaves move, the heads' three vectors among them (through the chunked form)
    moved = {k for k, v in train.items() if bool(jnp.any(start[k[1:]] != v))}
    for leaf in ("in_proj", "conv1d", "a_log", "dt_bias", "d", "norm", "out_proj", "q_proj", "k_proj", "gate_proj"):
        assert any(leaf in k for k in moved), leaf
    assert sorted(k for k in train if k not in moved) == []
