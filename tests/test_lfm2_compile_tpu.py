"""`lfm2-8b-a1b.ppo-hh`'s programs, compiled for one v5e chip with no chip: the
expert layer forward and backward, a cached decode step and the train step
in outline, and the trainer's score program that hands out the trunk state,
at the cell's widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    abstract, arena_rewrites, BF16, F32, I32, instructions_of_at_least, kernel_names, LFM2,
    pallas_mode, ppo_cell_trainer, S, traced_score, v5e,
)


def _expert_stacks(params) -> list:
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if any("expert_" in str(getattr(k, "key", k)) for k in path) and leaf.ndim == 2]


@pytest.mark.parametrize("tokens", [16 * 1024, 64])
def test_expert_layer_compiles_and_leaves_its_stacks_where_they_lie(v5e, pallas_mode, tokens):
    """`SparseMoE` forward and backward at the cell's shapes: the three
    grouped products carry their names, and no step re-lays an expert stack
    or its gradient: the float32 leaves are `[fan_in, experts x fan_out]`,
    the kernels read a column block of the bfloat16 cast and write a column
    block of the gradient (a `[fan_in, experts, fan_out]` stack cost a
    transposing copy of every gradient, 117 MB each, PR 29)."""
    from trlx_tpu.models import config_from_preset
    from trlx_tpu.models.transformer import SparseMoE

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    layer = SparseMoE(cfg)
    one = SingleDeviceSharding(v5e[0])
    x = S((max(tokens // 1024, 1), min(tokens, 1024), cfg.d_model), BF16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, BF16))["params"])
    stacks = _expert_stacks(params)
    assert sorted(s.shape for s in stacks) == [(1792, 8 * 2048), (2048, 8 * 1792), (2048, 8 * 1792)]

    def loss(p, h):
        return (layer.apply({"params": p}, h).astype(F32) ** 2).sum()

    args = abstract((params, x), one)
    fwd = jax.jit(lambda p, h: layer.apply({"params": p}, h)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(kernel_names(fwd)) == ["moe_gmm"] * 3
    assert arena_rewrites(fwd, *stacks) == []
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(kernel_names(bwd)) == ["moe_gmm"] * 3 + ["moe_gmm_dlhs"] * 3 + ["moe_tgmm"] * 3
    assert arena_rewrites(bwd, *stacks) == []


def _lfm2_decode_step(v5e, leaves=F32):
    """One cached step of the cell's 10-layer model, 64 rows over a cache of
    1024, through the K/V tables and the convolution states, compiled for
    one v5e chip with parameters of type `leaves`: the program, the
    parameters' shapes and the cache's."""
    from trlx_tpu.models import config_from_preset, init_kv_cache
    from trlx_tpu.models.transformer import TransformerLM

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    b, total = 64, 1024
    tokens = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    params = jax.tree_util.tree_map(lambda a: S(a.shape, leaves), params)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, b, total))
    assert [sorted(layer) for layer in cache["layers"]] == [
        ["conv"] if kind == "conv" else ["k", "v"] for kind in cfg.layer_types]

    def step(p, tok, c, mask):
        return model.apply({"params": p}, tok, c, mask, False, method=TransformerLM.decode_step)

    compiled = jax.jit(step, donate_argnums=(2,)).trace(
        *abstract((params, S((b, 1), I32), cache, S((b, 1), I32)), one)).lower(
        lowering_platforms=("tpu",)).compile()
    return compiled, params, cache


def test_lfm2_decode_step_compiles_without_copying_an_expert_stack(v5e, pallas_mode):
    compiled, params, _ = _lfm2_decode_step(v5e)
    assert kernel_names(compiled).count("moe_gmm") == 3 * 8
    assert arena_rewrites(compiled, *_expert_stacks(params)) == []


def test_lfm2_decode_step_reads_its_kv_cache_once_for_all_query_heads(v5e, pallas_mode):
    """The two attention layers contract 32 query heads against a cache of 8
    kv heads. Repeating K and V to the query heads first made every step
    write and read `[64, 1024, 8, 4, 64]` broadcasts, float32 and bfloat16
    (5.83 GB a step by the compiler's count, over the sampler's bfloat16
    copy of the parameters); the grouped contraction reads the cache where
    it lies (3.03 GB): nothing the size of a repeated cache is left."""
    compiled, _, cache = _lfm2_decode_step(v5e, BF16)
    k = next(layer["k"] for layer in cache["layers"] if "k" in layer)
    assert k.shape == (64, 1024, 8, 64)
    repeated = int(np.prod(k.shape)) * 4  # 8 kv heads -> 32 query heads
    assert instructions_of_at_least(compiled, repeated) == []
    assert compiled.cost_analysis()["bytes accessed"] < 3.5e9


def test_lfm2_train_step_fits_the_chip_at_batch_16(v5e, pallas_mode, capsys):
    """The cell's train step in outline (windowed head over the 128 response
    positions, gradients of the top two blocks, AdamW), compiled for one
    v5e chip at 16 x 1024: the compiler's own account of its memory, beside
    the float32 leaves it is handed, has to leave room in 16 GB."""
    import optax
    from flax.traverse_util import flatten_dict, unflatten_dict

    from trlx_tpu.models import CausalLMWithValueHead, config_from_preset
    from trlx_tpu.models.policy import trainable_mask
    from trlx_tpu.utils.modeling import logprobs_of_labels

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    model = CausalLMWithValueHead(cfg)
    one = SingleDeviceSharding(v5e[0])
    b, t, new = 16, 1024, 128
    probe = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), probe, jnp.ones_like(probe))["params"])
    flat, mask = flatten_dict(params), flatten_dict(trainable_mask(params, cfg, 2))
    train = {k: v for k, v in flat.items() if mask[k]}
    frozen = {k: v for k, v in flat.items() if not mask[k]}
    assert not any("expert_bias" in k for k in train)
    opt = optax.adamw(6e-6)
    opt_state = jax.eval_shape(opt.init, train)

    def train_step(train, frozen, opt_state, tokens, attn_mask):
        def loss(train):
            logits, values, _ = model.apply(
                {"params": unflatten_dict({**train, **frozen})}, tokens, attn_mask,
                window=(t - new - 1, new), method=CausalLMWithValueHead.forward)
            return -logprobs_of_labels(logits, tokens[:, t - new:]).mean() + (values ** 2).mean()

        grads = jax.grad(loss)(train)
        updates, opt_state_new = opt.update(grads, opt_state, train)
        return optax.apply_updates(train, updates), opt_state_new

    compiled = jax.jit(train_step, donate_argnums=(0, 2)).trace(
        *abstract((train, frozen, opt_state, S((b, t), I32), S((b, t), I32)), one)).lower(
        lowering_platforms=("tpu",)).compile()
    assert arena_rewrites(compiled, *_expert_stacks(params)) == []
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(v.shape)) * 4 for v in flat.values())
    with capsys.disabled():
        print(f"\nlfm2-8b-a1b train step at {b} x {t} for v5e: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB, "
              f"float32 leaves {held / 1e9:.2f} GB")
    # beside this program the process holds the reference copy of the top
    # blocks (0.84 GB) and the sampler's bfloat16 view while it runs
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5e9


def test_lfm2_score_program_with_the_trunk_state_compiles_and_says_what_it_holds(
        v5e, pallas_mode, tmp_path, capsys):
    """`lfm2-8b-a1b.ppo-hh` collects in one chunk of 64 x 1,024, so its
    score program hands out the state entering block 8 as a sixth output
    (`_score_hands_out_trunk_state`): under the name the device trace knows
    (`jit_score`), compiled for one v5e chip, its outputs are the
    five-output program's and 64 x 1,024 x 2,048 bfloat16 states, 268 MB
    that stand on the device from the score to the cycle's last step in
    the fill's place."""
    b, t = 64, 1024
    trainer = ppo_cell_trainer(tmp_path, "lfm2-8b-a1b", LFM2, batch_size=16, num_rollouts=b,
                            chunk_size=b, max_new=128)
    assert trainer._score_hands_out_trunk_state()
    five, six = (traced_score(trainer, v5e[0], b, t, hands_out) for hands_out in (False, True))
    assert [(o.shape, o.dtype) for o in six.out_info] == [
        *((o.shape, o.dtype) for o in five.out_info), ((b, t, trainer.model_cfg.d_model), BF16)]
    five_bytes = sum(int(np.prod(o.shape)) * o.dtype.itemsize for o in five.out_info)
    assert five_bytes == 3 * b * (t - 1) * 4 + 2 * 4
    lowered = six.lower(lowering_platforms=("tpu",))
    assert "module @jit_score " in lowered.as_text()[:200]
    memory = lowered.compile().memory_analysis()
    with capsys.disabled():
        print(f"\nlfm2-8b-a1b score at {b} x {t} for v5e with the trunk state: outputs "
              f"{memory.output_size_in_bytes / 1e6:.1f} MB ({five_bytes / 1e6:.1f} MB without it), "
              f"temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB, arguments "
              f"{memory.argument_size_in_bytes / 1e9:.2f} GB")
    want = five_bytes + b * t * trainer.model_cfg.d_model * 2
    # (the compiler rounds every output buffer up to its tile)
    assert want <= memory.output_size_in_bytes <= want + 64 * 1024
