"""The `serve_parallel_hybrid` job: `serve` (bench/jobs/serve.py: window,
traffic, logprob comparison, fallbacks, compiles in the window, all its own
code) for a configuration whose EVERY layer keeps two kinds of state at once:
keys and values by head a TOKEN (its attention branch, in the paged arena) and
a recurrent matrix and convolution tails a SLOT (its Mamba-2 branch, beside
the arena). As `serve_latent`, `serve_hybrid` and `serve_kv_hybrid`, what
differs is the count `check_kv_precision` holds the pool's bytes to: this
module binds that one name in `jobs/serve.py`, for this process, to its own
count, and calls `serve.run(ctx)`.

The count is made from the configuration file's published keys and stated
precisions, not from the program's config object:

    (blocks + 1) x block x layers x 2 x num_key_value_heads x head_dim x bytes(kv_cache)
  + slots x layers x (mamba_n_heads x mamba_d_head x mamba_d_state x bytes(recurrent_state)
                      + (mamba_d_conv - 1) x (mamba_d_ssm + 2 x mamba_n_groups x mamba_d_state) x bytes(conv_state))

A program that held the recurrent matrices in bfloat16 holds 1.07 GB fewer of
3.77 at the cell's sizes and is refused; one that kept no keys and values, or
no state, holds fewer still.

The weights are the seed's (`benchlib/weights.py`) but for three leaves a
layer, which `family_leaves` sets from the seed by the family's published
initialisation (Mamba-2's, which Falcon-H1's code keeps): `A_log = log(U[1,
16])`, `dt_bias = softplus^-1(dt)` with dt log-uniform in [1e-3, 1e-1], `D =
1`. Why: the seed's rule (every bias 0.02 n) gives dt about 0.69 and A about
-1, a state that forgets in two positions, so a state that was dropped,
mis-carried from the prefill to the slot or rounded could not show in any
comparison; at the family's own leaves a head's memory is tens to thousands
of positions, as a trained checkpoint's is. No leaf depends on anything the
program computes; program and reference are handed the same leaves. `run`
puts `SeededFamily` where `jobs/serve.py` reads `weights`, for this process.
"""

from benchlib.files import load_module
from benchlib.result import Checks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
DT_MIN, DT_MAX, DT_FLOOR, A_RANGE = 1e-3, 1e-1, 1e-4, (1.0, 16.0)


def stated_pool_bytes(total_blocks: int, block_size: int, slots: int, sizes: dict, precision: dict) -> int:
    layers = int(sizes["num_hidden_layers"])
    heads, d_head, d_state = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_head"]), int(sizes["mamba_d_state"])
    conv_width = int(sizes["mamba_d_ssm"]) + 2 * int(sizes["mamba_n_groups"]) * d_state
    a_token = layers * 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) * BYTES[precision["kv_cache"]]
    a_slot = layers * (heads * d_head * d_state * BYTES[precision["recurrent_state"]]
                       + (int(sizes["mamba_d_conv"]) - 1) * conv_width * BYTES[precision["conv_state"]])
    return (total_blocks + 1) * block_size * a_token + slots * a_slot


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for K/V by head AND slot state in every
    layer: the bytes of the arrays the engine's pool added against
    `stated_pool_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_pool_bytes(engine.total_blocks, engine.kv_block_size, engine.num_slots, sizes,
                             ctx.config["precision"]["serve"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against {sizes['num_hidden_layers']} "
                   f"layers' keys and values by head a token AND recurrent state and convolution tails a slot in "
                   f"the stated precisions ({want}), relative difference", abs(kv_held - want) / want, limit)


def family_leaves(params, seed: int, dt_range=(DT_MIN, DT_MAX), a_range=A_RANGE):
    """`params` with every block's `ssm/a_log`, `ssm/dt_bias` and `ssm/d` set
    from the seed by the family's published initialisation (the module's
    docstring has the rule and its reason), each in its leaf's own type. The
    ranges are the family's; a CPU test at a width of 64 may ask for larger
    steps, at which the state's part of the output is as large as the skip's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import weights

    lm = dict(params["lm"])
    key = jax.random.fold_in(weights.seed_key(seed), 1024)
    for name in sorted(n for n in lm if n.startswith("block_")):
        ssm = dict(lm[name]["ssm"])
        like = ssm["a_log"]["bias"]
        k_a, k_dt = jax.random.split(jax.random.fold_in(key, int(name.split("_")[1])))
        a = jax.random.uniform(k_a, like.shape, jnp.float32, *a_range)
        dt = jnp.exp(jax.random.uniform(k_dt, like.shape, jnp.float32, np.log(dt_range[0]), np.log(dt_range[1])))
        dt = jnp.maximum(dt, DT_FLOOR)
        ssm["a_log"] = {"bias": jnp.log(a).astype(like.dtype)}
        ssm["dt_bias"] = {"bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(like.dtype)}  # softplus^-1
        ssm["d"] = {"scale": jnp.ones_like(ssm["d"]["scale"])}
        lm[name] = {**lm[name], "ssm": ssm}
    return {**params, "lm": lm}


class SeededFamily:
    """What `jobs/serve.py` reads as `weights`: the seed's leaves, then the
    three leaves a layer by the family's initialisation."""

    def param_shapes(self, model, *init_args):
        from benchlib import weights

        return weights.param_shapes(model, *init_args)

    def make_params(self, shape_tree, seed: int, dtype):
        from benchlib import weights

        return family_leaves(weights.make_params(shape_tree, seed, dtype), seed)


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    serve.weights = SeededFamily()
    return serve.run(ctx)
