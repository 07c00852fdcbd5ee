"""The `serve_hybrid` job: `serve` (bench/jobs/serve.py: window, traffic,
logprob comparison, fallbacks, compiles in the window, all its own code) for
a configuration whose layers keep two kinds of state: a latent plane a TOKEN
(its latent-attention layers, in the paged arena) and a recurrent matrix and
convolution tails a SLOT (its linear-attention layers, beside the arena). As
`serve_latent`, the one thing that differs is the count `check_kv_precision`
holds the pool's bytes to: this module binds that one name in `jobs/serve.py`,
for this process, to its own count, and calls `serve.run(ctx)`.

The count is made from the configuration file's published keys and stated
precisions, not from the program's config object:

    (blocks + 1) x block x latent layers x (kv_lora_rank + qk_rope_head_dim) x bytes(kv_cache)
  + slots x linear layers x (heads x head_dim x head_dim x bytes(recurrent_state)
                             + 3 x (short_conv_kernel_size - 1) x heads x head_dim x bytes(conv_state))

Layer i is a latent one where (i + 1) % layer_group_size == 0. A program
that held the recurrent matrices in bfloat16 holds a third fewer bytes than
this and is refused; one that kept keys and values by head holds more.

The weights are the seed's (`benchlib/weights.py`) but for one leaf a layer:
`expert_bias`, which `balance_expert_bias` sets as a trained checkpoint's is
set, by DeepSeek-V3's auxiliary-loss-free rule on seeded tokens through the
plain reference's float32 forward: no leaf depends on anything the program
computes. `run` puts `SeededBalanced` where `jobs/serve.py` reads `weights`,
for this process.
"""

from benchlib.files import load_module, merge
from benchlib.result import Checks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_counts(sizes: dict):
    """(latent layers, linear layers) of the layers held."""
    n, period = int(sizes["num_hidden_layers"]), int(sizes["layer_group_size"])
    latent = sum(1 for i in range(n) if (i + 1) % period == 0)
    return latent, n - latent


def stated_pool_bytes(total_blocks: int, block_size: int, slots: int, sizes: dict, precision: dict) -> int:
    latent, linear = layer_counts(sizes)
    heads, dim = int(sizes["num_attention_heads"]), int(sizes["head_dim"])
    a_token = latent * (int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"])) * BYTES[precision["kv_cache"]]
    a_slot = linear * (heads * dim * dim * BYTES[precision["recurrent_state"]]
                       + 3 * (int(sizes["short_conv_kernel_size"]) - 1) * heads * dim * BYTES[precision["conv_state"]])
    return (total_blocks + 1) * block_size * a_token + slots * a_slot


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for a latent arena beside slot state: the
    bytes of the arrays the engine's pool added against `stated_pool_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_pool_bytes(engine.total_blocks, engine.kv_block_size, engine.num_slots, sizes,
                             ctx.config["precision"]["serve"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    latent, linear = layer_counts(sizes)
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against {latent} latent planes a token "
                   f"and {linear} layers' recurrent state and convolution tails a slot in the stated precisions "
                   f"({want}), relative difference", abs(kv_held - want) / want, limit)


# The balancing rule's constants: rows of the reference's own width (the
# longest prompt + the output, so the programs are the comparison's), steps a
# layer, and the step u, falling linearly to 0.
BALANCE_ROWS, BALANCE_STEPS, BALANCE_RATE = 3, 200, 0.02


def balance_expert_bias(params, sizes: dict, width: int, seed: int, log=None):
    """`params` with every expert layer's `expert_bias` set so that its
    experts are chosen equally often on seeded tokens.

    Why: the published model routes with `moe_router_enable_expert_bias`, a
    bias a training run moves until the load is even (DeepSeek-V3's
    auxiliary-loss-free balancing: after each batch b_e += u sign(mean load -
    load_e)). Seeded weights have no such history: the states of different
    tokens share a large common part, every token's scores lean the same way,
    and a layer sends most tokens to the same few experts, which ones and how
    few by the seed. A chip then streams 44 to 49 of its 64 experts a layer a
    step by seed (PERF.md section 6, PR 41), and a third of the step moves
    with it; under an even load it streams what a deployment's chip does.

    How: `BALANCE_ROWS` rows of `width` token ids from the seed go through the
    plain reference, float32, a half block at a time; where a block's second
    half is an expert layer, `BALANCE_STEPS` steps of the rule above on the
    router's input there (`router_input`) with the reference's
    `choose_experts`, and the rows go on through the layer as balanced (a
    later layer's input depends on the earlier choices). The router scores
    as it is published; only the selection bias moves; program and reference
    are handed the same leaf."""
    import jax
    import jax.numpy as jnp

    from benchlib import weights

    ref = load_module("reference/ling_flash.py")
    static = ref._static(sizes, False)
    top_k, n_group, topk_group = static["top_k"], static["n_group"], static["topk_group"]
    lm = dict(params["lm"])
    tokens = jax.random.randint(jax.random.fold_in(weights.seed_key(seed), 512), (BALANCE_ROWS, width), 0,
                                lm["embed_tokens"]["embedding"].shape[0])
    mask = jnp.ones((width,), jnp.int32)
    positions = ref.ops.positions_from_mask(mask)

    @jax.jit
    def fit(x, router, bias):
        scores = jax.nn.sigmoid(jnp.matmul(x, router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        even = scores.shape[0] * top_k / scores.shape[1]

        def load(b):
            chosen = ref.choose_experts(scores, b, top_k=top_k, n_group=n_group, topk_group=topk_group, departs=())
            return jnp.zeros_like(b).at[chosen.reshape(-1)].add(1.0)

        start = bias.astype(jnp.float32)
        end = jax.lax.fori_loop(
            0, BALANCE_STEPS,
            lambda i, b: b + BALANCE_RATE * (1.0 - i / BALANCE_STEPS) * jnp.sign(even - load(b)), start)
        served = end.astype(bias.dtype)
        return served, load(start).max() / even, load(served.astype(jnp.float32)).max() / even

    worst = []
    with jax.default_matmul_precision("highest"):
        rows = [ref.ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[row]) for row in tokens]
        for i, (is_latent, is_dense) in enumerate(ref.layers_of(sizes)):
            kind = dict(static, is_latent=is_latent, is_dense=is_dense)
            block = lm[f"block_{i}"]
            rows = [ref.mixed(h, block, mask, positions, **kind) for h in rows]
            if not is_dense:
                x = jnp.concatenate([ref.router_input(a, block["ln_mlp"], eps=static["eps"]) for a in rows])
                bias, before, after = fit(x, block["mlp"]["router"]["kernel"], block["mlp"]["expert_bias"]["bias"])
                block = lm[f"block_{i}"] = {**block, "mlp": {**block["mlp"], "expert_bias": {"bias": bias}}}
                worst.append((round(float(before), 2), round(float(after), 2)))
            rows = [ref.fed(a, block, **kind) for a in rows]
    if log is not None:
        log(f"expert_bias balanced on {BALANCE_ROWS * width} seeded tokens through the reference, {BALANCE_STEPS} "
            f"steps a layer: the most chosen expert over an even share, by layer (before, after): {worst}")
    return {**params, "lm": lm}


class SeededBalanced:
    """What `jobs/serve.py` reads as `weights`: the seed's leaves, then the
    balanced selection bias."""

    def __init__(self, sizes: dict, width: int, log):
        self.sizes, self.width, self.log = sizes, width, log

    def param_shapes(self, model, *init_args):
        from benchlib import weights

        return weights.param_shapes(model, *init_args)

    def make_params(self, shape_tree, seed: int, dtype):
        from benchlib import weights

        return balance_expert_bias(weights.make_params(shape_tree, seed, dtype), self.sizes, self.width, seed,
                                   self.log)


def reference_width(ctx) -> int:
    """Positions the comparison's reference runs a request at
    (`serve.compare_outputs`): the longest prompt + the output."""
    eng = merge(ctx.cell["engine"], ctx.cell.get("rehearse_engine") if ctx.rehearse else None)
    mix = merge(ctx.traffic, ctx.traffic.get("rehearse") if ctx.rehearse else None)
    return int(eng["max_prompt_len"]) + int(mix["output_len"]["max"])


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    serve.weights = SeededBalanced(sizes, reference_width(ctx), ctx.log)
    return serve.run(ctx)
