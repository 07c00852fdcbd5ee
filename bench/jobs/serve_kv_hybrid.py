"""The `serve_kv_hybrid` job: `serve` (bench/jobs/serve.py: window, traffic,
logprob comparison, fallbacks, compiles in the window, all its own code) for
a configuration whose layers keep two kinds of state: keys and values by head a
TOKEN (its softmax-attention layers, `gqa_layers`, in the paged arena) and a
recurrent matrix and convolution tails a SLOT (its linear-attention layers,
beside the arena). As `serve_latent` and `serve_hybrid`, the one thing that
differs is the count `check_kv_precision` holds the pool's bytes to: this
module binds that one name in `jobs/serve.py`, for this process, to its own
count, and calls `serve.run(ctx)`.

The count is made from the configuration file's published keys and stated
precisions, not from the program's config object:

    (blocks + 1) x block x GQA layers x 2 x num_key_value_heads x head_dim x bytes(kv_cache)
  + slots x KDA layers x (num_heads x head_dim x head_dim x bytes(recurrent_state)
                          + 3 x (short_conv_kernel_size - 1) x num_heads x head_dim x bytes(conv_state))

with the KDA sizes from `linear_attn_config`. Layer i is a GQA one where i is
in `gqa_layers`. A program that held the recurrent matrices in bfloat16
holds a third fewer bytes than this and is refused; one that kept keys and
values for every layer holds more.

The weights are the seed's (`benchlib/weights.py`) but for one leaf a layer,
`expert_bias`, which `balance_expert_bias` sets as `serve_hybrid`'s does
(bench/jobs/serve_hybrid.py has the rule's reason and its constants), through
THIS configuration's plain reference: no leaf depends on anything the program
computes. `run` puts `SeededBalanced` where `jobs/serve.py` reads `weights`,
for this process.
"""

from benchlib.files import load_module
from benchlib.result import Checks

hybrid = load_module("jobs/serve_hybrid.py")  # the balancing rule's constants, the bytes of a type
BYTES = hybrid.BYTES


def layer_counts(sizes: dict):
    """(GQA layers, KDA layers) of the layers held."""
    n = int(sizes["num_hidden_layers"])
    gqa = sum(1 for i in range(n) if i in sizes["gqa_layers"])
    return gqa, n - gqa


def stated_pool_bytes(total_blocks: int, block_size: int, slots: int, sizes: dict, precision: dict) -> int:
    gqa, kda = layer_counts(sizes)
    linear = sizes["linear_attn_config"]
    heads, dim, taps = int(linear["num_heads"]), int(linear["head_dim"]), int(linear["short_conv_kernel_size"])
    a_token = gqa * 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) * BYTES[precision["kv_cache"]]
    a_slot = kda * (heads * dim * dim * BYTES[precision["recurrent_state"]]
                    + 3 * (taps - 1) * heads * dim * BYTES[precision["conv_state"]])
    return (total_blocks + 1) * block_size * a_token + slots * a_slot


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for K/V by head beside slot state: the bytes
    of the arrays the engine's pool added against `stated_pool_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_pool_bytes(engine.total_blocks, engine.kv_block_size, engine.num_slots, sizes,
                             ctx.config["precision"]["serve"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    gqa, kda = layer_counts(sizes)
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against {gqa} layers' keys and values "
                   f"by head a token and {kda} layers' recurrent state and convolution tails a slot in the stated "
                   f"precisions ({want}), relative difference", abs(kv_held - want) / want, limit)


def balance_expert_bias(params, sizes: dict, reference: str, width: int, seed: int, log=None):
    """`params` with every layer's `expert_bias` set so that its experts are
    chosen equally often on seeded tokens: `serve_hybrid.balance_expert_bias`'s
    rule and constants (DeepSeek-V3's auxiliary-loss-free balancing, b_e += u
    sign(mean load - load_e), on `BALANCE_ROWS` rows of `width` seeded ids
    through the plain reference's float32 forward, a half block at a time),
    through the reference this configuration names. The router scores as it is
    published; only the selection bias moves; program and reference are handed
    the same leaf."""
    import jax
    import jax.numpy as jnp

    from benchlib import weights

    ref = load_module(f"reference/{reference}.py")
    static = ref._static(sizes, False)
    top_k = static["top_k"]
    lm = dict(params["lm"])
    tokens = jax.random.randint(jax.random.fold_in(weights.seed_key(seed), 512), (hybrid.BALANCE_ROWS, width), 0,
                                lm["embed_tokens"]["embedding"].shape[0])
    mask = jnp.ones((width,), jnp.int32)
    positions = ref.ops.positions_from_mask(mask)

    @jax.jit
    def fit(x, router, bias):
        scores = jax.nn.sigmoid(jnp.matmul(x, router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        even = scores.shape[0] * top_k / scores.shape[1]
        load = lambda b: jnp.zeros_like(b).at[ref.choose_experts(scores, b, top_k=top_k).reshape(-1)].add(1.0)
        start = bias.astype(jnp.float32)
        end = jax.lax.fori_loop(
            0, hybrid.BALANCE_STEPS,
            lambda i, b: b + hybrid.BALANCE_RATE * (1.0 - i / hybrid.BALANCE_STEPS) * jnp.sign(even - load(b)), start)
        served = end.astype(bias.dtype)
        return served, load(start).max() / even, load(served.astype(jnp.float32)).max() / even

    worst = []
    with jax.default_matmul_precision("highest"):
        rows = [ref.ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[row]) for row in tokens]
        for i, is_gqa in enumerate(ref.layers_of(sizes)):
            block = lm[f"block_{i}"]
            rows = [ref.mixed(h, block, mask, positions, is_gqa=is_gqa, **static) for h in rows]
            x = jnp.concatenate([ref.router_input(a, block["ln_mlp"], eps=static["eps"]) for a in rows])
            bias, before, after = fit(x, block["mlp"]["router"]["kernel"], block["mlp"]["expert_bias"]["bias"])
            block = lm[f"block_{i}"] = {**block, "mlp": {**block["mlp"], "expert_bias": {"bias": bias}}}
            worst.append((round(float(before), 2), round(float(after), 2)))
            rows = [ref.fed(a, block, **static) for a in rows]
    if log is not None:
        log(f"expert_bias balanced on {hybrid.BALANCE_ROWS * width} seeded tokens through the reference, "
            f"{hybrid.BALANCE_STEPS} steps a layer: the most chosen expert over an even share, by layer "
            f"(before, after): {worst}")
    return {**params, "lm": lm}


class SeededBalanced(hybrid.SeededBalanced):
    """What `jobs/serve.py` reads as `weights`: the seed's leaves, then the
    selection bias balanced through the reference this configuration names."""

    def __init__(self, sizes: dict, reference: str, width: int, log):
        super().__init__(sizes, width, log)
        self.reference = reference

    def make_params(self, shape_tree, seed: int, dtype):
        from benchlib import weights

        return balance_expert_bias(weights.make_params(shape_tree, seed, dtype), self.sizes, self.reference,
                                   self.width, seed, self.log)


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    serve.weights = SeededBalanced(sizes, ctx.config["reference"], hybrid.reference_width(ctx), ctx.log)
    return serve.run(ctx)
