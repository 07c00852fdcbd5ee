"""The `serve_sparse_latent` job: `serve` (bench/jobs/serve.py: window,
traffic, logprob comparison, fallbacks, compiles in the window, all its own
code) for a configuration whose latent layers are of two shapes and whose
full layers keep an index's key a token beside the latent. As
`serve_latent.py`, the one thing that differs is the count
`check_kv_precision` holds the cache's bytes to: this module binds that one
name in `jobs/serve.py`, for this process, to its own count, and calls
`serve.run(ctx)`.

A full layer (`layer_types` "full_attention") caches `kv_lora_rank +
qk_rope_head_dim` values a token and the index's key of `index_head_dim`
beside them (512 + 64 + 128 at the published sizes); a sliding layer
`swa_kv_lora_rank + swa_qk_rope_head_dim` (1,024 + 64). The count is made
from the configuration file's published keys and its `layer_types`, not from
the program's config object: a program that kept keys and values by head,
dropped the index keys, or kept the sliding layers at the full layers' 576
holds other bytes than this and is refused.

The weights are the seed's (`benchlib/weights.py`) but for two leaves a
layer, the scales of the norms on the two latents (`q_a_norm`, `kv_a_norm`),
which `trained_norm_scales` divides by the factor `apply_mla_qkv_lora_rescale`
multiplies behind them, sqrt(hidden_size / rank): a checkpoint trained under
the rescale holds scales that give its latents the size its attention wants;
the seed's scales of 1 + 0.05 n under a factor of 3.2 give attention scores of
a standard deviation of 6 to 7 and a softmax that sees two or three positions,
and then one position chosen otherwise by an index in bfloat16 moves a logprob
by 1 (0.78 rms for the sound program against 0.87 for the reference in int8,
my chip run, PR 51: a comparison that can refuse nothing). With the scales so
set the rescaled latents have the unit size the family's other configurations'
have. Program and reference both still multiply by the factor, and both read
the same leaves: no leaf depends on anything the program computes. `run` puts
`SeededTrainedNorms` where `jobs/serve.py` reads `weights`, for this process.
"""

from benchlib.files import load_module
from benchlib.result import Checks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def cached_values_per_token(sizes: dict) -> int:
    """What a token caches over all layers, by the published keys."""
    full = int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"]) + int(sizes["index_head_dim"])
    sliding = int(sizes["swa_kv_lora_rank"]) + int(sizes["swa_qk_rope_head_dim"])
    kinds = list(sizes["layer_types"])
    assert set(kinds) <= {"full_attention", "sliding_attention"}, kinds
    return kinds.count("full_attention") * full + kinds.count("sliding_attention") * sliding


def stated_cache_bytes(total_blocks: int, block_size: int, sizes: dict, kv_cache: str) -> int:
    """(blocks + the reserved zero block) x block x values a token x bytes of the stated type."""
    return (total_blocks + 1) * block_size * cached_values_per_token(sizes) * BYTES[kv_cache]


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for this cache: the bytes of the arrays the
    engine's pool added against `stated_cache_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_cache_bytes(engine.total_blocks, engine.kv_block_size, sizes,
                              ctx.config["precision"]["serve"]["kv_cache"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against the latent planes and index "
                   f"keys of {cached_values_per_token(sizes)} values a token in the stated precision ({want}), "
                   f"relative difference", abs(kv_held - want) / want, limit)


def trained_norm_scales(params, sizes: dict):
    """`params` with each block's `q_a_norm` and `kv_a_norm` scales divided by
    sqrt(hidden_size / rank), the rank its kind of layer has (`layer_types`):
    what the rescale multiplies, taken out of the leaf it stands behind."""
    if not sizes.get("apply_mla_qkv_lora_rescale"):
        return params
    lm = dict(params["lm"])
    for i, kind in enumerate(sizes["layer_types"]):
        pre = "swa_" if kind == "sliding_attention" else ""
        block = dict(lm[f"block_{i}"])
        attn = dict(block["attn"])
        for leaf, rank in (("q_a_norm", sizes[pre + "q_lora_rank"]), ("kv_a_norm", sizes[pre + "kv_lora_rank"])):
            scale = attn[leaf]["scale"]
            attn[leaf] = {"scale": (scale.astype("float32") * (int(rank) / int(sizes["hidden_size"])) ** 0.5
                                    ).astype(scale.dtype)}
        block["attn"] = attn
        lm[f"block_{i}"] = block
    return {**params, "lm": lm}


class SeededTrainedNorms:
    """What `jobs/serve.py` reads as `weights`: the seed's leaves, then the
    latents' norms' scales as a checkpoint trained under the rescale has them."""

    def __init__(self, sizes: dict):
        self.sizes = sizes

    def param_shapes(self, model, *init_args):
        from benchlib import weights

        return weights.param_shapes(model, *init_args)

    def make_params(self, shape_tree, seed: int, dtype):
        from benchlib import weights

        return trained_norm_scales(weights.make_params(shape_tree, seed, dtype), self.sizes)


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    serve.weights = SeededTrainedNorms(ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"])
    return serve.run(ctx)
