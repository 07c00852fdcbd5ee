"""The `serve_latent` job: `serve` (bench/jobs/serve.py: window, traffic,
logprob comparison, fallbacks, compiles in the window, all its own code) for
a configuration whose cache is not keys and values by head. The one thing
that differs is the count `check_kv_precision` holds the cache's bytes to:
this module binds that one name in `jobs/serve.py`, for this process, to its
own count, and calls `serve.run(ctx)`.

A latent-attention layer caches ONE plane a token, `kv_lora_rank +
qk_rope_head_dim` values (576 at the published sizes), in the stated type.
The count is made from the configuration file's published keys, not from
the program's config object: a program that kept keys and values apart, or
per-head keys beside the latent, holds more bytes than this and is refused.
"""

from benchlib.files import load_module
from benchlib.result import Checks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def cached_values_per_token(sizes: dict) -> int:
    """What a token caches over all layers, by the published keys: every
    layer of this family is a latent one."""
    return int(sizes["num_hidden_layers"]) * (int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"]))


def stated_cache_bytes(total_blocks: int, block_size: int, sizes: dict, kv_cache: str) -> int:
    """(blocks + the reserved zero block) x block x values a token x bytes of the stated type."""
    return (total_blocks + 1) * block_size * cached_values_per_token(sizes) * BYTES[kv_cache]


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for a latent cache: the bytes of the arrays
    the engine's pool added against `stated_cache_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_cache_bytes(engine.total_blocks, engine.kv_block_size, sizes,
                              ctx.config["precision"]["serve"]["kv_cache"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against one latent plane of "
                   f"{cached_values_per_token(sizes)} values a token in the stated precision ({want}), "
                   f"relative difference", abs(kv_held - want) / want, limit)


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    return serve.run(ctx)
