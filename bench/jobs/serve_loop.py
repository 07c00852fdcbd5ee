"""The `serve_loop` job: `serve` (bench/jobs/serve.py: window, traffic,
logprob comparison, fallbacks, compiles in the window, all its own code) for
a LOOPED configuration, whose one stack of layers runs `total_ut_steps` times
a token and keeps keys and values of its own in every (pass, layer). As
`serve_latent` and `serve_kv_hybrid`, the one thing that differs is the count
`check_kv_precision` holds the pool's bytes to: this module binds that one
name in `jobs/serve.py`, for this process, to its own count, and calls
`serve.run(ctx)`.

The count is made from the configuration file's published keys and stated
precision, not from the program's config object:

    (blocks + 1) x block x total_ut_steps x num_hidden_layers x 2 x num_key_value_heads x head_dim x bytes(kv_cache)

A program that kept one plane a layer for every pass to share holds a quarter
of this and is refused; an int8 arena (`--control`) holds half and is refused.
"""

from benchlib.files import load_module
from benchlib.result import Checks

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def stated_pool_bytes(total_blocks: int, block_size: int, sizes: dict, precision: dict) -> int:
    planes = int(sizes["total_ut_steps"]) * int(sizes["num_hidden_layers"])
    a_token = planes * 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) * BYTES[precision["kv_cache"]]
    return (total_blocks + 1) * block_size * a_token


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """`serve.check_kv_precision` for a plane a (pass, layer): the bytes of the
    arrays the engine's pool added against `stated_pool_bytes`."""
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    want = stated_pool_bytes(engine.total_blocks, engine.kv_block_size, sizes, ctx.config["precision"]["serve"])
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against keys and values by head a token in "
                   f"{sizes['total_ut_steps']} passes x {sizes['num_hidden_layers']} layers in the stated precision "
                   f"({want}), relative difference", abs(kv_held - want) / want, limit)


def run(ctx):
    serve = load_module("jobs/serve.py")
    serve.check_kv_precision = check_kv_precision
    return serve.run(ctx)
