"""Readings for the limit on `engine_logprob_rms` in
`openpangu-ultra-moe-718b.rollout-longctx`, at the cell's own widths, in one
process on the chip:

    python3 bench/tests/pangu_onchip.py --seeds 11,2147483659 --variants sound,reference_int8,...

A small engine (4 slots, the cell's block size, prompt bucket and output
length; the numbers a request reads do not depend on its neighbours: no
expert has a capacity) serves 4 prompts of the cell's lengths to 1,024
sampled tokens each, and every reported logprob is compared with the plain
reference's full forward, as `correct` compares them. Variants (each a patch
from outside, none an option of the program):

  sound           the program as the cell runs it (bfloat16 weights, compute, cache)
  reference_int8  the control of `correct`: the reference in int8 against itself
  no_sandwich     the two norms behind attention and the feed-forward left out
  no_rope_score   the rotary part of the score left out (q_rope, k_r zeroed)
  scale_nope      1/sqrt(qk_nope) in place of 1/sqrt(qk_nope + qk_rope) on the scores
  softmax_bf16    the decode steps' softmax in bfloat16 (through the gather read
                  path: the kernel's own softmax is float32 by construction)

There is no float32 variant: 3.4 B parameters in float32 do not fit the chip.
One JSON line a (seed, variant). On the CPU add `--rehearse-cpu` (tiny preset).
Not a pytest file: it needs the chip."""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

CELL = "openpangu-ultra-moe-718b.rollout-longctx"


def serve(cfg, params, prompts, max_new, eng, seed, decode_kernel):
    import numpy as np

    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy
    from trlx_tpu.ops.sampling import GenerationConfig

    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True, eos_token_id=cfg.vocab_size + 1,
                               pad_token_id=0)
    engine = InferenceEngine(
        CausalLMPolicy(cfg), cfg, params, gen_cfg, seed=seed % (2**31), kv_paging=True,
        num_slots=len(prompts), max_prompt_len=eng["max_prompt_len"], max_prefill_batch=eng["max_prefill_batch"],
        prompt_bucket=eng["prompt_bucket"], kv_block_size=eng["kv_block_size"], kv_pool_blocks=0,
        kv_cache_dtype=eng["kv_cache_dtype"], decode_kernel=decode_kernel)
    slots = list(range(len(prompts)))
    for slot, prompt in zip(slots, prompts):  # a row a prefill, as the cell admits them
        engine.insert_requests([(prompt, max_new)], [slot])
    tokens, logprobs = [[] for _ in prompts], [[] for _ in prompts]
    for _ in range(max_new + 1):  # a step in flight: the last outputs come a call later
        tok, lp, emitted, _ = engine.step()
        for s in slots:
            if emitted[s] and len(tokens[s]) < max_new:
                tokens[s].append(int(tok[s]))
                logprobs[s].append(float(lp[s]))
    fallbacks = engine.kv_stats()["kv_kernel_fallbacks"]
    del engine
    return [np.asarray(t, np.int32) for t in tokens], [np.asarray(x, np.float32) for x in logprobs], fallbacks


@contextlib.contextmanager
def patched(name, cfg):
    """The variant's patch, on while the program serves and off again before
    the reference runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models import transformer

    undo = []

    def put(obj, attr, value):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    if name == "no_rope_score":
        put(transformer, "apply_rope", lambda x, *a, **k: jnp.zeros_like(x))
    elif name == "scale_nope":
        whole, sqrt = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, np.sqrt
        put(np, "sqrt", lambda x, *a, **k: sqrt(cfg.qk_nope_head_dim if np.ndim(x) == 0 and x == whole else x,
                                                *a, **k))
    elif name == "softmax_bf16":
        softmax = jax.nn.softmax
        put(jax.nn, "softmax", lambda x, axis=-1: softmax(x.astype(jnp.bfloat16), axis=axis))
    try:
        yield
    finally:
        for obj, attr, value in reversed(undo):
            setattr(obj, attr, value)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variants", default="sound")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchlib import device, files, traffic, weights
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.utils import logging as program_logging

    program_logging.set_verbosity(program_logging.WARNING)
    _, cell, config, mix = files.load_cell(CELL)
    device.setup_compile_cache()
    info = device.require_device(1, args.rehearse_cpu)
    mix = files.merge(mix, mix.get("rehearse") if args.rehearse_cpu else None)
    eng = files.merge(cell["engine"], cell.get("rehearse_engine") if args.rehearse_cpu else None)
    eng.setdefault("kv_cache_dtype", "bf16")
    program = config["rehearse" if args.rehearse_cpu else "program"]
    sizes = config["rehearse_sizes" if args.rehearse_cpu else "sizes"]
    ref = files.load_module(f"reference/{config['reference']}.py")
    readings = files.load_module("tests/laguna_onchip.py").readings  # the same comparison, the same keys
    max_new = int(mix["output_len"]["max"])
    t_ref = -(-int(mix["prompt_len"]["max"]) // eng["prompt_bucket"]) * eng["prompt_bucket"] + max_new

    extra = dict(program["model_extra_configs"])
    sound = config_from_preset(program["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                               param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    variants = {"sound": {}, "reference_int8": {}, "no_sandwich": dict(sandwich_norm=False),
                "no_rope_score": {}, "scale_nope": {}, "softmax_bf16": {}}
    kernel = "interpret" if args.rehearse_cpu else eng["decode_kernel"]

    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        # the shortest, two middling and the longest of the cell's prompts
        pool = np.sort(traffic.lengths(mix["prompt_len"], int(mix["pool"]), rng))
        lens = pool[[0, len(pool) // 3, 2 * len(pool) // 3, -1]]
        prompts = traffic.token_ids(lens, {"low": 0, "high": sound.vocab_size}, rng)
        tokens = jnp.zeros((1, 32), jnp.int32)
        # every variant serves the SOUND tree (a variant that reads fewer leaves leaves them unread)
        shapes = weights.param_shapes(CausalLMPolicy(sound), tokens, jnp.ones_like(tokens))
        params = weights.make_params(shapes, seed, sound.param_dtype)
        for name in args.variants.split(","):
            cfg = dataclasses.replace(sound, **variants[name])
            t0 = time.monotonic()
            with patched(name, cfg):
                out_tokens, logprobs, fallbacks = serve(cfg, params, prompts, max_new, eng, seed,
                                                        "xla" if name == "softmax_bf16" else kernel)
            out = readings(ref, params["lm"], sizes, prompts, out_tokens, logprobs, t_ref, name == "reference_int8")
            print(json.dumps({"seed": seed, "variant": name, "device": info["kind"],
                              "prompts": [int(n) for n in lens], "fallbacks": fallbacks,
                              "seconds": round(time.monotonic() - t0, 1), **out}), flush=True)
        del params


if __name__ == "__main__":
    main()
