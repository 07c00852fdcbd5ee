"""Readings for the limit on `engine_logprob_rms` in `ouro-2.6b.rollout-math`,
at the cell's own widths (the whole published model), in one process on the chip:

    python3 bench/tests/ouro_onchip.py --seeds 11,2147483659 --variants sound,reference_int8,pass0_kv,program_pass0

A small engine (2 slots, the cell's block size, prompt bucket and output
length; the numbers a request reads do not depend on its neighbours) serves 2
prompts of the cell's lengths (the shortest and the longest of a seed's pool)
to the cell's 352 sampled tokens each, and every reported logprob is compared with the
plain reference's full forward, as `correct` compares them. Variants:

  sound                the program as the cell runs it (bfloat16 weights, compute, cache)
  reference_int8       the control of `correct`: the reference in int8 against itself
  pass0_kv, no_pass_norm, no_sandwich
                       a departure: the SOUND run's logprobs re-read against the
                       reference WITH the departure (`bench/reference/ouro.py`
                       `DEPARTURES`), which is what a program with that departure
                       would be held to
  program_pass0        the PROGRAM made to read and write pass 0's planes in every
                       pass (`ops.paged_attention.pass_table` replaced here, in the
                       tool: no switch in the program), served again and read
                       against the sound reference

There is no float32 variant: 2.67 B parameters in float32 are 10.7 GB.
One JSON line a (seed, variant). On the CPU add `--rehearse-cpu` (tiny preset).
Not a pytest file: it needs the chip."""

import argparse
import functools
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

CELL = "ouro-2.6b.rollout-math"


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
    from trlx_tpu.ops import paged_attention
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
    serve = files.load_module("tests/pangu_onchip.py").serve  # a row a prefill, a step in flight
    readings = files.load_module("tests/laguna_onchip.py").readings  # the same comparison, the same keys
    max_new = int(mix["output_len"]["max"])
    t_ref = -(-int(mix["prompt_len"]["max"]) // eng["prompt_bucket"]) * eng["prompt_bucket"] + max_new

    extra = dict(program["model_extra_configs"])
    cfg = config_from_preset(program["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                             param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    kernel = "interpret" if args.rehearse_cpu else eng["decode_kernel"]
    with_departure = lambda name: types.SimpleNamespace(logprobs=functools.partial(ref.logprobs, departure=name))
    names = args.variants.split(",")
    unknown = set(names) - {"sound", "reference_int8", "program_pass0", *ref.DEPARTURES}
    if unknown:
        raise SystemExit(f"unknown variants {sorted(unknown)}")

    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        pool = np.sort(traffic.lengths(mix["prompt_len"], int(mix["pool"]), rng))
        lens = pool[[0, -1]]  # the shortest and the longest of the cell's prompts
        prompts = traffic.token_ids(lens, {"low": 0, "high": cfg.vocab_size}, rng)
        tokens = jnp.zeros((1, 32), jnp.int32)
        shapes = weights.param_shapes(CausalLMPolicy(cfg), tokens, jnp.ones_like(tokens))
        params = weights.make_params(shapes, seed, cfg.param_dtype)
        t0 = time.monotonic()
        sound = serve(cfg, params, prompts, max_new, eng, seed, kernel)
        served = round(time.monotonic() - t0, 1)
        for name in names:
            t0 = time.monotonic()
            out_tokens, logprobs, fallbacks = sound
            if name == "program_pass0":
                table = paged_attention.pass_table
                paged_attention.pass_table = lambda t, _, passes, blocks: table(t, 0, passes, blocks)
                try:
                    out_tokens, logprobs, fallbacks = serve(cfg, params, prompts, max_new, eng, seed, kernel)
                finally:
                    paged_attention.pass_table = table
            reference = with_departure(name) if name in ref.DEPARTURES else ref
            out = readings(reference, params["lm"], sizes, prompts, out_tokens, logprobs, t_ref,
                           name == "reference_int8")
            print(json.dumps({"seed": seed, "variant": name, "device": info["kind"],
                              "prompts": [int(n) for n in lens], "fallbacks": fallbacks, "served_s": served,
                              "seconds": round(time.monotonic() - t0, 1), **out}), flush=True)
        del params, sound


if __name__ == "__main__":
    main()
