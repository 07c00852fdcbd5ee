"""Readings for the limit on `engine_logprob_rms` in
`solar-open2-250b.rollout-longctx`, at the cell's own widths, in one process
on the chip:

    python3 bench/tests/solar_onchip.py --seeds 11,2147483659 --variants sound,reference_int8,...

A small engine (4 slots, the cell's block size, prompt bucket and output
length; the numbers a request reads do not depend on its neighbours: no
expert has a capacity, a slot's state is its own) serves 4 prompts spanning
the cell's lengths (1,024 to 8,192) to 1,024 sampled tokens each, a row a
prefill as the cell admits them, and every reported logprob is compared with
the plain reference's full forward, as `correct` compares them. Variants:

  sound           the program as the cell runs it (bfloat16 weights, compute, K/V
                  and tails; float32 recurrent state)
  reference_int8  the control of `correct`: the reference in int8 against itself
  state_bf16      the program with its recurrent matrices held in bfloat16
                  (`kda_state_dtype`; the cell's byte count refuses it besides)
  beta_one, with_rope, no_gqa_gate
                  the sound program against the reference with that published
                  flag flipped (`kda_allow_neg_eigval` false: beta = sigmoid;
                  `use_rope` true; `use_gqa_gate` false)
  bounded_gate, no_conv, no_qk_l2norm, gqa_gate_per_head, no_kda_gate
                  the sound program against the reference WITH that departure
                  (bench/reference/solar_open2.py `departures`): how far the
                  comparison stands from a program that had it
  slow:<variant>  any of the above with every `dt_bias/bias` leaf shifted by -4
                  on both sides (softplus(f - 4) is about e^-4 of softplus(f):
                  a key channel then keeps a state for hundreds of tokens), so
                  that a stale or misplaced state far back shows

The leaves are the cell's: the seed's, with the selection bias balanced as
the job balances it. One JSON line a (seed, variant). On the CPU add
`--rehearse-cpu` (tiny preset). Not a pytest file: it needs the chip."""

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

CELL = "solar-open2-250b.rollout-longctx"
FLAGS = {"beta_one": {"kda_allow_neg_eigval": False}, "with_rope": {"use_rope": True},
         "no_gqa_gate": {"use_gqa_gate": False}}
DEPARTURES = ("bounded_gate", "no_conv", "no_qk_l2norm", "gqa_gate_per_head", "no_kda_gate")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variants", default="sound")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()

    import jax.numpy as jnp
    import numpy as np
    from flax.traverse_util import flatten_dict, unflatten_dict

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
    job = files.load_module(f"jobs/{cell['job']}.py")
    onchip = files.load_module("tests/pangu_onchip.py")  # the same engine loop and the same comparison
    readings = files.load_module("tests/laguna_onchip.py").readings
    max_new = int(mix["output_len"]["max"])
    t_ref = -(-int(mix["prompt_len"]["max"]) // eng["prompt_bucket"]) * eng["prompt_bucket"] + max_new

    extra = dict(program["model_extra_configs"])
    sound = config_from_preset(program["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                               param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    kernel = "interpret" if args.rehearse_cpu else eng["decode_kernel"]

    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        # the shortest, two middling and the longest of the cell's prompts
        pool = np.sort(traffic.lengths(mix["prompt_len"], int(mix["pool"]), rng))
        lens = pool[[0, len(pool) // 3, 2 * len(pool) // 3, -1]]
        prompts = traffic.token_ids(lens, {"low": 0, "high": sound.vocab_size}, rng)
        tokens = jnp.zeros((1, 32), jnp.int32)
        shapes = weights.param_shapes(CausalLMPolicy(sound), tokens, jnp.ones_like(tokens))
        # the seed's leaves with the selection bias balanced, as the cell's job makes them
        seeded = job.balance_expert_bias(weights.make_params(shapes, seed, sound.param_dtype), sizes,
                                         config["reference"], t_ref, seed)
        slowed = unflatten_dict({k: (v - 4.0).astype(v.dtype) if k[-2] == "dt_bias" else v
                                 for k, v in flatten_dict(seeded).items()})
        served = {}  # (slow, state type) -> what the engine gave: a departure re-reads the sound run
        for variant in args.variants.split(","):
            slow, _, name = variant.rpartition(":")
            params = slowed if slow else seeded
            cfg = dataclasses.replace(sound, kda_state_dtype=jnp.bfloat16) if name == "state_bf16" else sound
            t0 = time.monotonic()
            key = (bool(slow), name == "state_bf16")
            if key not in served:
                served[key] = onchip.serve(cfg, params, prompts, max_new, eng, seed, kernel)
            out_tokens, logprobs, fallbacks = served[key]
            departed = dict(sizes, departures=[name] if name in DEPARTURES else [], **FLAGS.get(name, {}))
            out = readings(ref, params["lm"], departed, prompts, out_tokens, logprobs, t_ref,
                           name == "reference_int8")
            print(json.dumps({"seed": seed, "variant": variant, "device": info["kind"],
                              "prompts": [int(n) for n in lens], "fallbacks": fallbacks,
                              "seconds": round(time.monotonic() - t0, 1), **out}), flush=True)
        del seeded, slowed, served


if __name__ == "__main__":
    main()
