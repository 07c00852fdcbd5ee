"""Readings for the limit on `engine_logprob_rms` in
`falcon-h1-34b.rollout-chat`, at the cell's own widths, in one process on the
chip:

    python3 bench/tests/falcon_onchip.py --seeds 11,2147483659 --variants sound,reference_int8,...

A small engine (4 slots, the cell's block size, prompt bucket and output
length; the numbers a request reads do not depend on its neighbours: a slot's
state is its own) serves 4 prompts spanning the cell's lengths (64 to 1,024)
to 512 sampled tokens each, a row a prefill as the cell admits them, and every
reported logprob is compared with the plain reference's full forward, as
`correct` compares them. Variants:

  sound           the program as the cell runs it (bfloat16 weights, compute, K/V
                  and tails; float32 recurrent state)
  reference_int8  the control of `correct`: the reference in int8 against itself
  state_bf16      the program with its recurrent matrices held in bfloat16
                  (`ssm_state_dtype`; the cell's byte count refuses it besides)
  float32         the program computing in float32 at `highest` over the same
                  bfloat16 leaves: how far the mathematics stands from the
                  reference's at the published widths
  no_d, norm_before_gate, ungrouped_norm, state_bf16_reference
                  the sound program against the reference WITH that departure
                  (bench/reference/falcon_h1.py `departures`; the last is the
                  reference's state rounded to bfloat16 after every token): how
                  far the comparison stands from a program that had it
  branches        no engine: the program's forward over the longest prompt, and
                  what its attention and its SSM mixer each add to the residual
                  in every layer against the reference's, relative to the
                  reference's largest entry

The leaves are the cell's: the seed's, with A_log, dt_bias and D by the
family's initialisation as the job sets them. One JSON line a (seed,
variant). On the CPU add `--rehearse-cpu` (tiny preset). Not a pytest file: it
needs the chip."""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

CELL = "falcon-h1-34b.rollout-chat"
DEPARTURES = {"no_d": "no_d", "norm_before_gate": "norm_before_gate", "ungrouped_norm": "ungrouped_norm",
              "state_bf16_reference": "state_bf16"}


def branch_readings(cfg, ref, lm, sizes, prompt):
    """Relative distance of each branch's contribution, by layer, over one prompt."""
    import jax
    import numpy as np

    from trlx_tpu.models.transformer import Attention, Mamba2Mixer, TransformerLM

    tokens, mask = np.asarray(prompt, np.int32)[None], np.ones((1, len(prompt)), np.int32)
    branch = lambda mdl, name: isinstance(mdl, (Attention, Mamba2Mixer)) and name == "__call__"
    _, state = jax.jit(lambda p, t, m: TransformerLM(cfg).apply(
        {"params": p}, t, m, capture_intermediates=branch, mutable=["intermediates"]))(lm, tokens, mask)
    off = lambda got, want: float(np.abs(np.asarray(got, np.float32) - np.asarray(want)).max() / np.abs(want).max())
    out = {}
    for layer in range(cfg.n_layers):
        caught = state["intermediates"][f"block_{layer}"]
        want_attn, want_ssm = ref.branches(lm, tokens[0], mask[0], sizes, layer=layer)
        got_attn = np.asarray(caught["attn"]["__call__"][0][0], np.float32) * cfg.multipliers.attention_out
        out[f"layer_{layer}"] = {"attention": off(got_attn, want_attn), "ssm": off(caught["ssm"]["__call__"][0][0], want_ssm),
                                 "largest_attention": float(np.abs(want_attn).max()),
                                 "largest_ssm": float(np.abs(want_ssm).max())}
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variants", default="sound")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()

    import jax
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
    job = files.load_module(f"jobs/{cell['job']}.py")
    serve = files.load_module("tests/pangu_onchip.py").serve  # the same engine loop
    readings = files.load_module("tests/laguna_onchip.py").readings  # and the same comparison
    max_new = int(mix["output_len"]["max"])
    t_ref = -(-int(mix["prompt_len"]["max"]) // eng["prompt_bucket"]) * eng["prompt_bucket"] + max_new

    extra = dict(program["model_extra_configs"])
    sound = config_from_preset(program["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                               param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    kernel = "interpret" if args.rehearse_cpu else eng["decode_kernel"]
    programs = {"state_bf16": dataclasses.replace(sound, ssm_state_dtype=jnp.bfloat16),
                "float32": dataclasses.replace(sound, dtype=jnp.float32)}

    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        # the shortest, two middling and the longest of the cell's prompts
        pool = np.sort(traffic.lengths(mix["prompt_len"], int(mix["pool"]), rng))
        lens = pool[[0, len(pool) // 3, 2 * len(pool) // 3, -1]]
        prompts = traffic.token_ids(lens, {"low": 0, "high": sound.vocab_size}, rng)
        tokens = jnp.zeros((1, 32), jnp.int32)
        shapes = weights.param_shapes(CausalLMPolicy(sound), tokens, jnp.ones_like(tokens))
        params = job.family_leaves(weights.make_params(shapes, seed, sound.param_dtype), seed)
        served = {}  # program -> what the engine gave: a departure re-reads the sound run
        for variant in args.variants.split(","):
            t0 = time.monotonic()
            line = {"seed": seed, "variant": variant, "device": info["kind"], "prompts": [int(n) for n in lens]}
            if variant == "branches":
                out = branch_readings(sound, ref, params["lm"], sizes, prompts[-1])
            else:
                which = variant if variant in programs else "sound"
                if which not in served:
                    precision = jax.default_matmul_precision("highest") if which == "float32" else jax.default_matmul_precision(None)
                    with precision:
                        served[which] = serve(programs.get(which, sound), params, prompts, max_new, eng, seed, kernel)
                out_tokens, logprobs, fallbacks = served[which]
                departed = dict(sizes, departures=[DEPARTURES[variant]] if variant in DEPARTURES else [])
                out = readings(ref, params["lm"], departed, prompts, out_tokens, logprobs, t_ref,
                               variant == "reference_int8")
                line["fallbacks"] = fallbacks
            print(json.dumps({**line, "seconds": round(time.monotonic() - t0, 1), **out}), flush=True)
        del params, served
        gc.collect()  # an engine and its programs' closures hold each other, and with them the seed's 8.8 GB of leaves


if __name__ == "__main__":
    main()
