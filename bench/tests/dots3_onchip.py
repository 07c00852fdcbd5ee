"""Readings for the limit on `engine_logprob_rms` in
`dots3-note-prev.rollout-longdoc`, at the cell's own widths, in one process on
the chip:

    python3 bench/tests/dots3_onchip.py --seeds 11,2147483659 --variants sound,reference_int8,no_index,...

A small engine (2 slots, the cell's block size, prompt bucket and output
length; the numbers a request reads do not depend on its neighbours: no
expert has a capacity) serves 2 prompts of the cell's lengths (the shortest
and the longest of a seed's pool) to 512 sampled tokens each, and every
reported logprob is compared with the plain reference's full forward, as
`correct` compares them. Variants:

  sound            the program as the cell runs it (bfloat16 weights, compute, planes)
  reference_int8   the control of `correct`: the reference in int8 against itself
  no_index, topk_less, window_less, no_rescale, no_relu, swa_theta_as_full
                   a departure: the SOUND run's logprobs re-read against the
                   reference WITH the departure (`attention_sizes`; on the chip
                   `topk_less` keeps 1,024 of 2,048 and `window_less` 512 of 513),
                   which is what a program with that departure would be held to
  dense_both       the program with an index that keeps every position (`index_topk` past
                   the cache's length: the index's kernels, the gather and the attention
                   over the chosen all run, and choose everything) against the reference
                   with the index left out: what the kernels and the forms cost alone,
                   without a choice that bfloat16 could make otherwise
  chosen_differ    layer 0's chosen sets of the longest prompt, the index computed
                   in float32 against the same with every operand rounded to
                   bfloat16 (what the program holds): the share of chosen positions
                   that differ, over the queries past the first `index_topk`

There is no float32 variant: 4.1 B parameters in float32 do not fit the chip.
One JSON line a (seed, variant). On the CPU add `--rehearse-cpu` (tiny preset).
Not a pytest file: it needs the chip."""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

CELL = "dots3-note-prev.rollout-longdoc"
DEPARTURES = ("no_index", "topk_less", "window_less", "no_rescale", "no_relu", "swa_theta_as_full")


def chosen_differ(ref, lm, sizes, prompt):
    """The share of layer 0's chosen positions that a bfloat16 index names
    otherwise than a float32 one, over the queries that have more than
    `index_topk` positions to choose from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kind = ref.attention_sizes(sizes, "full_attention")
    topk, t = kind["index_topk"], len(prompt) - len(prompt) % ref.QUERY_BLOCK
    if t <= topk:
        return {"chosen_differ_share": 0.0, "queries": 0}
    tokens, positions = jnp.asarray(prompt[:t]), jnp.arange(t)
    p = lm["block_0"]
    eps = float(sizes["rms_norm_eps"])
    causal = positions[None, :] <= positions[:, None]

    def sets(round_to):
        cast = lambda a: jnp.asarray(a).astype(round_to).astype(jnp.float32)
        x = cast(ref.rms_norm(ref.ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens]), p["ln_attn"], eps))
        c_q = ref.rms_norm(ref.dense(x, ref.ops.f32(p["attn"]["q_a_proj"]["kernel"]), False), p["attn"]["q_a_norm"],
                           eps) * float(np.sqrt(x.shape[-1] / kind["q_rank"]))
        leaves = jax.tree_util.tree_map(cast, p["attn"]["indexer"])
        return ref.chosen(x, cast(c_q), leaves, causal, positions, heads=kind["index_heads"], topk=topk,
                          theta=kind["theta"])

    with jax.default_matmul_precision("highest"):
        a, b = jax.jit(lambda: sets(jnp.float32))(), jax.jit(lambda: sets(jnp.bfloat16))()
    late = np.asarray(a)[topk:], np.asarray(b)[topk:]
    return {"chosen_differ_share": float((late[0] & ~late[1]).sum() / late[0].sum()), "queries": int(t - topk)}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variants", default="sound")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from benchlib import device, files, traffic
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
    weights = files.load_module(f"jobs/{cell['job']}.py").SeededTrainedNorms(sizes)  # the leaves the job serves
    serve = files.load_module("tests/pangu_onchip.py").serve  # a row a prefill, a step in flight
    readings = files.load_module("tests/laguna_onchip.py").readings  # the same comparison, the same keys
    max_new = int(mix["output_len"]["max"])
    t_ref = -(-int(mix["prompt_len"]["max"]) // eng["prompt_bucket"]) * eng["prompt_bucket"] + max_new

    extra = dict(program["model_extra_configs"])
    cfg = config_from_preset(program["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                             param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    kernel = "interpret" if args.rehearse_cpu else eng["decode_kernel"]
    # what `readings` takes for the reference: the reference with one departure
    with_departure = lambda name: types.SimpleNamespace(logprobs=functools.partial(ref.logprobs, departure=name))
    names = args.variants.split(",")
    unknown = set(names) - {"sound", "reference_int8", "chosen_differ", "dense_both", *DEPARTURES}
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
        out_tokens, logprobs, fallbacks = serve(cfg, params, prompts, max_new, eng, seed, kernel)
        served = round(time.monotonic() - t0, 1)
        for name in names:
            t0 = time.monotonic()
            if name == "dense_both":
                everything = dataclasses.replace(cfg, latent_kinds=tuple(
                    (k, dataclasses.replace(spec, index_topk=2 * t_ref) if spec.index_topk else spec)
                    for k, spec in cfg.latent_kinds))
                dense_tokens, dense_logprobs, _ = serve(everything, params, prompts, max_new, eng, seed, kernel)
                out = readings(with_departure("no_index"), params["lm"], sizes, prompts, dense_tokens,
                               dense_logprobs, t_ref, False)
            elif name == "chosen_differ":
                out = chosen_differ(ref, params["lm"], sizes, prompts[-1])
            elif name in DEPARTURES:
                out = readings(with_departure(name), params["lm"], sizes, prompts, out_tokens, logprobs, t_ref, False)
            else:
                out = readings(ref, params["lm"], sizes, prompts, out_tokens, logprobs, t_ref,
                               name == "reference_int8")
            print(json.dumps({"seed": seed, "variant": name, "device": info["kind"],
                              "prompts": [int(n) for n in lens], "fallbacks": fallbacks, "served_s": served,
                              "seconds": round(time.monotonic() - t0, 1), **out}), flush=True)
        del params


if __name__ == "__main__":
    main()
