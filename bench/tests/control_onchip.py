"""Readings for the limits of `correct`, at a cell's own size, many seeds in
one process (set-up is long; the comparison needs no timed window):

    python3 bench/tests/control_onchip.py --workload <cell> --seeds 1,2,3,...

For every seed it prints, as one JSON line each, what `correct` compares
for the sound program, the same for the control that is the reference
computed in int8 in the program's place (`control:` readings), and for a
`ppo` cell the sampler's readings with the program's own int8 trunk switched
on (`int8_trunk:` readings). The int8 KV cache of a `serve` cell is a run of
its own: `bench/run.py --control` (another arena cannot live beside the
first). On the CPU add `--rehearse-cpu`. Not a pytest file: it needs the chip.
"""

import argparse
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def reseed_trainer(trainer, seed):
    """New weights from `seed` in a trainer that is already built (its
    compiled programs stay). Reaches into the trainer: a tool, not a run."""
    import jax.numpy as jnp

    from benchlib import weights
    from trlx_tpu.trainer.base_trainer import partition_params

    trainer.train_params = trainer.frozen_params = trainer.ref_params = None
    trainer._quant_frozen_cache = None
    tokens = jnp.zeros((1, 32), jnp.int32)
    shapes = weights.param_shapes(trainer.model, tokens, jnp.ones_like(tokens))
    params = trainer.place_params(weights.make_params(shapes, seed, trainer.model_cfg.param_dtype))
    mask = trainer.make_trainable_mask(params)
    trainer.train_params, trainer.frozen_params = partition_params(params, mask)
    trainer.ref_params = trainer._build_ref_params()


def ppo_readings(ctx, job, seeds):
    trainer, config, pipeline = job.build_trainer(ctx)
    for seed in seeds:
        ctx.seed = seed
        reseed_trainer(trainer, seed)
        out = job.compare_outputs(ctx, trainer, config, pipeline, int8_reference=True)
        trainer.config.method.quantize_frozen_trunk = True
        trunk = job.compare_outputs(ctx, trainer, config, pipeline, parts=("sampler",))
        trainer.config.method.quantize_frozen_trunk = False
        trainer._quant_frozen_cache = None
        out.update({f"int8_trunk:{k}": v for k, v in trunk.items()})
        yield seed, out


def serve_readings(ctx, job, seeds, n_requests=16):
    import jax.numpy as jnp
    import numpy as np

    from benchlib import files, traffic, weights

    mix = files.merge(ctx.traffic, ctx.traffic.get("rehearse") if ctx.rehearse else None)
    engine, scheduler, cfg, params, _ = job.build_engine(ctx, mix)
    job.warm_up(engine, mix, np.random.default_rng(0))
    scheduler.start()
    try:
        for seed in seeds:
            ctx.seed = seed
            rng = np.random.default_rng(seed)
            tokens = jnp.zeros((1, 32), jnp.int32)
            shapes = weights.param_shapes(engine.model, tokens, jnp.ones_like(tokens))
            params = None
            params = weights.make_params(shapes, seed, cfg.param_dtype)
            engine.set_params(params)
            p_lens = traffic.lengths(mix["prompt_len"], int(mix["pool"]), rng)[:n_requests]
            o_lens = traffic.lengths(mix["output_len"], int(mix["pool"]), rng)[:n_requests]
            prompts = traffic.token_ids(p_lens, {"low": 0, "high": cfg.vocab_size}, rng)
            reqs = [scheduler.submit(p, max_new_tokens=int(o)) for p, o in zip(prompts, o_lens)]
            for r in reqs:
                r.wait(300)
            done = [r for r in reqs if r.ok and len(r.token_ids) > 1]
            yield seed, job.compare_outputs(ctx, cfg, params, done, engine, int8_reference=True)
    finally:
        scheduler.stop()


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchlib import device, files
    from trlx_tpu.utils import logging as program_logging

    program_logging.set_verbosity(program_logging.WARNING)
    _, cell, config, traffic_mix = files.load_cell(args.workload)
    device.setup_compile_cache()
    info = device.require_device(cell["chips"], args.rehearse_cpu)
    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic_mix, seed=seeds[0], seconds=0.0, trace=False,
        rehearse=args.rehearse_cpu, control=False, t_start=time.monotonic(),
        compiles=device.CompileLog(), log=lambda m: print(f"[control] {m}", flush=True),
        peaks=None)
    job = files.load_module(f"jobs/{cell['job']}.py")
    readings = ppo_readings if cell["job"] == "ppo" else serve_readings
    for seed, out in readings(ctx, job, seeds):
        print(json.dumps({"workload": args.workload, "seed": seed, "device": info["kind"], **out}),
              flush=True)


if __name__ == "__main__":
    main()
