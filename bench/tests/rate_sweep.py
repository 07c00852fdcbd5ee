"""The sweep that finds a serve cell's knee, once, when the cell is defined:

    python3 bench/tests/rate_sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 25

One engine, warmed once; at each rate an open loop of the cell's own mix for
`--seconds`, then a drain. A rate is sustained when the queue does not grow:
what was offered is finished about as fast as it came, and time to first
token stays where it was at the rate below. The cell's file then takes four
fifths of the highest sustained rate as a number. Not a pytest file.
"""

import argparse
import json
import os
import sys
import time
import types

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=25.0)
    parser.add_argument("--seed", type=int, default=2_147_483_659)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args()

    from benchlib import device, files, traffic
    from benchlib.result import percentile
    from trlx_tpu.utils import logging as program_logging

    program_logging.set_verbosity(program_logging.WARNING)
    _, cell, config, mix = files.load_cell(args.workload)
    device.setup_compile_cache()
    info = device.require_device(cell["chips"], args.rehearse_cpu)
    ctx = types.SimpleNamespace(cell=cell, config=config, traffic=mix, seed=args.seed,
                                rehearse=args.rehearse_cpu, control=False, trace=False)
    job = files.load_module(f"jobs/{cell['job']}.py")
    mix = files.merge(mix, mix.get("rehearse") if args.rehearse_cpu else None)
    engine, scheduler, cfg, _, _ = job.build_engine(ctx, mix)
    rng = np.random.default_rng(args.seed)
    job.warm_up(engine, mix, rng)
    n_pool = int(mix["pool"])
    p_lens = traffic.lengths(mix["prompt_len"], n_pool, rng)
    o_lens = traffic.lengths(mix["output_len"], n_pool, rng)
    prompts = traffic.token_ids(p_lens, {"low": 0, "high": cfg.vocab_size}, rng)
    scheduler.start()
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            start = time.monotonic()
            due = start + traffic.arrival_times({"kind": "poisson", "rate_per_s": rate},
                                                args.seconds, rng)
            reqs = []
            for i, d in enumerate(due):
                time.sleep(max(d - time.monotonic(), 0.0))
                reqs.append((scheduler.submit(prompts[i % n_pool],
                                              max_new_tokens=int(o_lens[i % n_pool])), d))
            offered_end = time.monotonic()
            depth_at_end = scheduler.metrics.get("queue_depth")
            unfinished_at_end = sum(1 for r, _ in reqs if r.finish_time is None)
            for r, _ in reqs:
                r.wait(300)
            drained = time.monotonic()
            ok = [(r, d) for r, d in reqs if r.ok and len(r.token_ids) > 1]
            ttft = [(r.first_token_time - d) * 1e3 for r, d in ok]
            itl = [(r.finish_time - r.first_token_time) / (len(r.token_ids) - 1) * 1e3 for r, _ in ok]
            tokens = sum(len(r.token_ids) for r, _ in ok)
            print(json.dumps({
                "rate_per_s": rate, "device": info["kind"], "offered": len(reqs), "ok": len(ok),
                "offered_seconds": offered_end - start, "drain_seconds": drained - offered_end,
                "queue_depth_at_end": depth_at_end, "unfinished_at_end": unfinished_at_end,
                "tokens_per_s_over_all": tokens / (drained - start),
                "ttft_ms": {q: percentile(ttft, q) for q in (50, 90, 99)},
                "itl_ms": {q: percentile(itl, q) for q in (50, 90, 99)}}), flush=True)
    finally:
        scheduler.stop()


if __name__ == "__main__":
    main()
