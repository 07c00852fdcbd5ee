"""The `serve` job: a `Scheduler` over a paged `InferenceEngine`, in the
process that holds the chip, fed by this thread.

Requests enter through `Scheduler.submit` with a stream sink. The traffic
file decides the loop: `backlog` keeps the scheduler's queue at a fixed depth
for the whole window (a trainer collecting rollouts through the engine);
`poisson` is an open loop at a fixed rate, each request timed from when it
was due. A ramp at the same load comes first and counts as set-up. Weights
come from the seed in the type the configuration serves them in.
"""

import collections
import time

import numpy as np

from benchlib import tracing, traffic, weights
from benchlib.files import load_module, merge
from benchlib.result import Checks, percentile


class CountingSink:
    """The stream sink of a request: counts what the scheduler pushes."""

    def __init__(self):
        self.items = 0

    def put(self, item):
        self.items += 1


def build_engine(ctx, mix):
    import jax.numpy as jnp

    from trlx_tpu.inference import InferenceEngine, Scheduler
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.ops.sampling import GenerationConfig

    program = ctx.config["rehearse" if ctx.rehearse else "program"]
    serving = ctx.config["serving"]
    preset = program["model_path"].split(":", 1)[1]
    extra = dict(program["model_extra_configs"])
    cfg = config_from_preset(
        preset, extra.pop("vocab_size"), **extra,
        param_dtype=jnp.dtype(serving["param_dtype"]), dtype=jnp.dtype(serving["compute_dtype"]))
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    shapes = weights.param_shapes(model, tokens, jnp.ones_like(tokens))
    params = weights.make_params(shapes, ctx.seed, cfg.param_dtype)

    eng = merge(ctx.cell["engine"], ctx.cell.get("rehearse_engine") if ctx.rehearse else None)
    if ctx.control:
        eng["kv_cache_dtype"] = "int8"  # the program's own lower-precision path
    gen_cfg = GenerationConfig(
        max_new_tokens=int(mix["output_len"]["max"]), do_sample=True,
        eos_token_id=cfg.vocab_size + 1,  # out of range: a row ends at its own max_new_tokens
        pad_token_id=0)
    before = _bytes_in_use()
    engine = InferenceEngine(
        model, cfg, params, gen_cfg, seed=ctx.seed % (2**31), kv_paging=True,
        num_slots=eng["num_slots"], max_prompt_len=eng["max_prompt_len"],
        max_prefill_batch=eng["max_prefill_batch"], prompt_bucket=eng["prompt_bucket"],
        kv_block_size=eng["kv_block_size"], kv_pool_blocks=eng["kv_pool_blocks"],
        kv_cache_dtype=eng["kv_cache_dtype"], decode_kernel=eng["decode_kernel"])
    scheduler = Scheduler(engine, max_queue_depth=eng["max_queue_depth"])
    kv_held = _bytes_in_use() - before
    return engine, scheduler, cfg, params, kv_held


def _bytes_in_use():
    """Bytes of every array alive on the devices now."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


def check_kv_precision(ctx, engine, cfg, kv_held, checks: Checks):
    """The cache holds keys and values in the type the configuration states:
    the bytes of the arrays the engine's pool added against blocks x block x
    layers x 2 x kv heads x head size x bytes of that type. (A logprob cannot
    tell an int8 cache from a bfloat16 one: PERF.md section 2.)"""
    stated = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[
        ctx.config["precision"]["serve"]["kv_cache"]]
    want = (engine.total_blocks + 1) * engine.kv_block_size * cfg.n_layers * 2 \
        * cfg.kv_heads * cfg.head_dim * stated
    limit = load_module(f"reference/{ctx.config['reference']}.py").LIMITS["serve"]["kv_bytes_rel"]
    checks.at_most(f"bytes of the arrays the engine's pool holds ({kv_held}) against the stated "
                   f"precision's ({want}), relative difference", abs(kv_held - want) / want, limit)


def warm_up(engine, mix, rng):
    """Every prefill program the traffic can reach, (rows bucket) x (prompt
    width bucket), and the decode step, through the engine's own doors."""
    bucket = engine.prompt_bucket
    lo = -(-int(mix["prompt_len"]["min"]) // bucket) * bucket
    hi = -(-int(mix["prompt_len"]["max"]) // bucket) * bucket
    rows, n = [], 1
    while n <= engine.max_prefill_batch:
        rows.append(n)
        n *= 2
    for plen in range(lo, hi + 1, bucket):
        for pb in rows:
            ids = rng.integers(0, 256, size=plen).astype(np.int32)
            slots = list(range(pb))
            engine.insert_requests([(ids, 2)] * pb, slots)
            engine.step()
            engine.release_slots(slots)
    return len(rows) * len(range(lo, hi + 1, bucket))


class StepLog:
    """Wraps `engine.step` from outside: wall seconds and emitted tokens of
    every step, with the time it ended; in a traced run also a span and the
    tokens resident in the cache for the rows that decoded."""

    def __init__(self, engine, live, traced: bool):
        self.rows = []  # (t_end, seconds, tokens emitted, resident tokens or -1)
        inner = engine.step

        def step():
            t0 = time.monotonic()
            if traced:
                with tracing.span("engine.step"):
                    out = inner()
            else:
                out = inner()
            t1 = time.monotonic()
            resident = -1
            if traced:
                while live and live[0].finish_reason is not None:
                    live.popleft()
                resident = sum(len(r.prompt_ids) + len(r.token_ids) for r in list(live)
                               if r.stage in ("prefill", "decode") and r.finish_reason is None)
            self.rows.append((t1, t1 - t0, int(np.asarray(out[2]).sum()), resident))
            return out

        engine.step = step


def compare_outputs(ctx, cfg, params, done, engine, int8_reference=False):
    """Readings: the engine's `token_logprobs` (prefill, then decode through
    the paged cache) against the reference's full forward over prompt +
    output, for a seeded sample of finished requests. `int8_reference` adds
    the control's readings (the reference computed in int8)."""
    ref = load_module(f"reference/{ctx.config['reference']}.py")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    rng = np.random.default_rng(ctx.seed + 2)
    k = min(int(ctx.cell["check"]["requests"]), len(done))
    picked = [done[i] for i in sorted(rng.choice(len(done), size=k, replace=False))]
    t_ref = engine.max_prompt_len + engine.gen_cfg.max_new_tokens
    errs, cerrs = [], []
    for req in picked:
        seq = np.concatenate([req.prompt_ids, np.asarray(req.token_ids, np.int32)])
        tokens = np.zeros((1, t_ref), np.int32)
        mask = np.zeros((1, t_ref), np.int32)
        tokens[0, :len(seq)] = seq
        mask[0, :len(seq)] = 1
        p = len(req.prompt_ids)
        got = np.asarray(req.token_logprobs, np.float32)
        window = slice(p - 1, p - 1 + len(got))
        want = np.asarray(ref.logprobs(params["lm"], tokens, mask, sizes))[0, window]
        errs.append(np.abs(got - want))
        if int8_reference:
            control = np.asarray(ref.logprobs(params["lm"], tokens, mask, sizes, int8=True))
            cerrs.append(np.abs(control[0, window] - want))
    err = np.concatenate(errs)
    out = {"engine_logprob_rms": float(np.sqrt(np.mean(err**2))),
           "tokens": int(err.size), "requests": k}
    if cerrs:
        out["control:engine_logprob_rms"] = float(np.sqrt(np.mean(np.concatenate(cerrs) ** 2)))
    return out


def check_outputs(ctx, cfg, params, done, checks: Checks, engine):
    limits = load_module(f"reference/{ctx.config['reference']}.py").LIMITS[ctx.cell["job"]]
    got = compare_outputs(ctx, cfg, params, done, engine)
    checks.at_most(f"engine_logprob_rms |program - reference| over {got['tokens']} tokens of "
                   f"{got['requests']} requests (prefill, then paged decode)",
                   got["engine_logprob_rms"], limits["engine_logprob_rms"])


def run(ctx):
    checks = Checks()
    mix = merge(ctx.traffic, ctx.traffic.get("rehearse") if ctx.rehearse else None)
    rng = np.random.default_rng(ctx.seed)
    engine, scheduler, cfg, params, kv_held = build_engine(ctx, mix)
    check_kv_precision(ctx, engine, cfg, kv_held, checks)
    ctx.log(f"engine: decode path {engine.decode_path!r}, {engine.total_blocks} blocks, "
            f"kv {engine.kv_stats().get('kv_pool_bytes', 0) / 1e9:.2f} GB")
    n_programs = warm_up(engine, mix, rng)
    ctx.log(f"warmed {n_programs} prefill programs and the decode step; "
            f"{len(ctx.compiles.events)} backend compiles, {ctx.compiles.seconds():.1f} s")

    # the pool of requests: the same multiset of sizes for every seed
    n_pool = int(mix["pool"])
    p_lens = traffic.lengths(mix["prompt_len"], n_pool, rng)
    o_lens = traffic.lengths(mix["output_len"], n_pool, rng)
    prompts = traffic.token_ids(p_lens, {"low": 0, "high": cfg.vocab_size}, rng)

    live = collections.deque()
    steps = StepLog(engine, live, traced=bool(ctx.trace))
    admitted_at = {}
    if ctx.trace:
        inner_insert = scheduler._insert_batch

        def insert_batch(batch, slots):
            now = time.monotonic()
            for req in batch:
                admitted_at[req.id] = now
            with tracing.span("scheduler.insert_batch"):
                return inner_insert(batch, slots)

        scheduler._insert_batch = insert_batch
        tracing.wrap(scheduler, "_admit", "scheduler.admit")

    from trlx_tpu.inference.scheduler import QueueFullError

    requests = []  # (request or None if refused, due time or None)

    def submit(i, due=None, max_new=None):
        try:
            req = scheduler.submit(prompts[i % n_pool],
                                   max_new_tokens=int(max_new or o_lens[i % n_pool]),
                                   stream=CountingSink())
        except QueueFullError:
            requests.append((None, due))
            return
        requests.append((req, due))
        live.append(req)

    arrivals = mix["arrivals"]
    ramp = float(mix["ramp_seconds"])
    scheduler.start()
    window = tracing.TracedWindow()
    trace_at, trace_for = float(ctx.cell["trace"]["start_s"]), float(ctx.cell["trace"]["seconds"])
    if ctx.rehearse:
        trace_at, trace_for = 0.2 * ctx.seconds, 0.5 * ctx.seconds
    late = []
    try:
        start = time.monotonic()
        t0 = start + ramp
        t1 = t0 + ctx.seconds
        tracing_now = False

        def profiler_tick(now):
            nonlocal tracing_now
            if ctx.trace and not tracing_now and window.trace is None and now >= t0 + trace_at:
                window.start()
                tracing_now = True
            elif tracing_now and now >= t0 + trace_at + trace_for:
                window.stop()
                tracing_now = False

        if arrivals["kind"] == "backlog":
            # the first request of each slot is cut to a different length, so
            # that the slots leave lockstep during the ramp and the window sees
            # the steady mixture of a long-running actor, prefills spread out
            i, n_slots = 0, engine.num_slots
            while (now := time.monotonic()) < t1:
                profiler_tick(now)
                for _ in range(int(arrivals["depth"] - scheduler.metrics.get("queue_depth"))):
                    cap = int(o_lens[i % n_pool])
                    submit(i, max_new=max(cap * (i + 1) // n_slots, 1) if i < n_slots else None)
                    i += 1
                time.sleep(0.002)
        else:
            due = start + traffic.arrival_times(arrivals, ramp + ctx.seconds, rng)
            for i, d in enumerate(due):
                while (now := time.monotonic()) < d:
                    profiler_tick(now)
                    time.sleep(min(d - now, 0.01))
                late.append(time.monotonic() - d)
                submit(i, float(d))
            while time.monotonic() < t1:
                profiler_tick(time.monotonic())
                time.sleep(0.01)
            # an open loop's requests are all answered before the run ends
            deadline = time.monotonic() + float(mix["drain_seconds"])
            for req, _ in requests:
                if req is not None:
                    req.wait(max(deadline - time.monotonic(), 0.0))
        if tracing_now:
            window.stop()
        setup_s = t0 - ctx.t_start
        t_end = time.monotonic()
    finally:
        scheduler.stop()

    in_window = ctx.compiles.between(t0, t_end)
    checks.equal("backend compiles inside the window", len(in_window), 0)
    if in_window:
        ctx.log(f"compiled inside the window: {in_window}")

    end_to_end = {"setup_s": setup_s}
    if arrivals["kind"] == "backlog":
        # judged on tokens: every request that finished inside the window
        counted = [(r, d) for r, d in requests
                   if r is not None and r.finish_time is not None and t0 <= r.finish_time < t1]
        tokens = sum(n for t_end, _, n, _ in steps.rows if t0 <= t_end < t1)
        end_to_end["serve_tokens_per_s"] = tokens / ctx.seconds
        ctx.log(f"{tokens} tokens from {sum(1 for r in steps.rows if t0 <= r[0] < t1)} steps "
                f"inside the window; {len(counted)} requests finished in it")
    else:
        counted = [(r, d) for r, d in requests if d >= t0]
        ok = [(r, d) for r, d in counted if r is not None and r.ok and r.first_token_time]
        ttft = [(r.first_token_time - d) * 1e3 for r, d in ok]
        itl = [(r.finish_time - r.first_token_time) / (len(r.token_ids) - 1) * 1e3
               for r, _ in ok if len(r.token_ids) > 1]
        if ttft and itl:
            end_to_end["ttft_p90_ms"] = percentile(ttft, 90)
            end_to_end["itl_p90_ms"] = percentile(itl, 90)
        lat = [x * 1e3 for x in late]
        ctx.log(f"open loop: {len(counted)} requests due in the window at "
                f"{arrivals['rate_per_s']}/s; the generator ran late by p50 "
                f"{percentile(lat, 50):.3f} ms, p95 {percentile(lat, 95):.3f} ms, max {max(lat):.3f} ms")
    attempted = len(counted)
    failed = sum(1 for r, _ in counted if r is None or not r.ok)
    reasons = collections.Counter("refused" if r is None else r.finish_reason for r, _ in counted)
    checks.equal(f"requests not ending eos/length/stop among {attempted} ({dict(reasons)})",
                 failed, 0)
    checks.true(f"requests counted ({attempted})", attempted > 0)
    done = [r for r, _ in counted if r is not None and r.ok and len(r.token_ids) > 1]
    if done:
        with tracing.span("check_outputs"):
            check_outputs(ctx, cfg, params, done, checks, engine)
    kv = engine.kv_stats()
    checks.equal("paged-kernel fallbacks", sum(kv["kv_kernel_fallbacks"].values()), 0)

    in_win = [r for r in steps.rows if t0 <= r[0] < t1]
    waits = [(admitted_at[r.id] - r.enqueue_time) * 1e3 for r, _ in counted
             if r is not None and r.id in admitted_at]
    tw = (window.t0, window.t1) if window.trace is not None else None
    return {
        "checks": checks, "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end,
        "measurements": {
            "trace": window.trace,
            "series": {"engine.step_s": [r[1] for r in in_win],
                       "engine.step_tokens": [r[2] for r in in_win],
                       "sched.queue_wait_ms": waits},
            "constants": {"num_slots": engine.num_slots},
            # the paged kernel's calls inside the traced part: one per layer
            # per step (the decode program's only Pallas kernel), over the
            # tokens then resident
            "kernel_calls": {"paged_decode": {
                "steps_resident_tokens": [r[3] for r in steps.rows
                                          if tw and tw[0] <= r[0] - r[1] / 2 < tw[1]],
                "layers": cfg.n_layers, "heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
                "head_dim": cfg.head_dim,
                "kv_bytes": np.dtype(engine.kv_cache_dtype).itemsize}},
        },
    }

