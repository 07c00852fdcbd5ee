"""The `serve` job: a `Scheduler` over a paged `InferenceEngine`, in the
process that holds the chip, fed by this thread.

Requests enter through `Scheduler.submit` with a stream sink. The traffic
file decides the loop: `backlog` keeps the scheduler's queue at a fixed depth
(a trainer collecting rollouts through the engine) and times whole passes
over the request pool, from one decode step's end to another's, as the `ppo`
job times whole cycles: every seed's window then holds the same requests;
`poisson` is an open loop at a fixed rate over `--seconds` of wall clock,
each request timed from when it was due. A ramp at the same load comes first
and counts as set-up. Weights come from the seed in the type the
configuration serves them in. `--trace 1` profiles some seconds inside the
window, with spans put on from outside; `--trace 2` measures exactly as
`--trace 0` does and, once the window has closed, keeps the same load on and
profiles some seconds of it, in which the program's own `trlx:` spans say
what the host did (a backlog only: the open loop, which no cell uses yet,
refuses `--trace 2`).
"""

import bisect
import collections
import threading
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


def round_up(n: int, bucket: int) -> int:
    return -(-int(n) // bucket) * bucket


def warm_up(engine, mix, rng):
    """Every prefill program the traffic can reach, (rows bucket) x (prompt
    width bucket), and the decode step, through the engine's own doors."""
    bucket = engine.prompt_bucket
    lo = round_up(mix["prompt_len"]["min"], bucket)
    hi = round_up(mix["prompt_len"]["max"], bucket)
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
    every step, with the time it ended; with `spans` (`--trace 1`) also a
    span; while `resident` is set (with the trace: `paged_decode_roofline`
    prices the traced steps) also the tokens resident in the cache for the
    rows that decoded."""

    def __init__(self, engine, live, spans: bool):
        self.rows = []  # (t_end, seconds, tokens emitted, resident tokens or -1)
        self.resident = spans
        inner = engine.step

        def step():
            t0 = time.monotonic()
            if spans:
                with tracing.span("engine.step"):
                    out = inner()
            else:
                out = inner()
            t1 = time.monotonic()
            resident = -1
            if self.resident:
                while live and live[0].finish_reason is not None:
                    live.popleft()
                resident = sum(len(r.prompt_ids) + len(r.token_ids) for r in list(live)
                               if r.stage in ("prefill", "decode") and r.finish_reason is None)
            self.rows.append((t1, t1 - t0, int(np.asarray(out[2]).sum()), resident))
            return out

        engine.step = step


class InsertLog:
    """Wraps `scheduler._insert_batch` from outside: when every call began
    and ended, its requests and the width its prompts were padded to; with
    `spans` (`--trace 1`) also a span. A counter and two clock reads a call."""

    def __init__(self, scheduler, bucket: int, spans: bool):
        self.rows = []  # (t_begin, t_end, requests, width)
        self.admitted_at = {}
        inner = scheduler._insert_batch

        def insert_batch(batch, slots):
            t0 = time.monotonic()
            for req in batch:
                self.admitted_at[req.id] = t0
            try:
                if spans:
                    with tracing.span("scheduler.insert_batch"):
                        return inner(batch, slots)
                return inner(batch, slots)
            finally:
                self.rows.append((t0, time.monotonic(), len(batch),
                                  round_up(max(len(r.prompt_ids) for r in batch), bucket)))

        scheduler._insert_batch = insert_batch


def backlog_window(step_ends, admissions, ramp_end, pool, seconds):
    """The two edges of a backlog run's window, from the logs of one thread.

    `step_ends`: when each decode step ended, ascending; `admissions`: (when
    the `_insert_batch` call began, its requests), ascending. The window opens
    at the end of the first step that ends at or after `ramp_end`, and closes
    at the end of the first step after the admission that brought the count
    since the opening to k x pool, k the smallest whole number that makes it
    at least `seconds` long. Returns None while the logs do not reach that
    far, else (index of the step whose end opens it, index of the step whose
    end closes it, k, admissions since the opening)."""
    first = bisect.bisect_left(step_ends, ramp_end)
    if first >= len(step_ends):
        return None
    admitted, k = 0, 1
    for began, n in admissions:
        if began < step_ends[first]:
            continue
        admitted += n
        if admitted < k * pool:
            continue
        last = bisect.bisect_right(step_ends, began)  # the step that decodes what was admitted
        if last >= len(step_ends):
            return None
        if step_ends[last] - step_ends[first] >= seconds:
            return first, last, k, admitted
        k = admitted // pool + 1
    return None


LATE_S = 0.02  # a step this much over the median of its kind is counted as late


def admission_series(step_rows, insert_rows):
    """What admissions cost a backlog window, from its two logs. `step_rows`
    are the `StepLog` rows from the step whose end opens the window to the one
    whose end closes it, `insert_rows` the `InsertLog` rows inside it. The
    prefill program is dispatched and not waited for, so its device time
    shows in the step after it and not in `_insert_batch`. Hence, taking a
    step with what led up to it, from the previous step's end to its own:
    `engine.decode_step_s`, the `engine.step` seconds of the steps with no
    admission before them (the decode program alone), and
    `sched.admission_s`, for each step with one, how much longer it took end
    to end than the median step without (the host part of `_insert_batch` and
    the prefill the step waited behind). Also the steps that ended more than
    `LATE_S` after the median of their kind, and by how much in all: the host
    was late to return from a step the device had finished (PERF.md section 5)."""
    began = [r[0] for r in insert_rows]
    plain, plain_whole, behind = [], [], []
    for prev, row in zip(step_rows, step_rows[1:]):
        if bisect.bisect_left(began, prev[0]) == bisect.bisect_left(began, row[0]):
            plain.append(row[1])
            plain_whole.append(row[0] - prev[0])
        else:
            behind.append(row[0] - prev[0])

    def over_median(xs):
        return [x - float(np.median(xs)) for x in xs]

    late = [x for x in over_median(plain_whole) + over_median(behind) if x > LATE_S]
    return {"engine.decode_step_s": plain,
            "sched.admission_s": [x - float(np.median(plain_whole)) for x in behind]
            if plain_whole else []}, late


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
    if ctx.trace == 2 and mix["arrivals"]["kind"] != "backlog":
        # no cell offers an open loop yet, so tracing after one has no chip
        # run behind it: the cell that brings the traffic brings that too
        raise SystemExit(f"[bench] FAIL: --trace 2 traces a backlog after its window; "
                         f"{mix['arrivals']['kind']!r} arrivals are measured with --trace 0 or 1")

    def since_start():  # the parts of set-up, so that a run says which of them moved
        return f"{time.monotonic() - ctx.t_start:.2f} s after the process began"

    ctx.log(f"set-up: imports done, the device up, {since_start()}")
    engine, scheduler, cfg, params, kv_held = build_engine(ctx, mix)
    check_kv_precision(ctx, engine, cfg, kv_held, checks)
    ctx.log(f"engine: decode path {engine.decode_path!r}, {engine.total_blocks} blocks, "
            f"kv {engine.kv_stats().get('kv_pool_bytes', 0) / 1e9:.2f} GB")
    ctx.log(f"set-up: weights made, engine built, {since_start()}")
    n_programs = warm_up(engine, mix, rng)
    ctx.log(f"warmed {n_programs} prefill programs and the decode step; "
            f"{len(ctx.compiles.events)} backend compiles, {ctx.compiles.seconds():.1f} s")
    ctx.log(f"set-up: programs warmed, {since_start()}")

    # the pool of requests: the same multiset of sizes for every seed
    n_pool = int(mix["pool"])
    p_lens = traffic.lengths(mix["prompt_len"], n_pool, rng)
    o_lens = traffic.lengths(mix["output_len"], n_pool, rng)
    prompts = traffic.token_ids(p_lens, {"low": 0, "high": cfg.vocab_size}, rng)

    live = collections.deque()
    steps = StepLog(engine, live, spans=ctx.trace == 1)
    inserts = InsertLog(scheduler, engine.prompt_bucket, spans=ctx.trace == 1)
    if ctx.trace == 1:
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
    ctx.log(f"set-up: the ramp of {ramp} s begins {since_start()}")
    window = tracing.TracedWindow()
    trace_at, trace_for = float(ctx.cell["trace"]["start_s"]), float(ctx.cell["trace"]["seconds"])
    if ctx.rehearse:
        trace_at, trace_for = 0.2 * ctx.seconds, 0.5 * ctx.seconds
    late = []
    try:
        start = time.monotonic()
        t0 = start + ramp
        t1 = t0 + ctx.seconds
        tracing_now, stopping = False, None

        def profiler_tick(now):
            # the profiler is stopped, and its trace read, on a thread of its
            # own: that takes seconds, and the thread that feeds may not stall
            nonlocal tracing_now, stopping
            if ctx.trace == 1 and not tracing_now and stopping is None and now >= t0 + trace_at:
                window.start()
                tracing_now = True
            elif tracing_now and now >= t0 + trace_at + trace_for:
                stopping = threading.Thread(target=window.stop)
                stopping.start()
                tracing_now = False

        def trace_after(keep_load_on):
            """`--trace 2`, once the measured window has closed and its numbers
            lie in the logs: with the same load kept on (`keep_load_on()`
            offers what is due and sleeps a moment), start the profiler; open
            the traced window at the end of the first step to end after that
            (starting the profiler stalls the host: that step is no sample);
            trace the cell's `trace.seconds`; stop on a thread of its own,
            because stopping and reading the trace takes seconds and the thread
            that feeds may not stall meanwhile."""
            def load_on_until(done):
                give_up = time.monotonic() + 120
                while not done():
                    if time.monotonic() > give_up:
                        raise SystemExit("[bench] FAIL: the traced part of the run got stuck")
                    keep_load_on()

            steps.resident = True
            window.start(open_window=False)
            n_steps = len(steps.rows)
            load_on_until(lambda: len(steps.rows) > n_steps)
            window.open()
            until = time.monotonic() + trace_for
            load_on_until(lambda: time.monotonic() >= until)
            stopper = threading.Thread(target=window.stop)
            stopper.start()
            load_on_until(lambda: not stopper.is_alive())
            stopper.join()

        if arrivals["kind"] == "backlog":
            # the first request of each slot is cut to a different length, so
            # that the slots leave lockstep during the ramp and the window sees
            # the steady mixture of a long-running actor, prefills spread out.
            # The backlog is topped up until whole passes over the pool have
            # been admitted for `--seconds`; the logs are looked at only once
            # that long has passed, a few times a second, and the edges read
            # from them afterwards
            i, n_slots = 0, engine.num_slots

            def top_up():
                nonlocal i
                for _ in range(int(arrivals["depth"] - scheduler.metrics.get("queue_depth"))):
                    cap = int(o_lens[i % n_pool])
                    submit(i, max_new=max(cap * (i + 1) // n_slots, 1) if i < n_slots else None)
                    i += 1
                time.sleep(0.002)

            edges, look_at, give_up = None, t1, t1 + 2 * ctx.seconds + 30
            while edges is None and (now := time.monotonic()) < give_up:
                profiler_tick(now)
                top_up()
                if now >= look_at:
                    look_at = now + 0.05
                    edges = backlog_window([r[0] for r in steps.rows],
                                           [(r[0], r[2]) for r in inserts.rows],
                                           t0, n_pool, ctx.seconds)
            if ctx.trace == 2 and edges is not None:
                trace_after(top_up)
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
        if stopping is not None:
            stopping.join()
        t_end = time.monotonic()
    finally:
        scheduler.stop()

    series, constants = {}, {"num_slots": engine.num_slots}
    if arrivals["kind"] == "backlog":
        if edges is None:
            raise SystemExit(f"[bench] FAIL: no whole pass over the pool of {n_pool} was admitted "
                             f"and decoded for {ctx.seconds} s before the run gave up")
        first, last, k, admitted = edges
        # the window: from the end of step `first` to the end of step `last`
        t0, t1 = steps.rows[first][0], steps.rows[last][0]
        in_win = steps.rows[first + 1:last + 1]
        checks.equal(f"requests admitted inside the window against {k} passes over the pool",
                     admitted, k * n_pool)
        series, late_steps = admission_series(steps.rows[first:last + 1],
                                              [r for r in inserts.rows if t0 <= r[0] < t1])
        if series["sched.admission_s"]:
            constants["window_s_per_insert"] = (t1 - t0) / len(series["sched.admission_s"])
    else:
        in_win = [r for r in steps.rows if t0 <= r[0] < t1]
    setup_s = t0 - ctx.t_start

    in_window = ctx.compiles.between(t0, t_end)
    checks.equal("backend compiles inside the window", len(in_window), 0)
    if in_window:
        ctx.log(f"compiled inside the window: {in_window}")

    end_to_end = {"setup_s": setup_s}
    if arrivals["kind"] == "backlog":
        # judged on tokens: every request that finished inside the window
        counted = [(r, d) for r, d in requests
                   if r is not None and r.finish_time is not None and t0 < r.finish_time <= t1]
        tokens = sum(r[2] for r in in_win)
        end_to_end["serve_tokens_per_s"] = tokens / (t1 - t0)
        by_shape = collections.Counter((r[2], r[3]) for r in inserts.rows if t0 <= r[0] < t1)
        ctx.log(f"window {t1 - t0:.3f} s, {k} passes: {tokens} tokens from {len(in_win)} steps "
                f"({sum(1 for r in in_win if r[2] != engine.num_slots)} of them emitted other than "
                f"{engine.num_slots}); {len(counted)} requests finished in it; "
                f"{sum(by_shape.values())} `_insert_batch` calls by (rows, width) "
                f"{dict(sorted(by_shape.items()))}")
        ctx.log(f"steps behind an admission took {sum(series['sched.admission_s']):.3f} s more "
                f"than as many of the {len(series['engine.decode_step_s'])} steps behind none; "
                f"{len(late_steps)} steps ended over {LATE_S * 1e3:.0f} ms later than the median "
                f"of their kind, {sum(late_steps):.3f} s in all")
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

    waits = [(inserts.admitted_at[r.id] - r.enqueue_time) * 1e3 for r, _ in counted
             if r is not None and r.id in inserts.admitted_at]
    tw = (window.t0, window.t1) if window.trace is not None else None
    if tw and "serve_tokens_per_s" in end_to_end:
        # what tracing costs while it is on: whole steps inside the traced window
        traced = [r for r in steps.rows if tw[0] <= r[0] <= tw[1]]
        if len(traced) > 1:
            rate = sum(r[2] for r in traced[1:]) / (traced[-1][0] - traced[0][0])
            ctx.log(f"with tracing on, {len(traced) - 1} steps emitted {rate:.2f} tokens/s, "
                    f"{100 * (rate / end_to_end['serve_tokens_per_s'] - 1):+.2f}% against the window's")
    return {
        "checks": checks, "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end,
        "measurements": {
            "trace": window.trace,
            "series": {"engine.step_s": [r[1] for r in in_win],
                       "engine.step_tokens": [r[2] for r in in_win],
                       "sched.queue_wait_ms": waits, **series},
            "constants": constants,
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

