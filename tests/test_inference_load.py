"""Localhost load test for the continuous-batching inference server
(the ISSUE 2 acceptance run): 16 concurrent mixed-length requests
through a 4-slot pool must beat serving the same requests sequentially
through `trainer.generate` by >= 2x aggregate tokens/sec, with greedy
outputs bit-identical to the direct path, live /metrics during the run,
and a mid-run checkpoint promotion picked up by hot-reload without
dropping any in-flight request.

Plus the ISSUE 9 sustained-saturation SLO run: a closed-loop workload
held against a supervised 2-replica fleet while the supervisor kills and
respawns a replica mid-run — p50/p99 latency SLOs, zero dropped
requests, and the capacity-recovery time, recorded to
BENCH_load_slo.json."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from trlx_tpu.inference import InferenceEngine, InferenceServer, Scheduler, remote_generate
from trlx_tpu.ops.sampling import GenerationConfig

N_REQUESTS = 16
NUM_SLOTS = 4  # pool deliberately smaller than the request count
MAX_NEW = 32


# A test run does not edit a committed record: results go to the
# git-ignored logs/ directory; BENCH_load_slo.json at the root is only
# ever updated by hand from there.
RECORD_PATH = os.path.join(os.path.dirname(__file__), "..", "logs",
                           "BENCH_load_slo.json")


def _merge_bench_record(path, record=None, **sections):
    """Read-modify-write logs/BENCH_load_slo.json: the SLO run owns the
    top-level keys, other tests (the paged KV A/B) own named sections —
    whichever runs later must not clobber the other's numbers."""
    merged = {}
    try:
        with open(path) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        pass
    if record is not None:
        keep = {k: merged[k]
                for k in ("paged_kv", "multi_tenant", "sessions", "decode_kernel")
                if k in merged}
        merged = {**record, **keep}
    merged.update(sections)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(merged, f, indent=2)


@pytest.fixture(scope="module")
def trainer():
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    # big enough that decode steps are compute- (not dispatch-) bound on
    # CPU, so the throughput comparison measures batching, not overhead
    config = default_sft_config().evolve(
        model=dict(
            model_path="random:gpt2-tiny",
            model_extra_configs=dict(
                d_model=256, n_layers=4, n_heads=8, d_ff=1024, dtype="float32"
            ),
        ),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=128, total_steps=0, tracker=None, batch_size=2),
    )
    return SFTTrainer(config)


def workload():
    rng = np.random.RandomState(7)
    prompts, max_news = [], []
    for i in range(N_REQUESTS):
        plen = int(rng.choice([6, 20, 40, 60]))  # two prompt buckets
        prompts.append(rng.randint(0, 255, size=plen).tolist())
        max_news.append(int(rng.choice([8, 16, 24, MAX_NEW])))
    return prompts, max_news


def direct_generate(trainer, prompt, max_new):
    out = trainer.generate(
        np.asarray([prompt], np.int32), np.ones((1, len(prompt)), np.int32),
        gen_kwargs=dict(max_new_tokens=max_new, do_sample=False),
    )
    toks = np.asarray(out["response_tokens"])[0]
    mask = np.asarray(out["response_mask"])[0]
    return toks[mask > 0].tolist()


@pytest.mark.slow
def test_continuous_batching_load(trainer, tmp_path):
    prompts, max_news = workload()

    # ---- sequential baseline: one trainer.generate per request --------
    for p, m in zip(prompts, max_news):  # warm the jit caches per bucket
        direct_generate(trainer, p, m)
    t0 = time.perf_counter()
    direct_outputs = [direct_generate(trainer, p, m) for p, m in zip(prompts, max_news)]
    seq_elapsed = time.perf_counter() - t0
    seq_tokens = sum(len(o) for o in direct_outputs)
    seq_tps = seq_tokens / seq_elapsed

    # ---- continuous batching through the server -----------------------
    tok = trainer.tokenizer
    gen_cfg = GenerationConfig(
        max_new_tokens=MAX_NEW, do_sample=False,
        eos_token_id=tok.eos_token_id, pad_token_id=tok.pad_token_id,
    )
    # max_prefill_batch=1: every prefill program (one per prompt bucket)
    # is compiled during warm-up, so the measured run is compile-free
    engine = InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=NUM_SLOTS, max_prompt_len=64, max_prefill_batch=1,
    )
    sched = Scheduler(engine, max_queue_depth=64, max_wait_s=0.002)
    ckpt_dir = tmp_path / "ckpts"
    server = InferenceServer(
        sched, tokenizer=tok, host="127.0.0.1", port=0,
        watch_dir=str(ckpt_dir), reload_interval_s=0.1,
    )
    url = server.start_background()
    try:
        fn = remote_generate(url, concurrency=N_REQUESTS)
        # warm each prefill bucket + the decode program
        for p in ([1] * 6, [1] * 40):
            fn(p, max_new_tokens=2)

        results = [None] * N_REQUESTS
        errors = []

        def worker(i):
            try:
                results[i] = fn(prompts[i], max_new_tokens=max_news[i])
            except Exception as e:  # pragma: no cover
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(N_REQUESTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()

        # mid-run: promote a checkpoint (same weights) -> hot-reload must
        # pick it up while requests are in flight
        time.sleep(0.2)
        metrics_midrun = urllib.request.urlopen(url + "/metrics", timeout=30).read().decode()
        trainer.iter_count = 123
        trainer.save(str(ckpt_dir / "checkpoint_123"))

        for t in threads:
            t.join(timeout=600)
        engine_elapsed = time.perf_counter() - t0

        assert not errors, f"requests failed: {errors}"
        assert all(r is not None for r in results)
        engine_tokens = sum(len(r["token_ids"]) for r in results)
        engine_tps = engine_tokens / engine_elapsed

        # every request dropped nothing and matches the direct path
        for i, (r, want) in enumerate(zip(results, direct_outputs)):
            assert r["finish_reason"] in ("eos", "length")
            assert r["token_ids"] == want, f"request {i} diverged from trainer.generate"

        # /metrics observed the run: queue depth, slot occupancy, latency
        # histograms all present while requests were in flight
        assert "trlx_tpu_inference_queue_depth" in metrics_midrun
        assert "trlx_tpu_inference_slots_active" in metrics_midrun
        assert "trlx_tpu_inference_prefill_latency_seconds_bucket" in metrics_midrun
        assert "trlx_tpu_inference_decode_step_latency_seconds_bucket" in metrics_midrun

        # the checkpoint promote landed without dropping anything
        deadline = time.monotonic() + 30
        while server.watcher.reloads < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert server.watcher.reloads >= 1, "hot-reload missed the promoted checkpoint"
        health = json.loads(urllib.request.urlopen(url + "/healthz", timeout=30).read())
        assert health["checkpoint_step"] == 123

        speedup = engine_tps / seq_tps
        print(
            f"\nsequential: {seq_tokens} tokens in {seq_elapsed:.2f}s ({seq_tps:.1f} tok/s); "
            f"continuous: {engine_tokens} tokens in {engine_elapsed:.2f}s "
            f"({engine_tps:.1f} tok/s); speedup {speedup:.2f}x"
        )
        assert speedup >= 2.0, (
            f"continuous batching only {speedup:.2f}x over sequential "
            f"({engine_tps:.1f} vs {seq_tps:.1f} tok/s)"
        )
    finally:
        server.shutdown()


# ----------------------------------------------------------------------
# Sustained-saturation SLO harness (ROADMAP item 5 / ISSUE 9)
# ----------------------------------------------------------------------

SLO_WORKERS = 4          # closed-loop clients (each: submit -> await -> repeat)
SLO_REQUESTS = 40        # total requests across all workers
SLO_MAX_NEW = 8
# generous single-CPU-CI bounds: the point is the *shape* of the run
# (saturated, zero drops, recovery) — latency regressions show up in the
# recorded JSON long before they trip these
SLO_P50_S = 30.0
SLO_P99_S = 120.0
SLO_RECOVERY_S = 90.0


@pytest.mark.slow
def test_sustained_saturation_slo_with_replica_kill(trainer):
    """Closed-loop load against a supervised 2-replica fleet: a replica
    is killed mid-run, the router fails its traffic over (zero drops),
    and the supervisor respawns it back to full capacity — all while the
    p50/p99 latency SLOs hold. Latencies + capacity-recovery time land
    in BENCH_load_slo.json."""
    from trlx_tpu.inference.supervisor import FleetSupervisor, ThreadReplica

    tok = trainer.tokenizer
    gen_cfg = GenerationConfig(
        max_new_tokens=SLO_MAX_NEW, do_sample=False,
        eos_token_id=tok.eos_token_id, pad_token_id=tok.pad_token_id,
    )

    def boot_server():
        engine = InferenceEngine(
            trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
            num_slots=4, max_prompt_len=64,
        )
        sched = Scheduler(engine, max_queue_depth=64, max_wait_s=0.002)
        server = InferenceServer(sched, tokenizer=tok, host="127.0.0.1", port=0)
        server.start_background()
        return server

    supervisor = FleetSupervisor(
        lambda i: ThreadReplica(boot_server),
        num_replicas=2,
        router_kwargs=dict(replica_retries=1, hedge=False, concurrency=SLO_WORKERS),
        # generous probe budget: on a saturated single-CPU box /healthz
        # competes with decode for the core, and a tight timeout makes the
        # supervisor kill healthy-but-busy replicas. A HARD kill is still
        # detected within one tick via handle.alive, not probes.
        tick_s=0.02, probe_interval_s=0.5, probe_timeout_s=30.0,
        unhealthy_after=4, respawn_backoff_s=0.2, start_timeout_s=300.0,
        sync_interval_s=3600.0,
    ).start()
    try:
        assert supervisor.wait_ready(timeout_s=300.0), "fleet never came up"
        router = supervisor.router
        rng = np.random.RandomState(13)
        # warm every replica's prefill/decode programs before timing
        for seat in supervisor.seats:
            urllib.request.urlopen(
                urllib.request.Request(
                    seat.url + "/generate",
                    data=json.dumps({"prompt_ids": [1] * 6,
                                     "max_new_tokens": 2}).encode(),
                    headers={"Content-Type": "application/json"},
                ),
                timeout=300,
            ).read()

        latencies, ttfts, errors = [], [], []
        lat_lock = threading.Lock()
        next_req = [0]

        tokens_out = [0]

        def worker():
            while True:
                with lat_lock:
                    if next_req[0] >= SLO_REQUESTS:
                        return
                    next_req[0] += 1
                prompt = rng.randint(0, 255, size=int(rng.choice([6, 20, 40]))).tolist()
                t0 = time.perf_counter()
                try:
                    res = router.generate([prompt], max_new_tokens=SLO_MAX_NEW)[0]
                    assert res["finish_reason"] in ("eos", "length")
                    # TTFT is first-class next to total latency: measured
                    # server-side, it must exist and be bounded by it
                    assert 0 < res["ttft_s"] <= res["latency_s"]
                    with lat_lock:
                        latencies.append(time.perf_counter() - t0)
                        ttfts.append(float(res["ttft_s"]))
                        tokens_out[0] += len(res["token_ids"])
                except Exception as e:
                    with lat_lock:
                        errors.append(repr(e))

        threads = [threading.Thread(target=worker) for _ in range(SLO_WORKERS)]
        run_t0 = time.perf_counter()
        for t in threads:
            t.start()

        # mid-run chaos: kill a replica under load, then time the
        # supervisor's detect -> respawn -> full-capacity recovery
        # (against a pre-kill death baseline, so a spurious earlier death
        # can't make recovery look instant)
        time.sleep(1.0)
        deaths_before = supervisor.counters["deaths"]
        # stamp BEFORE shutdown(): it blocks long enough for the
        # supervisor to detect + respawn while it runs
        kill_t = time.perf_counter()
        supervisor.seats[0].handle.server.shutdown()
        recovery_deadline = kill_t + SLO_RECOVERY_S
        recovery_s = None
        while time.perf_counter() < recovery_deadline:
            if (supervisor.counters["deaths"] > deaths_before
                    and supervisor.healthy_active() == 2):
                recovery_s = time.perf_counter() - kill_t
                break
            time.sleep(0.05)

        for t in threads:
            t.join(timeout=600)
        run_elapsed = time.perf_counter() - run_t0

        assert not errors, f"dropped requests under saturation: {errors[:3]}"
        assert len(latencies) == SLO_REQUESTS
        assert recovery_s is not None, (
            f"fleet did not recover to full capacity within {SLO_RECOVERY_S}s"
        )
        p50 = float(np.percentile(latencies, 50))
        p99 = float(np.percentile(latencies, 99))
        # serving-path decode throughput: aggregate from the client side,
        # per-replica from each seat's tokens_generated_total counter
        # (the killed seat's counter restarts with its respawn)
        per_replica_tps = {}
        for seat in supervisor.seats:
            try:
                text = urllib.request.urlopen(
                    seat.url + "/metrics", timeout=30).read().decode()
                for line in text.splitlines():
                    if line.startswith("trlx_tpu_inference_tokens_generated_total"):
                        per_replica_tps[seat.url] = round(
                            float(line.split()[-1]) / run_elapsed, 2)
            except Exception:
                pass
        record = {
            "workers": SLO_WORKERS,
            "requests": SLO_REQUESTS,
            "elapsed_s": round(run_elapsed, 3),
            "throughput_rps": round(SLO_REQUESTS / run_elapsed, 3),
            "decode_tokens_per_s": round(tokens_out[0] / run_elapsed, 2),
            "decode_tokens_per_s_per_replica": per_replica_tps,
            "latency_p50_s": round(p50, 4),
            "latency_p99_s": round(p99, 4),
            "latency_max_s": round(float(np.max(latencies)), 4),
            "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
            "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
            "dropped_requests": len(errors),
            "capacity_recovery_s": round(recovery_s, 3),
            "supervisor": {
                k: v for k, v in supervisor.stats().items()
                if isinstance(v, (int, float))
            },
            "events": list(supervisor.events),
        }
        out_path = RECORD_PATH
        _merge_bench_record(out_path, record)
        print(f"\nsustained-saturation SLO: {json.dumps(record)}")
        assert p50 <= SLO_P50_S, f"p50 {p50:.2f}s blew the {SLO_P50_S}s SLO"
        assert p99 <= SLO_P99_S, f"p99 {p99:.2f}s blew the {SLO_P99_S}s SLO"
        assert supervisor.counters["respawns"] >= 3  # 2 boots + the respawn
    finally:
        supervisor.stop()


# ----------------------------------------------------------------------
# Paged-vs-fixed KV pool A/B at a fixed HBM budget (ISSUE 10)
# ----------------------------------------------------------------------

AB_REQUESTS = 16
AB_MAX_NEW = 8


@pytest.mark.slow
def test_paged_vs_fixed_ab_at_equal_hbm(trainer):
    """Same process, same weights, same 16-request burst, same KV HBM
    budget (2 full-length fixed rows == 6 paged blocks + the zero
    block): the paged pool must hold >= 2x the resident requests, finish
    the burst with zero 503s, and stay bit-identical to the fixed pool's
    greedy outputs. Resident-concurrency and tokens/s for both pools are
    committed to BENCH_load_slo.json under "paged_kv"."""
    tok = trainer.tokenizer
    gen_cfg = GenerationConfig(
        max_new_tokens=AB_MAX_NEW, do_sample=False,
        eos_token_id=10_000, pad_token_id=tok.pad_token_id,
    )
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 255, size=int(n)).tolist()
               for n in np.tile([6, 10, 14, 18], 4)]

    def run(label, **engine_kw):
        engine = InferenceEngine(
            trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
            max_prompt_len=64, **engine_kw,
        )
        sched = Scheduler(engine, max_queue_depth=64, max_wait_s=0.002).start()
        try:
            # warm the prefill bucket + decode program off the clock
            warm = [sched.submit(p, 2) for p in prompts[:2]]
            for r in warm:
                assert r.wait(600)
            t0 = time.perf_counter()
            reqs = [sched.submit(p, AB_MAX_NEW) for p in prompts]
            for r in reqs:
                assert r.wait(600), f"{label}: request timed out"
            elapsed = time.perf_counter() - t0
        finally:
            sched.stop()
        tokens = sum(len(r.token_ids) for r in reqs)
        return {
            "outputs": [r.token_ids for r in reqs],
            "tokens_per_s": round(tokens / elapsed, 2),
            "resident_peak": int(sched.metrics.get("slots_active_peak")),
            "kv_pool_bytes": engine.kv_stats().get("kv_pool_bytes", 0),
        }

    # 2 fixed rows of cache_len=96 == 6 allocatable 32-token blocks
    fixed = run("fixed", num_slots=2)
    paged = run("paged", num_slots=8, kv_paging=True, kv_block_size=32,
                kv_pool_blocks=7, prefix_cache=True)
    assert paged["outputs"] == fixed["outputs"], "paged diverged from fixed"
    ratio = paged["resident_peak"] / max(fixed["resident_peak"], 1)
    record = {
        "requests": AB_REQUESTS,
        "max_new_tokens": AB_MAX_NEW,
        "fixed": {k: v for k, v in fixed.items() if k != "outputs"},
        "paged": {k: v for k, v in paged.items() if k != "outputs"},
        "resident_concurrency_ratio": round(ratio, 2),
        "throughput_ratio": round(
            paged["tokens_per_s"] / max(fixed["tokens_per_s"], 1e-9), 2),
    }
    out_path = RECORD_PATH
    _merge_bench_record(out_path, paged_kv=record)
    print(f"\npaged-vs-fixed A/B: {json.dumps(record)}")
    assert ratio >= 2.0, (
        f"paged resident peak {paged['resident_peak']} is not >= 2x the "
        f"fixed pool's {fixed['resident_peak']} at equal HBM"
    )


# ----------------------------------------------------------------------
# Multi-tenant skewed-workload SLO (ISSUE 12)
# ----------------------------------------------------------------------

MT_MAX_NEW = 8
MT_HOT_REQUESTS = 18     # saturating tenant (3 closed-loop workers)
MT_BG_REQUESTS = 6       # background tenant (1 worker)
MT_P99_S = 120.0         # generous single-CPU-CI bound, like the SLO run


@pytest.mark.slow
def test_multi_tenant_skewed_load_slo(tmp_path):
    """Two tenants on one trunk, heavily skewed (3 hot workers vs 1
    background worker) under fair-share admission: every request from
    BOTH tenants completes with finite latency, per-tenant p50/p99 and
    the resident adapter set are recorded to BENCH_load_slo.json under
    "multi_tenant", and the equal-HBM accounting shows >= 3 adapters
    resident where the same budget fits <= 1 extra monolithic policy."""
    import zlib

    import jax

    from trlx_tpu import resilience
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.inference import AdapterStore
    from trlx_tpu.models.lora import split_lora
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny",
                   peft_config={"peft_type": "LORA", "r": 4, "lora_alpha": 16},
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    mt_trainer = SFTTrainer(config)

    def save_adapter(seed, name):
        def bump(path, x):
            leaf = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
            if "_lora_" in leaf:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(seed), zlib.crc32(leaf.encode()))
                return x + 0.3 * jax.random.normal(key, x.shape, x.dtype)
            return x

        import orbax.checkpoint as ocp

        variant = jax.tree_util.tree_map_with_path(bump, mt_trainer.params)
        lora_flat, _ = split_lora(variant)
        d = str(tmp_path / "adapters" / name)
        ocp.PyTreeCheckpointer().save(
            os.path.join(d, "state"),
            {"train_params": {str(k): np.asarray(v) for k, v in lora_flat.items()}},
            force=True,
        )
        resilience.write_manifest(d, step=1)

    for i, name in enumerate(("hot", "bg", "spare")):
        save_adapter(20 + i, name)

    tok = mt_trainer.tokenizer
    gen_cfg = GenerationConfig(
        max_new_tokens=MT_MAX_NEW, do_sample=False,
        eos_token_id=10_000, pad_token_id=tok.pad_token_id,
    )
    store = AdapterStore(mt_trainer.params,
                         adapter_dir=str(tmp_path / "adapters"), max_resident=4)
    engine = InferenceEngine(
        mt_trainer.model, mt_trainer.model_cfg, mt_trainer.params, gen_cfg,
        num_slots=4, max_prompt_len=64, multi_tenant=True, adapter_store=store,
        kv_paging=True, kv_block_size=16, prefix_cache=True,
    )
    sched = Scheduler(engine, max_queue_depth=64, max_wait_s=0.002,
                      fair_share=True, tenant_weights={"hot": 1.0, "bg": 1.0})
    server = InferenceServer(sched, tokenizer=tok, host="127.0.0.1", port=0)
    url = server.start_background()
    try:
        fn = remote_generate(url, concurrency=4)
        fn([1] * 6, max_new_tokens=2)  # warm prefill + decode programs
        fn([1] * 6, max_new_tokens=2, adapter_id="hot")
        fn([1] * 6, max_new_tokens=2, adapter_id="bg")

        rng = np.random.RandomState(23)
        prompt_pool = [rng.randint(0, 255, size=int(n)).tolist()
                       for n in np.tile([6, 14, 22], 8)]
        latencies = {"hot": [], "bg": []}
        errors = []
        counters = {"hot": 0, "bg": 0}
        lock = threading.Lock()

        def worker(tenant, budget):
            while True:
                with lock:
                    if counters[tenant] >= budget:
                        return
                    counters[tenant] += 1
                    i = counters[tenant]
                t0 = time.perf_counter()
                try:
                    res = fn(prompt_pool[i % len(prompt_pool)],
                             max_new_tokens=MT_MAX_NEW, adapter_id=tenant)
                    assert res["finish_reason"] in ("eos", "length")
                    assert all(isinstance(t, int) for t in res["token_ids"])
                    with lock:
                        latencies[tenant].append(time.perf_counter() - t0)
                except Exception as e:
                    with lock:
                        errors.append((tenant, repr(e)))

        threads = (
            [threading.Thread(target=worker, args=("hot", MT_HOT_REQUESTS))
             for _ in range(3)]
            + [threading.Thread(target=worker, args=("bg", MT_BG_REQUESTS))]
        )
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        elapsed = time.perf_counter() - t0

        assert not errors, f"dropped tenant requests: {errors[:3]}"
        assert len(latencies["hot"]) == MT_HOT_REQUESTS
        assert len(latencies["bg"]) == MT_BG_REQUESTS

        # equal-HBM accounting: one trunk + K tiny adapters vs extra
        # monolithic policies — the S-LoRA consolidation headline
        trunk_bytes = int(sum(
            int(np.prod(np.shape(v))) * np.dtype(np.asarray(v).dtype).itemsize
            for v in jax.tree_util.tree_leaves(mt_trainer.params)))
        budget = trunk_bytes + 3 * store.bytes_per_adapter
        monolithic_extra = (budget - trunk_bytes) // trunk_bytes
        adapters_at_budget = (budget - trunk_bytes) // store.bytes_per_adapter
        assert adapters_at_budget >= 3 and monolithic_extra <= 1

        def pcts(xs):
            return {"p50_s": round(float(np.percentile(xs, 50)), 4),
                    "p99_s": round(float(np.percentile(xs, 99)), 4)}

        record = {
            "elapsed_s": round(elapsed, 3),
            "tenants": {
                "hot": {"requests": MT_HOT_REQUESTS, "workers": 3,
                        **pcts(latencies["hot"])},
                "bg": {"requests": MT_BG_REQUESTS, "workers": 1,
                       **pcts(latencies["bg"])},
            },
            "resident_adapters": store.resident(),
            "adapter_capacity": store.capacity,
            "hbm": {
                "trunk_bytes": trunk_bytes,
                "bytes_per_adapter": store.bytes_per_adapter,
                "adapters_at_equal_hbm": int(adapters_at_budget),
                "extra_monolithic_at_equal_hbm": int(monolithic_extra),
            },
            "store": {k: v for k, v in store.stats().items()
                      if isinstance(v, (int, float))},
        }
        out_path = RECORD_PATH
        _merge_bench_record(out_path, multi_tenant=record)
        print(f"\nmulti-tenant skewed SLO: {json.dumps(record)}")
        for tenant in ("hot", "bg"):
            p99 = record["tenants"][tenant]["p99_s"]
            assert p99 <= MT_P99_S, f"{tenant} p99 {p99:.2f}s blew the SLO"
        assert sorted(store.resident()) == ["bg", "hot"]
    finally:
        server.shutdown()


# ----------------------------------------------------------------------
# Session turn-latency bench: retained-KV follow-up turns vs fresh
# full-concat prefills, recorded under "sessions"
# ----------------------------------------------------------------------

SESS_CONVERSATIONS = 8
SESS_TURNS = 3


@pytest.mark.slow
def test_session_multiturn_ttft_bench(trainer):
    """Concurrent 3-turn conversations against a paged session server:
    every follow-up turn must reuse retained blocks (delta prefill), TTFT
    must be measured and bounded by total latency, and the per-turn TTFT
    percentiles land in BENCH_load_slo.json under "sessions"."""
    tok = trainer.tokenizer
    gen_cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False,
        eos_token_id=tok.eos_token_id, pad_token_id=tok.pad_token_id,
    )
    engine = InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=4, max_prompt_len=128,
        kv_paging=True, kv_block_size=16,
    )
    engine.enable_sessions()
    sched = Scheduler(engine, max_queue_depth=64, max_wait_s=0.002)
    server = InferenceServer(sched, tokenizer=tok, host="127.0.0.1", port=0)
    url = server.start_background()

    def post(path, payload):
        req = urllib.request.Request(
            url + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read().decode())

    try:
        post("/generate", {"prompt_ids": [1] * 6, "max_new_tokens": 2})  # warm
        rng = np.random.RandomState(7)
        first_ttfts, follow_ttfts, errors = [], [], []
        lock = threading.Lock()
        hits = [0]

        def conversation(i):
            try:
                turn = rng.randint(32, 127, size=24).tolist()
                out = post("/chat", {"prompt_ids": turn, "max_new_tokens": 8})
                assert 0 < out["ttft_s"] <= out["latency_s"]
                with lock:
                    first_ttfts.append(out["ttft_s"])
                sid = out["session_id"]
                for _ in range(SESS_TURNS - 1):
                    delta = rng.randint(32, 127, size=8).tolist()
                    out = post("/chat", {"session_id": sid,
                                         "prompt_ids": delta,
                                         "max_new_tokens": 8})
                    assert 0 < out["ttft_s"] <= out["latency_s"]
                    with lock:
                        follow_ttfts.append(out["ttft_s"])
                        hits[0] += int(bool(out["retained_hit"]))
            except Exception as e:
                with lock:
                    errors.append(repr(e))

        threads = [threading.Thread(target=conversation, args=(i,))
                   for i in range(SESS_CONVERSATIONS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)

        assert not errors, f"dropped turns: {errors[:3]}"
        n_follow = SESS_CONVERSATIONS * (SESS_TURNS - 1)
        assert len(follow_ttfts) == n_follow
        # retained KV is doing its job: every follow-up turn reuses blocks
        assert hits[0] == n_follow, f"only {hits[0]}/{n_follow} retained hits"

        stats = engine.session_store.stats()
        record = {
            "conversations": SESS_CONVERSATIONS,
            "turns_per_conversation": SESS_TURNS,
            "retained_hit_rate": round(hits[0] / n_follow, 3),
            "first_turn_ttft_p50_s": round(float(np.percentile(first_ttfts, 50)), 4),
            "followup_ttft_p50_s": round(float(np.percentile(follow_ttfts, 50)), 4),
            "followup_ttft_p99_s": round(float(np.percentile(follow_ttfts, 99)), 4),
            "store": {k: v for k, v in stats.items()
                      if isinstance(v, (int, float))},
        }
        out_path = RECORD_PATH
        _merge_bench_record(out_path, sessions=record)
        print(f"\nsession multiturn bench: {json.dumps(record)}")
    finally:
        server.shutdown()
