"""Paged KV-cache pool (trlx_tpu/inference/paging.py + the engine's
kv_paging mode): block-table gather/scatter decode must stay bit-identical
to the fresh-batch greedy path, the prefix store must share prompt blocks
with correct refcounts and LRU eviction, int8 KV must complete within
tolerance, and a paged pool must hold strictly more resident requests
than the fixed-slot pool at the same HBM budget."""

import json
import urllib.request

import numpy as np
import pytest

from trlx_tpu.inference import (
    BlockPool,
    InferenceEngine,
    InferenceServer,
    KVPoolExhaustedError,
    QueueFullError,
    Scheduler,
    prefix_keys,
)
from trlx_tpu.inference.scheduler import InferenceRequest
from trlx_tpu.ops.sampling import GenerationConfig

EOS_FREE = 10_000  # an id the byte model never emits -> length-capped runs


@pytest.fixture(scope="module")
def trainer():
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    return SFTTrainer(config)


def direct_generate(trainer, prompt_ids, max_new):
    ids = np.asarray([prompt_ids], np.int32)
    mask = np.ones_like(ids)
    out = trainer.generate(
        ids, mask, gen_kwargs=dict(max_new_tokens=max_new, do_sample=False)
    )
    toks = np.asarray(out["response_tokens"])[0]
    m = np.asarray(out["response_mask"])[0]
    return toks[m > 0].tolist()


def make_engine(trainer, num_slots=2, max_new=8, max_prompt_len=64, **kw):
    gen_cfg = GenerationConfig(
        max_new_tokens=max_new, do_sample=False,
        eos_token_id=EOS_FREE, pad_token_id=trainer.tokenizer.pad_token_id,
    )
    return InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=num_slots, max_prompt_len=max_prompt_len, **kw,
    )


# ----------------------------------------------------------------------
# BlockPool host-side units (no device work)
# ----------------------------------------------------------------------

def test_prefix_keys_block_boundaries():
    bs = 4
    # shorter than one block: nothing to share
    assert prefix_keys(np.arange(3), bs) == []
    # exactly one block: still nothing — at least one token must prefill
    assert prefix_keys(np.arange(4), bs) == []
    # one block + 1: the first block is shareable
    keys = prefix_keys(np.arange(5), bs)
    assert len(keys) == 1
    assert keys[0] == np.arange(4, dtype=np.int32).tobytes()
    # chained keys each cover a strictly longer prefix
    keys = prefix_keys(np.arange(13), bs)
    assert len(keys) == 3
    assert keys[2] == np.arange(12, dtype=np.int32).tobytes()


def test_block_pool_alloc_release_accounting():
    pool = BlockPool(num_blocks=5, block_size=4)
    assert pool.total == 4 and pool.available() == 4 and pool.in_use() == 0
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a  # the zero block is never handed out
    assert pool.available() == 1 and pool.in_use() == 3
    with pytest.raises(KVPoolExhaustedError):
        pool.alloc(2)
    pool.release(a)
    assert pool.available() == 4 and pool.in_use() == 0


def test_block_pool_prefix_refcounts_and_idle():
    pool = BlockPool(num_blocks=6, block_size=4, prefix_cache=True)
    ids = np.arange(5, dtype=np.int32)
    (key,) = prefix_keys(ids, 4)
    (blk,) = pool.alloc(1)
    pool.register(key, blk)
    assert pool.refcount(blk) == 1
    # a second holder takes a reference instead of a new block
    assert pool.acquire_cached(key) == blk
    assert pool.refcount(blk) == 2
    assert pool.lookup_chain(ids) == 1
    # releases: refcount 2 -> 1 -> 0; at zero the CACHED block parks idle
    pool.release([blk])
    assert pool.refcount(blk) == 1 and pool.cached_idle() == 0
    pool.release([blk])
    assert pool.refcount(blk) == 0 and pool.cached_idle() == 1
    # still serving lookups while idle, and resurrection re-refs it
    assert pool.lookup_chain(ids) == 1
    assert pool.acquire_cached(key) == blk and pool.refcount(blk) == 1
    pool.release([blk])


def test_block_pool_lru_eviction_under_pressure():
    pool = BlockPool(num_blocks=4, block_size=4, prefix_cache=True)
    keys = [bytes([i]) for i in range(3)]
    blocks = pool.alloc(3)
    for k, b in zip(keys, blocks):
        pool.register(k, b)
    pool.release(blocks)  # all idle now, LRU order = registration order
    assert pool.cached_idle() == 3 and pool.available() == 3
    # allocation pressure evicts the OLDEST idle entry first
    got = pool.alloc(1)
    assert pool.evictions == 1
    assert keys[0] not in pool._store  # oldest evicted
    assert keys[1] in pool._store and keys[2] in pool._store
    pool.release(got)


def test_block_pool_unregister_rolls_back_cleanly():
    pool = BlockPool(num_blocks=4, block_size=4, prefix_cache=True)
    (blk,) = pool.alloc(1)
    pool.register(b"k", blk)
    pool.unregister(b"k")
    # the key is gone and the block recycles as uncached (straight to free)
    assert pool.acquire_cached(b"k") is None
    pool.release([blk])
    assert pool.cached_idle() == 0 and pool.available() == 3


def test_block_pool_flush_forgets_prefixes():
    pool = BlockPool(num_blocks=5, block_size=4, prefix_cache=True)
    held = pool.alloc(1)[0]
    idle = pool.alloc(1)[0]
    pool.register(b"held", held)
    pool.register(b"idle", idle)
    pool.release([idle])
    pool.flush_cached()
    assert pool.acquire_cached(b"held") is None
    assert pool.acquire_cached(b"idle") is None
    assert pool.cached_idle() == 0
    # the still-referenced block frees later like an ordinary one
    assert pool.available() == 3
    pool.release([held])
    assert pool.available() == 4


def test_block_pool_idle_capacity_trim():
    pool = BlockPool(num_blocks=6, block_size=4, prefix_cache=True,
                     idle_capacity=1)
    blocks = pool.alloc(3)
    for i, b in enumerate(blocks):
        pool.register(bytes([i]), b)
    pool.release(blocks)
    # only the most recent idle entry survives the capacity trim
    assert pool.cached_idle() == 1
    assert pool.evictions == 2


# ----------------------------------------------------------------------
# Paged decode: bit-identity, prefix sharing, int8
# ----------------------------------------------------------------------

def run_requests(engine, prompts, max_news, **sched_kw):
    sched = Scheduler(engine, max_wait_s=0.0, **sched_kw).start()
    try:
        reqs = [sched.submit(p, m) for p, m in zip(prompts, max_news)]
        for r in reqs:
            assert r.wait(300), "request timed out"
        return reqs, sched
    finally:
        sched.stop()


def test_paged_greedy_bit_identical_across_slot_reuse(trainer):
    """2 slots, 5 mixed-length requests through the paged pool: every
    greedy output matches fresh-batch trainer.generate token-for-token —
    including requests inserted into slots freed mid-flight — and every
    block returns to the pool afterwards."""
    engine = make_engine(trainer, num_slots=2, max_new=8,
                         kv_paging=True, kv_block_size=16)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 255, size=n).tolist() for n in (5, 37, 12, 50, 29)]
    max_news = [8, 5, 7, 8, 3]
    reqs, _ = run_requests(engine, prompts, max_news)
    for p, m, r in zip(prompts, max_news, reqs):
        assert r.finish_reason in ("eos", "length")
        assert r.token_ids == direct_generate(trainer, p, m), (
            f"paged output diverged for prompt len {len(p)}"
        )
    stats = engine.kv_stats()
    assert stats["kv_blocks_used"] == 0
    assert stats["kv_blocks_free"] == stats["kv_blocks_total"]


def test_prefix_cache_hit_and_block_reuse(trainer):
    """The same 40-token prompt served twice: the second request reuses
    the stored prompt blocks (>=1 hit), produces the identical greedy
    output, and the shared blocks park idle (not freed) after release."""
    engine = make_engine(trainer, num_slots=2, max_new=6,
                         kv_paging=True, kv_block_size=16, prefix_cache=True)
    p = np.random.RandomState(5).randint(0, 255, size=40).tolist()
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        r1 = sched.submit(p, 6)
        assert r1.wait(300)
        r2 = sched.submit(p, 6)
        assert r2.wait(300)
    finally:
        sched.stop()
    want = direct_generate(trainer, p, 6)
    assert r1.token_ids == want
    assert r2.token_ids == want, "prefix-shared decode diverged"
    stats = engine.kv_stats()
    assert stats["prefix_cache_hits"] >= 1
    assert stats["prefix_cache_idle_blocks"] >= 1
    assert stats["kv_blocks_used"] == 0


def test_submit_n_fanout_shares_prompt_blocks(trainer):
    """GRPO-style fan-out: submit_n(prompt, 3) admits three adjacent
    requests in one batch; the paged engine defers the duplicates one
    placement round and serves them from the first request's prompt
    blocks — all three outputs match the fresh-batch reference."""
    engine = make_engine(trainer, num_slots=4, max_new=6,
                         kv_paging=True, kv_block_size=16, prefix_cache=True)
    p = np.random.RandomState(11).randint(0, 255, size=37).tolist()
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        reqs = sched.submit_n(p, 3, max_new_tokens=6)
        assert len(reqs) == 3
        for r in reqs:
            assert r.wait(300)
    finally:
        sched.stop()
    want = direct_generate(trainer, p, 6)
    for r in reqs:
        assert r.token_ids == want, "fan-out sequence diverged"
    stats = engine.kv_stats()
    assert stats["prefix_cache_hits"] >= 2  # both duplicates shared
    assert stats["kv_blocks_used"] == 0


def test_submit_n_one_is_byte_equivalent_to_submit(trainer):
    """submit_n(p, 1) must be indistinguishable from submit(p): same
    single-request admission, byte-identical greedy output, and no
    prefix-cache traffic difference between the two paths."""
    engine = make_engine(trainer, num_slots=2, max_new=6,
                         kv_paging=True, kv_block_size=16, prefix_cache=True)
    p = np.random.RandomState(21).randint(0, 255, size=23).tolist()
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        reqs = sched.submit_n(p, 1, max_new_tokens=6)
        assert len(reqs) == 1
        assert reqs[0].wait(300)
        single = sched.submit(p, max_new_tokens=6)
        assert single.wait(300)
    finally:
        sched.stop()
    assert reqs[0].token_ids == single.token_ids
    assert reqs[0].token_ids == direct_generate(trainer, p, 6)
    assert reqs[0].finish_reason == single.finish_reason == "length"
    assert reqs[0].max_new_tokens == single.max_new_tokens


def test_submit_n_rejects_bad_n(trainer):
    engine = make_engine(trainer, num_slots=2, max_new=4,
                         kv_paging=True, kv_block_size=16)
    sched = Scheduler(engine, max_wait_s=0.0)
    with pytest.raises(ValueError):
        sched.submit_n([1, 2, 3], 0, max_new_tokens=4)


def test_int8_kv_within_tolerance(trainer):
    """int8 KV (per-token-per-head symmetric scales) must complete every
    request with a valid finish and track the f32 greedy path closely —
    on this model the argmax sequence should rarely flip."""
    engine = make_engine(trainer, num_slots=2, max_new=8,
                         kv_paging=True, kv_block_size=16,
                         kv_cache_dtype="int8")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 255, size=n).tolist() for n in (5, 37, 12, 50, 29)]
    max_news = [8, 5, 7, 8, 3]
    reqs, _ = run_requests(engine, prompts, max_news)
    matches = 0
    for p, m, r in zip(prompts, max_news, reqs):
        assert r.finish_reason in ("eos", "length")
        assert len(r.token_ids) == m
        matches += int(r.token_ids == direct_generate(trainer, p, m))
    assert matches >= 4, f"int8 KV diverged on {5 - matches}/5 greedy runs"
    # int8 arenas plus f32 scale planes cost less than half the f32 pool
    f32 = make_engine(trainer, num_slots=2, max_new=8,
                      kv_paging=True, kv_block_size=16)
    assert engine.kv_stats()["kv_pool_bytes"] < 0.5 * f32.kv_stats()["kv_pool_bytes"]


def test_kv_quantization_roundtrip_bound():
    import jax.numpy as jnp

    from trlx_tpu.ops.quant import dequantize_kv, quantize_kv

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 8, 2, 16).astype(np.float32)) * 3.0
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    err = np.abs(np.asarray(dequantize_kv(q, scale, jnp.float32)) - np.asarray(x))
    # symmetric rounding: error bounded by half a quantization step
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert np.all(err <= 0.5 * amax / 127.0 + 1e-6)


# ----------------------------------------------------------------------
# Fragmentation / admission: paged holds more residents at equal HBM
# ----------------------------------------------------------------------

def test_paged_beats_fixed_resident_concurrency_at_equal_hbm(trainer):
    """At the HBM budget of a 2-slot fixed pool (2 full-length cache
    rows), the paged pool holds >= 2x the concurrent requests: admission
    is paused, 8 one-block requests are queued, and resuming admits as
    many as the block budget allows in one batch."""
    # cache_len = round_up(32 + 4, 16) = 48 -> 3 blocks per full row;
    # 2 fixed rows = 6 allocatable blocks (+ the reserved zero block)
    paged = make_engine(trainer, num_slots=8, max_new=4, max_prompt_len=32,
                        kv_paging=True, kv_block_size=16, kv_pool_blocks=7)
    assert paged.total_blocks == 6
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 255, size=4).tolist() for _ in range(8)]
    sched = Scheduler(paged, max_wait_s=0.0, max_queue_depth=16).start()
    try:
        sched.pause_admission()
        reqs = [sched.submit(p, 4) for p in prompts]  # zero 503s
        sched.resume_admission()
        for r in reqs:
            assert r.wait(300)
    finally:
        sched.stop()
    for p, r in zip(prompts, reqs):
        assert r.token_ids == direct_generate(trainer, p, 4)
    peak = int(sched.metrics.get("slots_active_peak"))
    fixed_peak = 2  # by construction: the same HBM buys 2 fixed slots
    assert peak >= 2 * fixed_peak, (
        f"paged resident peak {peak} < 2x the fixed pool's {fixed_peak}"
    )


def test_fixed_pool_503s_where_paged_fits(trainer):
    """The fragmentation regression pinned: a burst that 503s against the
    fixed-slot pool (2 slots + depth-2 queue) is fully absorbed by a
    paged pool at the same HBM budget (more slots, same bytes — excess
    requests queue for blocks instead of bouncing)."""
    fixed = make_engine(trainer, num_slots=2, max_new=4, max_prompt_len=32)
    sched = Scheduler(fixed, max_wait_s=0.0, max_queue_depth=2).start()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 255, size=4).tolist() for _ in range(8)]
    rejected = 0
    reqs = []
    try:
        # rapid burst: the driver thread is busy compiling/running the
        # first prefill while these enqueue, so the depth-2 queue fills
        for p in prompts:
            try:
                reqs.append(sched.submit(p, 4))
            except QueueFullError as e:
                assert e.retry_after > 0
                rejected += 1
        for r in reqs:
            assert r.wait(300)
    finally:
        sched.stop()
    assert rejected >= 1, "fixed-slot burst never hit backpressure"

    paged = make_engine(trainer, num_slots=8, max_new=4, max_prompt_len=32,
                        kv_paging=True, kv_block_size=16, kv_pool_blocks=7)
    sched = Scheduler(paged, max_wait_s=0.0, max_queue_depth=8).start()
    try:
        reqs = [sched.submit(p, 4) for p in prompts]  # no QueueFullError
        for r in reqs:
            assert r.wait(300)
    finally:
        sched.stop()
    for p, r in zip(prompts, reqs):
        assert r.token_ids == direct_generate(trainer, p, 4)


def test_admission_defers_when_blocks_short(trainer):
    """Block-aware admission: with free slots but a nearly-empty block
    pool, the FIFO head waits instead of exhausting the pool — nothing
    errors and every request completes once earlier ones release."""
    # 3 usable blocks; each request needs ceil((24 + 4)/16) = 2
    paged = make_engine(trainer, num_slots=4, max_new=4, max_prompt_len=32,
                        kv_paging=True, kv_block_size=16, kv_pool_blocks=4)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 255, size=24).tolist() for _ in range(4)]
    reqs, _ = run_requests(paged, prompts, [4] * 4, max_queue_depth=8)
    for p, r in zip(prompts, reqs):
        assert r.token_ids == direct_generate(trainer, p, 4)
    stats = paged.kv_stats()
    assert stats["kv_blocks_used"] == 0


# ----------------------------------------------------------------------
# Retry-After prediction
# ----------------------------------------------------------------------

def test_retry_after_derived_from_decode_latency(trainer):
    engine = make_engine(trainer, num_slots=2, max_new=8)
    sched = Scheduler(engine, max_queue_depth=1)
    # no decode signal yet: the queue-wave fallback stays >= 1s
    assert sched._predicted_retry_after() >= 1.0
    # with an observed decode EWMA and one in-flight request 15 tokens
    # from its budget, the prediction is latency x remaining steps
    sched._decode_ewma = 0.02
    req = InferenceRequest(id=0, prompt_ids=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=20, deadline=None)
    req.token_ids.extend([7] * 5)
    sched._slot_req[0] = req
    assert sched._predicted_retry_after() == pytest.approx(0.02 * 15)
    # the floor keeps clients from hammering a nearly-free pool
    sched._decode_ewma = 1e-6
    assert sched._predicted_retry_after() == pytest.approx(0.05)


def test_submit_rejects_request_that_can_never_fit(trainer):
    paged = make_engine(trainer, num_slots=2, max_new=8, max_prompt_len=32,
                        kv_paging=True, kv_block_size=16, kv_pool_blocks=2)
    sched = Scheduler(paged).start()
    try:
        with pytest.raises(ValueError, match="never"):
            sched.submit(list(range(30)), 8)  # needs 3 blocks, pool holds 1
    finally:
        sched.stop()


# ----------------------------------------------------------------------
# Composition: hot swap, engine validation
# ----------------------------------------------------------------------

def test_hot_swap_flushes_prefix_store(trainer):
    """set_params invalidates every cached prefix (stale-weights K/V must
    not serve new requests) and post-swap decodes stay correct."""
    engine = make_engine(trainer, num_slots=2, max_new=6,
                         kv_paging=True, kv_block_size=16, prefix_cache=True)
    p = np.random.RandomState(8).randint(0, 255, size=40).tolist()
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        r1 = sched.submit(p, 6)
        assert r1.wait(300)
        assert engine.kv_stats()["prefix_cache_idle_blocks"] >= 1
        engine.set_params(trainer.params)  # same weights, new version
        assert engine.kv_stats()["prefix_cache_idle_blocks"] == 0
        r2 = sched.submit(p, 6)
        assert r2.wait(300)
    finally:
        sched.stop()
    want = direct_generate(trainer, p, 6)
    assert r1.token_ids == want and r2.token_ids == want
    # the second run re-prefilled from scratch: a miss, not a stale hit
    assert engine.kv_stats()["prefix_cache_misses"] >= 1


def test_paged_engine_validation(trainer):
    with pytest.raises(NotImplementedError, match="int8"):
        make_engine(trainer, kv_cache_dtype="int8")  # needs kv_paging
    with pytest.raises(ValueError, match="prefix_cache"):
        make_engine(trainer, prefix_cache=True)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        make_engine(trainer, kv_paging=True, kv_cache_dtype="fp8")


# ----------------------------------------------------------------------
# Serving surface: n fan-out + kv occupancy on /healthz and /metrics
# ----------------------------------------------------------------------

def test_server_n_fanout_and_kv_metrics(trainer):
    engine = make_engine(trainer, num_slots=4, max_new=6,
                         kv_paging=True, kv_block_size=16, prefix_cache=True)
    sched = Scheduler(engine, max_wait_s=0.0)
    server = InferenceServer(sched, tokenizer=trainer.tokenizer,
                             host="127.0.0.1", port=0)
    url = server.start_background()
    try:
        p = np.random.RandomState(12).randint(0, 255, size=37).tolist()
        body = json.dumps({"prompt_ids": p, "n": 3, "max_new_tokens": 6}).encode()
        resp = json.loads(urllib.request.urlopen(
            urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"},
            ),
            timeout=300,
        ).read())
        assert resp["n"] == 3 and len(resp["sequences"]) == 3
        want = direct_generate(trainer, p, 6)
        for seq in resp["sequences"]:
            assert seq["token_ids"] == want
            assert seq["finish_reason"] in ("eos", "length")
        health = json.loads(
            urllib.request.urlopen(url + "/healthz", timeout=60).read()
        )
        assert health["kv"]["kv_blocks_total"] == engine.total_blocks
        assert health["kv"]["prefix_cache_hits"] >= 2
        metrics = urllib.request.urlopen(url + "/metrics", timeout=60).read().decode()
        assert "trlx_tpu_inference_kv_blocks_free" in metrics
        assert "trlx_tpu_inference_kv_pool_bytes" in metrics
        assert "trlx_tpu_inference_prefix_cache_hits" in metrics
    finally:
        server.shutdown()
