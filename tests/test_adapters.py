"""Multi-tenant LoRA serving (trlx_tpu/inference/adapters.py + the
engine/scheduler/server wiring): the adapter store's LRU/refcount/HBM
budget lifecycle, batched heterogeneous-adapter decode that is BITWISE
the per-adapter single-tenant engines, adapter-salted prefix isolation,
weighted fair-share admission, and the /admin/adapters control plane."""

import json
import os
import threading
import time
import urllib.request
import zlib
from collections import Counter, deque

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from trlx_tpu import resilience  # noqa: E402
from trlx_tpu.inference import (  # noqa: E402
    AdapterCapacityError,
    AdapterNotFoundError,
    AdapterStore,
    InferenceEngine,
    InferenceServer,
    QueueFullError,
    Scheduler,
    adapter_salt,
    remote_generate,
)
from trlx_tpu.inference.scheduler import InferenceRequest  # noqa: E402
from trlx_tpu.models.lora import split_lora, zero_lora  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig  # noqa: E402

EOS_FREE = 10_000  # an id the byte model never emits -> length-capped runs
PEFT_CONFIG = {"peft_type": "LORA", "r": 4, "lora_alpha": 16}


@pytest.fixture(scope="module")
def trainer():
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", peft_config=PEFT_CONFIG,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    return SFTTrainer(config)


def _perturb(params, seed):
    """A distinct trained-adapter variant of `params` (nonzero factors)."""

    def bump(path, x):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if "_lora_" in name:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), zlib.crc32(name.encode()))
            return x + 0.3 * jax.random.normal(key, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(bump, params)


def _save_adapter(params, directory, step=1):
    """Write one adapter checkpoint in the trainer `save` layout the
    store loads from (orbax state/ + manifest)."""
    import orbax.checkpoint as ocp

    lora_flat, _ = split_lora(params)
    ocp.PyTreeCheckpointer().save(
        os.path.join(directory, "state"),
        {"train_params": {str(k): np.asarray(v) for k, v in lora_flat.items()}},
        force=True,
    )
    resilience.write_manifest(directory, step=step)


@pytest.fixture(scope="module")
def adapter_dir(trainer, tmp_path_factory):
    """Three trained-adapter checkpoints (a1/a2/a3) + their full param
    variants for single-tenant reference runs."""
    root = tmp_path_factory.mktemp("adapters")
    variants = {}
    for i, name in enumerate(("a1", "a2", "a3")):
        variants[name] = _perturb(trainer.params, seed=10 + i)
        _save_adapter(variants[name], str(root / name))
    return str(root), variants


def make_mt_engine(trainer, store, num_slots=3, max_new=6, **kw):
    gen_cfg = GenerationConfig(
        max_new_tokens=max_new, do_sample=False,
        eos_token_id=EOS_FREE, pad_token_id=trainer.tokenizer.pad_token_id,
    )
    return InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=num_slots, max_prompt_len=64,
        multi_tenant=True, adapter_store=store, **kw,
    )


def run_engine(engine, rows, max_steps=64):
    """Drive the engine directly (no scheduler): insert, step to
    completion, reclaim — returns the emitted token lists."""
    engine.insert_requests(rows, list(range(len(rows))))
    out = [[] for _ in rows]
    done = [False] * len(rows)
    for _ in range(max_steps):
        tok, _, valid, fin = engine.step()
        for i in range(len(rows)):
            if valid[i] and not done[i]:
                out[i].append(int(tok[i]))
            if fin[i] and not done[i]:
                done[i] = True
                engine.reclaim_slots([i])
        if all(done):
            break
    assert all(done), "engine did not finish"
    return out


# ---------------------------------------------------------------------------
# AdapterStore lifecycle
# ---------------------------------------------------------------------------


def test_store_refcount_lru_and_capacity(trainer, adapter_dir):
    adir, _ = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=2)
    assert store.capacity == 2
    assert store.scan() == ["a1", "a2", "a3"]
    # base names are always slot 0 and never refcounted
    for base in (None, "", "base"):
        assert store.acquire(base) == 0
        assert store.known(base)

    s1, s2 = store.acquire("a1"), store.acquire("a2")
    assert sorted((s1, s2)) == [1, 2]
    assert store.resident() == ["a1", "a2"]
    # both pinned -> nothing evictable for a third tenant
    with pytest.raises(AdapterCapacityError):
        store.acquire("a3")
    # double pin, single release keeps it pinned
    assert store.acquire("a1") == s1
    store.release("a1")
    with pytest.raises(AdapterCapacityError):
        store.acquire("a3")
    store.release("a1")  # now idle -> LRU victim
    s3 = store.acquire("a3")
    assert s3 == s1, "a3 must reuse the evicted adapter's slot"
    assert store.resident() == ["a2", "a3"]
    assert store.refcount("a1") == 0
    stats = store.stats()
    assert stats["loads"] == 3 and stats["evictions"] == 1
    assert stats["resident_bytes"] == 2 * stats["bytes_per_adapter"]
    # re-acquiring the evicted adapter reloads it from disk
    store.release("a2")
    assert store.acquire("a1") in (1, 2)
    assert store.stats()["loads"] == 4


def test_store_hbm_budget_caps_capacity(trainer, adapter_dir):
    adir, _ = adapter_dir
    probe = AdapterStore(trainer.params, adapter_dir=adir, max_resident=8)
    per = probe.bytes_per_adapter
    # budget for exactly one adapter wins over max_resident
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=8,
                         hbm_budget_bytes=per + per // 2)
    assert store.capacity == 1
    store.acquire("a1")
    with pytest.raises(AdapterCapacityError):
        store.acquire("a2")
    # a budget that fits no adapter is a config error
    with pytest.raises(ValueError, match="fits no adapter"):
        AdapterStore(trainer.params, adapter_dir=adir, hbm_budget_bytes=per - 1)
    # a lora-free policy cannot back a store
    with pytest.raises(ValueError, match="no \\*_lora_\\* leaves"):
        AdapterStore(zero_params_without_lora(trainer.params))


def zero_params_without_lora(params):
    from flax import traverse_util

    flat = traverse_util.flatten_dict(params)
    return traverse_util.unflatten_dict(
        {k: v for k, v in flat.items() if not any("_lora_" in str(p) for p in k)}
    )


def test_store_unknown_and_reload(trainer, adapter_dir, tmp_path):
    adir, variants = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=2)
    assert not store.known("nope")
    with pytest.raises(AdapterNotFoundError):
        store.acquire("nope")
    with pytest.raises(AdapterNotFoundError):
        store.reload("a1")  # not resident yet

    store.load("a1")  # admin preload: resident but unpinned
    assert store.resident() == ["a1"] and store.refcount("a1") == 0
    assert store.changed() == []
    assert store.reload("a1") is False  # disk version unchanged

    # a newer on-disk checkpoint makes it stale -> reload picks it up
    _save_adapter(_perturb(trainer.params, seed=99), os.path.join(adir, "a1"), step=2)
    assert store.changed() == ["a1"]
    assert store.reload("a1") is True
    assert store.changed() == []
    assert store.stats()["reloads"] == 1
    # restore the fixture's a1 for later tests
    _save_adapter(variants["a1"], os.path.join(adir, "a1"), step=3)
    store.evict("a1")
    assert store.resident() == []


# ---------------------------------------------------------------------------
# Heterogeneous batched decode: bitwise vs single-adapter engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paging", [False, True], ids=["dense", "paged"])
def test_mixed_adapter_batch_bitwise(trainer, adapter_dir, paging):
    """One multi-tenant batch (base + a1 + a2 interleaved) must emit
    greedy tokens bit-identical to three single-adapter engines each
    serving its own merged params — the S-LoRA correctness bar."""
    adir, variants = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=4)
    kw = dict(kv_paging=True, kv_block_size=8, prefix_cache=True) if paging else {}
    engine = make_mt_engine(trainer, store, num_slots=3, **kw)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 255, size=n).tolist() for n in (7, 13, 21)]
    rows = [
        (np.asarray(prompts[0], np.int32), 6, None),
        (np.asarray(prompts[1], np.int32), 6, "a1"),
        (np.asarray(prompts[2], np.int32), 6, "a2"),
    ]
    got = run_engine(engine, rows)

    refs = [zero_lora(trainer.params), variants["a1"], variants["a2"]]
    gen_cfg = GenerationConfig(
        max_new_tokens=6, do_sample=False,
        eos_token_id=EOS_FREE, pad_token_id=trainer.tokenizer.pad_token_id,
    )
    for i, (p, ref_params) in enumerate(zip(prompts, refs)):
        ref = InferenceEngine(
            trainer.model, trainer.model_cfg, ref_params, gen_cfg,
            num_slots=1, max_prompt_len=64, **kw,
        )
        want = run_engine(ref, [(np.asarray(p, np.int32), 6)])[0]
        assert got[i] == want, f"row {i} diverged from its single-adapter engine"
    # pins dropped once requests reclaimed
    assert store.refcount("a1") == 0 and store.refcount("a2") == 0


def test_prefix_salt_isolation(trainer, adapter_dir):
    """The SAME prompt under two tenants must never share prefix blocks
    (cross-tenant K/V reuse would be both wrong and a timing leak);
    repeats under one tenant still hit, and a per-adapter flush drops
    only that tenant's cached blocks."""
    adir, _ = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=4)
    engine = make_mt_engine(
        trainer, store, num_slots=2, max_new=4,
        kv_paging=True, kv_block_size=8, prefix_cache=True,
        prefix_cache_capacity=16,
    )
    p = np.random.RandomState(1).randint(0, 255, size=33).astype(np.int32)
    run_engine(engine, [(p, 4, "a1")])
    run_engine(engine, [(p, 4, "a2")])
    assert engine.kv_stats()["prefix_cache_hits"] == 0, "cross-tenant prefix hit"
    run_engine(engine, [(p, 4, "a1")])
    assert engine.kv_stats()["prefix_cache_hits"] == 1
    # distinct salts -> distinct key spaces (and base stays unsalted so
    # single-tenant caches remain valid when multi-tenancy turns on)
    assert adapter_salt("a1") != adapter_salt("a2")
    assert adapter_salt(None) == adapter_salt("base") == b""
    assert engine.flush_adapter_prefixes("a1") > 0
    run_engine(engine, [(p, 4, "a1")])  # cold again after the flush
    assert engine.kv_stats()["prefix_cache_hits"] == 1
    run_engine(engine, [(p, 4, "a2")])  # a2's blocks survived the a1 flush
    assert engine.kv_stats()["prefix_cache_hits"] == 2


def test_base_flush_does_not_sweep_tenant_prefixes(trainer, adapter_dir):
    """The base policy's salt is empty — flushing it must drop only the
    unsalted key space, not startswith-match every tenant's salted keys."""
    adir, _ = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=4)
    engine = make_mt_engine(
        trainer, store, num_slots=2, max_new=4,
        kv_paging=True, kv_block_size=8, prefix_cache=True,
        prefix_cache_capacity=16,
    )
    p = np.random.RandomState(2).randint(0, 255, size=33).astype(np.int32)
    run_engine(engine, [(p, 4, None)])  # base: unsalted keys
    run_engine(engine, [(p, 4, "a1")])  # tenant: salted keys
    assert engine.flush_adapter_prefixes(None) > 0
    run_engine(engine, [(p, 4, "a1")])  # a1's blocks survived the base flush
    assert engine.kv_stats()["prefix_cache_hits"] == 1
    run_engine(engine, [(p, 4, None)])  # base really is cold again
    assert engine.kv_stats()["prefix_cache_hits"] == 1


def test_lru_evicted_adapter_flushes_stale_prefixes_on_reload(trainer, adapter_dir):
    """Store-internal LRU eviction remembers the evicted adapter's
    version; if its checkpoint moves while it is out of the stack, the
    next load flushes its salted prefixes (cached K/V was computed under
    the old factors). Unchanged checkpoints re-load without a flush."""
    adir, variants = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=1)
    flushed = []
    store.flush_prefixes = flushed.append
    store.load("a1")
    store.load("a2")  # capacity 1: LRU-evicts a1
    assert store.resident() == ["a2"]
    store.load("a1")  # checkpoint unchanged while evicted -> no flush
    assert flushed == []
    store.load("a2")  # a1 out again...
    _save_adapter(_perturb(trainer.params, seed=55),
                  os.path.join(adir, "a1"), step=20)  # ...and it moves on disk
    store.load("a1")  # stale re-load must flush a1's salted prefixes
    assert flushed == ["a1"]
    # restore the fixture's a1 factors for later tests
    _save_adapter(variants["a1"], os.path.join(adir, "a1"), step=21)


# ---------------------------------------------------------------------------
# Fair-share admission (weighted deficit round-robin)
# ---------------------------------------------------------------------------


class _FakeEngine:
    """Just enough engine surface for white-box scheduler tests."""

    num_slots = 4
    max_prefill_batch = 4
    kv_paging = False
    multi_tenant = True

    def blocks_available(self):
        return 0


def _mk_req(tenant, i):
    return InferenceRequest(id=i, prompt_ids=np.zeros(4, np.int32),
                            max_new_tokens=4, deadline=None, adapter_id=tenant)


def _fair_scheduler(weights, tenant_queue_depth=0):
    sched = Scheduler(_FakeEngine(), max_wait_s=0.0, fair_share=True,
                      tenant_weights=weights,
                      tenant_queue_depth=tenant_queue_depth)
    return sched


def test_fair_share_wdrr_order():
    """A saturating hot tenant cannot starve the others: with backlog on
    every tenant, admissions split by weight (vip at 2.0 drains twice as
    fast as cold at 1.0), and the hot tenant only soaks up slots the
    others do not claim."""
    sched = _fair_scheduler({"hot": 1.0, "cold": 1.0, "vip": 2.0})
    i = 0
    for _ in range(20):
        sched._queue.append(_mk_req("hot", i)); i += 1
    for _ in range(5):
        sched._queue.append(_mk_req("cold", i)); i += 1
    for _ in range(5):
        sched._queue.append(_mk_req("vip", i)); i += 1

    admitted = []
    while sched._queue:
        with sched._cond:
            batch, slots, _ = sched._pop_weighted(False, 0)
        assert batch, "fair-share pop stalled with backlog and free slots"
        admitted.extend(sched._tenant(r) for r in batch)
        sched._free.extend(slots)

    counts = Counter(admitted)
    assert counts == {"hot": 20, "cold": 5, "vip": 5}
    # every tenant is served from the very first rounds
    assert set(admitted[:8]) == {"hot", "cold", "vip"}
    first16 = Counter(admitted[:16])
    assert first16["vip"] >= first16["cold"], "weight 2.0 must not trail weight 1.0"


def test_fair_share_skips_blocked_tenants():
    """A tenant mid adapter-hot-reload (drain_tenant) is skipped without
    stalling the others; resume_tenant reopens it."""
    sched = _fair_scheduler({})
    sched._blocked_tenants.add("hot")
    sched._queue.extend([_mk_req("hot", 0), _mk_req("cold", 1)])
    with sched._cond:
        batch, slots, _ = sched._pop_weighted(False, 0)
    assert [sched._tenant(r) for r in batch] == ["cold"]
    assert len(sched._queue) == 1 and sched._queue[0].adapter_id == "hot"
    sched._free.extend(slots)
    sched.resume_tenant("hot")
    with sched._cond:
        batch, _, _ = sched._pop_weighted(False, 0)
    assert [sched._tenant(r) for r in batch] == ["hot"]


def test_per_tenant_queue_depth_cap():
    """tenant_queue_depth bounds EACH tenant's backlog: the hot tenant
    gets 503-style QueueFullError while a quiet tenant still enqueues."""
    sched = _fair_scheduler({}, tenant_queue_depth=2)
    sched._running = True  # white-box: enqueue without the driver thread
    sched._enqueue([_mk_req("hot", 0)])
    sched._enqueue([_mk_req("hot", 1)])
    with pytest.raises(QueueFullError):
        sched._enqueue([_mk_req("hot", 2)])
    sched._enqueue([_mk_req("cold", 3)])  # other tenants unaffected
    assert len(sched._queue) == 3


def test_admission_sheds_over_capacity_adapter_burst(trainer, adapter_dir):
    """A burst of more distinct tenants than the store has slots into an
    IDLE pool must not livelock: admission sheds tenant groups until the
    rest fit (head group always admits), and the shed tenants admit once
    the first wave's pins drop — every request still completes."""
    adir, _ = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=1)
    engine = make_mt_engine(trainer, store, num_slots=2, max_new=4)
    sched = Scheduler(engine, max_wait_s=0.0, fair_share=True)
    reqs = [_mk_req("a1", 0), _mk_req("a2", 1)]
    sched._queue.extend(reqs)

    sched._admit()  # capacity 1: only one tenant's request can pin
    assert len(sched._slot_req) == 1, "over-capacity burst must shrink, not requeue"
    assert len(sched._queue) == 1
    while sched._slot_req:
        sched._decode_once()
    sched._admit()  # the first tenant is idle now -> LRU slot frees
    assert len(sched._slot_req) == 1 and not sched._queue
    while sched._slot_req:
        sched._decode_once()
    assert all(r.finish_reason == "length" for r in reqs)
    assert store.stats()["evictions"] >= 1


def test_drain_tenant_sees_mid_admission_requests():
    """A request popped for admission but not yet registered in a slot
    already holds its adapter pin — drain_tenant must wait for it, or a
    hot-reload races the pin and silently defers."""
    sched = _fair_scheduler({})
    sched._admitting = [_mk_req("a1", 0)]
    assert sched.drain_tenant("a1", timeout_s=0.05) is False
    sched.resume_tenant("a1")
    sched._admitting = []
    assert sched.drain_tenant("a1", timeout_s=0.05) is True
    sched.resume_tenant("a1")


def test_tiny_weight_tops_up_in_one_step():
    """Deficit top-up is O(1) per admission round, not O(1/weight): a
    lone tenant at weight 1e-6 must pop immediately instead of spinning
    ~1e6 iterations under the scheduler condition lock."""
    sched = _fair_scheduler({"slow": 1e-6})
    sched._queue.append(_mk_req("slow", 0))
    t0 = time.monotonic()
    with sched._cond:
        batch, _, _ = sched._pop_weighted(False, 0)
    assert [sched._tenant(r) for r in batch] == ["slow"]
    assert time.monotonic() - t0 < 0.5

    with pytest.raises(ValueError, match="must be > 0"):
        _fair_scheduler({"bad": 0.0})


def test_adapter_id_validation(trainer, adapter_dir):
    """adapter_id against a single-tenant engine is a 400-class error;
    unknown adapters are rejected at submit time, not at decode."""
    adir, _ = adapter_dir
    gen_cfg = GenerationConfig(
        max_new_tokens=4, do_sample=False,
        eos_token_id=EOS_FREE, pad_token_id=trainer.tokenizer.pad_token_id,
    )
    plain = InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=1, max_prompt_len=64,
    )
    sched = Scheduler(plain, max_wait_s=0.0)
    with pytest.raises(ValueError, match="multi_tenant"):
        sched._validate(np.asarray([1, 2, 3], np.int32), 4, adapter_id="a1")

    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=2)
    mt = make_mt_engine(trainer, store, num_slots=1, max_new=4)
    sched_mt = Scheduler(mt, max_wait_s=0.0)
    with pytest.raises(ValueError, match="unknown adapter"):
        sched_mt._validate(np.asarray([1, 2, 3], np.int32), 4, adapter_id="nope")
    sched_mt._validate(np.asarray([1, 2, 3], np.int32), 4, adapter_id="a1")
    sched_mt._validate(np.asarray([1, 2, 3], np.int32), 4, adapter_id=None)


# ---------------------------------------------------------------------------
# Server control plane + per-adapter metrics
# ---------------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_server_multi_tenant_end_to_end(trainer, adapter_dir):
    """The HTTP surface: adapter_id routed per request, /admin/adapters
    list/load/evict/reload, healthz resident set, per-adapter labeled
    Prometheus series, and per-adapter hot-reload on checkpoint change."""
    adir, variants = adapter_dir
    store = AdapterStore(trainer.params, adapter_dir=adir, max_resident=2)
    engine = make_mt_engine(trainer, store, num_slots=2, max_new=4)
    sched = Scheduler(engine, max_wait_s=0.0, fair_share=True)
    # a huge poll interval keeps the background watcher quiet so the
    # poll_adapters() assertions below are deterministic
    server = InferenceServer(sched, tokenizer=trainer.tokenizer,
                             host="127.0.0.1", port=0,
                             reload_interval_s=3600.0)
    url = server.start_background()
    try:
        fn = remote_generate(url)
        base_out = fn([1, 2, 3, 4], max_new_tokens=4)
        a1_out = fn([1, 2, 3, 4], max_new_tokens=4, adapter_id="a1")
        assert base_out["finish_reason"] in ("eos", "length")
        assert a1_out["finish_reason"] in ("eos", "length")
        assert base_out["token_ids"] != a1_out["token_ids"], (
            "adapter a1 must decode differently from the base policy"
        )

        snap = json.loads(_get(url + "/admin/adapters"))
        assert snap["resident"] == ["a1"]
        assert snap["available"] == ["a1", "a2", "a3"]
        assert snap["stats"]["loads"] == 1

        health = json.loads(_get(url + "/healthz"))
        assert health["adapters"]["resident"] == ["a1"]
        assert health["adapters"]["capacity"] == 2

        metrics = _get(url + "/metrics")
        assert 'adapter_requests_total{adapter="a1"' in metrics
        assert 'adapter_tokens_generated_total{adapter="a1"}' in metrics
        assert 'adapter_request_latency_seconds_bucket{adapter="a1",le=' in metrics
        assert "trlx_tpu_inference_adapters_resident 1" in metrics

        # admin preload + eviction round trip
        out = _post(url + "/admin/adapters", {"load": "a2"})
        assert "a2" in out["resident"]
        out = _post(url + "/admin/adapters", {"evict": "a2"})
        assert out["resident"] == ["a1"]
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url + "/admin/adapters", {"evict": "nope"})
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url + "/generate", {"prompt_ids": [1, 2], "adapter_id": "nope"})
        assert err.value.code == 400

        # per-adapter hot-reload: a newer a1 checkpoint changes a1's
        # decode without touching the trunk or other tenants
        _save_adapter(_perturb(trainer.params, seed=77),
                      os.path.join(adir, "a1"), step=9)
        out = _post(url + "/admin/adapters", {"reload": "a1"})
        assert out["reloaded"] is True
        a1_new = fn([1, 2, 3, 4], max_new_tokens=4, adapter_id="a1")
        assert a1_new["token_ids"] != a1_out["token_ids"]
        base_again = fn([1, 2, 3, 4], max_new_tokens=4)
        assert base_again["token_ids"] == base_out["token_ids"]
        # watcher-side detection path: restore the fixture checkpoint
        # and let poll_adapters pick it up (no admin call)
        _save_adapter(variants["a1"], os.path.join(adir, "a1"), step=10)
        assert server.watcher.poll_adapters() == 1
        a1_back = fn([1, 2, 3, 4], max_new_tokens=4, adapter_id="a1")
        assert a1_back["token_ids"] == a1_out["token_ids"]
    finally:
        server.shutdown()
