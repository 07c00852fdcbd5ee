"""`InferenceEngine.step` keeps one decode program in flight: a call
dispatches the next step before it waits for the one dispatched a call
earlier, and still returns one step a call, in order. What that lag must
not break: greedy outputs stay those of a fresh-batch `trainer.generate`
in every mode of the engine, and a slot's output reaches only the request
that held the slot when the step was dispatched."""

import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from trlx_tpu.inference import AdapterStore, InferenceEngine, Scheduler  # noqa: E402
from trlx_tpu.ops.sampling import GenerationConfig  # noqa: E402

EOS_FREE = 10_000  # an id the byte model never emits -> length-capped runs


def _trainer(**model_kw):
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny", model_extra_configs={"dtype": "float32"}, **model_kw),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    return SFTTrainer(config)


@pytest.fixture(scope="module")
def trainer():
    return _trainer()


@pytest.fixture(scope="module")
def lora_trainer():
    return _trainer(peft_config={"peft_type": "LORA", "r": 4, "lora_alpha": 16})


def direct_generate(trainer, prompt_ids, max_new):
    """The fresh-batch reference: greedy `trainer.generate` on one prompt,
    its tokens and the logprob captured for each."""
    ids = np.asarray([prompt_ids], np.int32)
    out = trainer.generate(ids, np.ones_like(ids), capture=True,
                           gen_kwargs=dict(max_new_tokens=max_new, do_sample=False, eos_token_id=EOS_FREE))
    keep = np.asarray(out["response_mask"])[0] > 0
    return np.asarray(out["response_tokens"])[0][keep].tolist(), np.asarray(out["logprobs"])[0][keep]


def make_engine(trainer, num_slots=2, max_new=8, **kw):
    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=False, eos_token_id=EOS_FREE,
                               pad_token_id=trainer.tokenizer.pad_token_id)
    return InferenceEngine(trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
                           num_slots=num_slots, max_prompt_len=64, **kw)


def prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 255, size=n).astype(np.int32)


def assert_fresh_batch(trainer, req):
    want_tokens, want_lp = direct_generate(trainer, req.prompt_ids.tolist(), req.max_new_tokens)
    assert req.token_ids == want_tokens
    np.testing.assert_allclose(req.token_logprobs, want_lp, atol=1e-5)


# ----------------------------------------------------------------------
# (a) the order of dispatch and fetch
# ----------------------------------------------------------------------

def test_next_step_is_dispatched_before_the_fetch(trainer, monkeypatch):
    engine = make_engine(trainer, num_slots=2, max_new=4)
    p, q = prompt(0, 9), prompt(1, 40)
    want = direct_generate(trainer, p.tolist(), 4)[0]
    want_q = direct_generate(trainer, q.tolist(), 4)[0]
    events, step_of, kept = [], {}, []
    decode, get = engine._decode_fn, jax.device_get

    def logged_decode(*args):
        out = decode(*args)
        kept.append(out[1])  # alive to the end: a freed array's id may name the next one
        step_of[id(out[1])] = len(step_of) + 1
        events.append(("dispatch", step_of[id(out[1])]))
        return out

    def logged_get(x):
        events.append(("fetch", step_of[id(x[0])]))
        return get(x)

    engine._decode_fn = logged_decode
    monkeypatch.setattr(jax, "device_get", logged_get)

    engine.insert_requests([(p, 4)], [0])
    got = []
    for call in range(4):
        tok, _, emitted, finished = engine.step()
        assert emitted.tolist() == [True, False]
        got.append(int(tok[0]))
        assert bool(finished[0]) == (call == 3)
    # nothing was in flight at the first call: its own step, the one after,
    # then the wait for its own; from then on one step ahead of every fetch
    assert events == [("dispatch", 1), ("dispatch", 2), ("fetch", 1), ("dispatch", 3), ("fetch", 2),
                      ("dispatch", 4), ("fetch", 3), ("dispatch", 5), ("fetch", 4)]
    assert got == want  # the k-th call returned the k-th step

    # the engine stood without an active row: step 5 holds nothing anybody
    # waits for, so the first call after an insert returns that row's first token
    engine.reclaim_slots([0])
    del events[:]
    engine.insert_requests([(q, 4)], [1])
    tok, _, emitted, _ = engine.step()
    assert events == [("dispatch", 6), ("dispatch", 7), ("fetch", 6)]
    assert emitted.tolist() == [False, True] and int(tok[1]) == want_q[0]


def test_row_inserted_behind_a_step_in_flight_emits_one_call_later(trainer):
    """A row inserted after call k used to emit at call k+1. Step k+1 is
    now on the device before the insert is dispatched, so the insert queues
    behind it and the row's first token is step k+2's: a freed slot is
    refilled one step later, the price of a device that never waits."""
    engine = make_engine(trainer, num_slots=2, max_new=6)
    p, q = prompt(2, 7), prompt(3, 12)
    engine.insert_requests([(p, 6)], [0])
    assert engine.step()[2].tolist() == [True, False]  # call k
    engine.insert_requests([(q, 6)], [1])
    assert engine.step()[2].tolist() == [True, False]  # call k+1: dispatched before the insert
    got = []
    for _ in range(6):
        tok, _, emitted, _ = engine.step()
        assert emitted[1]
        got.append(int(tok[1]))
    assert got == direct_generate(trainer, q.tolist(), 6)[0]
    assert engine.kv_stats() == {}  # the dense pool: no paged counters
    assert engine._outputs_masked == 0  # the slot had stood empty: nothing was dropped


# ----------------------------------------------------------------------
# (b) greedy outputs through the Scheduler, in every mode
# ----------------------------------------------------------------------

MODES = {
    "dense": {},
    "paged": dict(kv_paging=True, kv_block_size=8),
    "paged-kernel": dict(kv_paging=True, kv_block_size=8, decode_kernel="interpret"),
    "paged-prefix": dict(kv_paging=True, kv_block_size=8, prefix_cache=True),
}


@pytest.mark.parametrize("mode", list(MODES))
def test_scheduler_outputs_are_fresh_batch_generate(trainer, mode):
    """Five requests over two slots and two prompt buckets: three of them go
    into a slot another freed, one step after it fell free."""
    engine = make_engine(trainer, num_slots=2, max_new=8, **MODES[mode])
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        reqs = [sched.submit(prompt(10 + i, n), m)
                for i, (n, m) in enumerate(((5, 8), (37, 5), (12, 7), (50, 8), (29, 3)))]
        assert all(r.wait(300) for r in reqs)
    finally:
        sched.stop()
    for r in reqs:
        assert r.finish_reason == "length"
        assert_fresh_batch(trainer, r)
    assert engine._steps_ahead >= engine._step_n > 0  # every call dispatched a step ahead
    assert engine._outputs_masked == 0  # natural finishes: the device drops the row itself
    if engine.kv_paging:
        assert sched.metrics.get("decode_steps_ahead_total") == engine._steps_ahead
        assert engine.kv_stats()["kv_blocks_used"] == 0


@pytest.mark.parametrize("mode", list(MODES))
def test_scheduler_hands_a_request_exactly_the_engines_step(trainer, mode):
    """What a request is handed is the step's `[P]` output at its slot: one
    token and its log-probability a step whose `valid` bit is set there, none
    a step whose bit is 0 (a row admitted behind the step in flight)."""
    engine = make_engine(trainer, num_slots=2, max_new=6, **MODES[mode])
    sched = Scheduler(engine, max_wait_s=0.0)
    sched._running = True  # the loop's turns are taken by hand below
    step, steps = engine.step, []

    def logged_step():
        steps.append((step(), dict(sched._slot_req)))
        return steps[-1][0]

    engine.step = logged_step
    reqs = [sched.submit(prompt(60 + i, n), m) for i, (n, m) in enumerate(((5, 6), (37, 3), (12, 5), (29, 2)))]
    handed = {id(r): ([], []) for r in reqs}
    silent = 0
    while any(r.finish_reason is None for r in reqs):
        before = {id(r): len(r.token_ids) for r in reqs}
        turn(sched)
        (tokens, logprobs, valid, _), held = steps.pop()
        assert not steps and tokens.shape == logprobs.shape == valid.shape == (2,)
        for slot, req in held.items():
            assert len(req.token_ids) - before[id(req)] == int(valid[slot])
            if valid[slot]:
                handed[id(req)][0].append(int(tokens[slot]))
                handed[id(req)][1].append(float(logprobs[slot]))
            silent += not valid[slot]
    sched.stop()
    assert silent > 0  # two of the four went into a slot another freed
    for r in reqs:
        assert (r.token_ids, r.token_logprobs) == handed[id(r)] and len(r.token_ids) == r.max_new_tokens


def test_multi_tenant_outputs_are_fresh_batch_generate(lora_trainer, tmp_path, monkeypatch):
    """Base and adapter rows in one pool, three requests over two slots:
    each is what `trainer.generate` gives under its own weights."""
    import orbax.checkpoint as ocp

    from trlx_tpu import resilience
    from trlx_tpu.models.lora import split_lora

    def bump(path, x):
        if "_lora_" not in jax.tree_util.keystr(path):
            return x
        return x + 0.3 * jax.random.normal(jax.random.PRNGKey(7), x.shape, x.dtype)

    tuned = jax.tree_util.tree_map_with_path(bump, lora_trainer.params)
    lora_flat, _ = split_lora(tuned)
    ocp.PyTreeCheckpointer().save(
        os.path.join(tmp_path, "a1", "state"),
        {"train_params": {str(k): np.asarray(v) for k, v in lora_flat.items()}}, force=True)
    resilience.write_manifest(os.path.join(tmp_path, "a1"), step=1)

    store = AdapterStore(lora_trainer.params, adapter_dir=str(tmp_path), max_resident=2)
    engine = make_engine(lora_trainer, num_slots=2, max_new=6, multi_tenant=True, adapter_store=store)
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        reqs = [sched.submit(prompt(20 + i, n), 6, adapter_id=a)
                for i, (n, a) in enumerate(((7, None), (13, "a1"), (21, "a1")))]
        assert all(r.wait(300) for r in reqs)
    finally:
        sched.stop()
    assert_fresh_batch(lora_trainer, reqs[0])
    monkeypatch.setattr(lora_trainer, "_decode_params", lambda: tuned)  # what `generate` samples under
    for r in reqs[1:]:
        assert_fresh_batch(lora_trainer, r)
    assert store.refcount("a1") == 0


def test_session_turn_outputs_are_fresh_batch_generate(trainer):
    """A second turn prefills its delta behind the blocks the first turn
    retained while a step was in flight: bitwise the whole conversation
    prefilled fresh."""
    engine = make_engine(trainer, num_slots=2, max_new=4, kv_paging=True, kv_block_size=8)
    store = engine.enable_sessions()
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        sess = store.create()
        r1 = sched.submit(prompt(30, 19), 4, session=sess)
        assert r1.wait(300)
        sess = store.begin_turn(sess.id)
        full = np.concatenate([sess.tokens, prompt(31, 6)])
        r2 = sched.submit(full, 4, session=sess)
        assert r2.wait(300)
    finally:
        sched.stop()
    assert sess.last_reused_blocks >= 1 and sess.last_prefill_tokens < len(full)
    assert_fresh_batch(trainer, r1)
    assert_fresh_batch(trainer, r2)


# ----------------------------------------------------------------------
# (c) a slot's output belongs to the request that held it at dispatch
# ----------------------------------------------------------------------

def turn(sched):
    """One turn of `Scheduler._loop`, on this thread."""
    sched._expire_queued()
    sched._admit()
    if sched._slot_req:
        sched._decode_once()


@pytest.mark.parametrize("cancel", ["stop", "deadline"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_cancelled_requests_token_in_flight_reaches_nobody(trainer, cancel, paged, monkeypatch):
    """Two slots: C decodes throughout, A is cancelled on the host after its
    second token (a stop sequence, or its deadline) and B is admitted into
    A's slot in the same loop turn. The step then in flight decoded A's
    third token for that slot: B's first token has to be its own. Without
    the rule the engine keeps (`_disown`), B is handed A's token: shown on a
    second engine."""
    kw = dict(kv_paging=True, kv_block_size=8) if paged else {}
    pa, pb, pc = prompt(40, 11), prompt(41, 23), prompt(42, 17)
    a_tokens = direct_generate(trainer, pa.tolist(), 8)[0]
    b_tokens = direct_generate(trainer, pb.tolist(), 8)[0]
    detok = lambda ids: "".join(f"<{i}:{t}>" for i, t in enumerate(ids))  # noqa: E731

    def serve(engine):
        sched = Scheduler(engine, max_wait_s=0.0, detokenize=detok)
        sched._running = True  # the loop's turns are taken by hand below
        c = sched.submit(pc, 8)
        a = sched.submit(pa, 8, stop_sequences=[f"<1:{a_tokens[1]}>"] if cancel == "stop" else None,
                         deadline_s=3600.0)
        b = sched.submit(pb, 8)
        turn(sched)  # C and A admitted; their first tokens, their second in flight
        assert len(sched._slot_req) == 2 and b.stage == "queued"
        if cancel == "deadline":
            a.deadline = time.monotonic() - 1.0
        turn(sched)  # A's second token cancels it, and its third is in flight
        assert a.finish_reason == cancel and len(sched._slot_req) == 1
        while b.finish_reason is None:
            turn(sched)  # the first of these admits B behind the step in flight
        sched.stop()
        return a, b, c

    engine = make_engine(trainer, num_slots=2, max_new=8, **kw)
    a, b, c = serve(engine)
    assert a.token_ids == a_tokens[:1 if cancel == "stop" else 2]  # the stop's own token is cut
    assert b.token_ids == b_tokens
    assert_fresh_batch(trainer, b)
    assert_fresh_batch(trainer, c)
    assert engine._outputs_masked == 1
    if paged:
        assert engine.kv_stats()["decode_outputs_masked_total"] == 1
        assert engine.kv_stats()["kv_blocks_used"] == 0

    unruly = make_engine(trainer, num_slots=2, max_new=8, **kw)
    monkeypatch.setattr(unruly, "_disown", lambda slots: None)
    _, b, _ = serve(unruly)
    assert b.token_ids == [a_tokens[2]] + b_tokens


# ----------------------------------------------------------------------
# (d) insert -> step -> release on the same slots, as the serve job warms up
# ----------------------------------------------------------------------

def test_insert_step_release_on_the_same_slots(trainer):
    """`bench/jobs/serve.py:warm_up`: rows that would run two tokens are
    released after one, and the next rows go into the same slots. Every step
    returns the first token of the rows just inserted and nothing of the rows
    before them, whose second token was in flight: that step speaks for
    nobody any more, so the next call leaves it unfetched and dispatches its
    own."""
    engine = make_engine(trainer, num_slots=4, max_new=2, kv_paging=True, kv_block_size=8,
                         max_prefill_batch=4)
    for i, (plen, pb) in enumerate((n, b) for n in (32, 64) for b in (1, 2, 4)):
        ids = prompt(50 + i, plen)
        slots = list(range(pb))
        engine.insert_requests([(ids, 2)] * pb, slots)
        tok, _, emitted, finished = engine.step()
        assert emitted.tolist() == [s < pb for s in range(4)] and not finished.any()
        assert tok[:pb].tolist() == [direct_generate(trainer, ids.tolist(), 1)[0][0]] * pb
        engine.release_slots(slots)
        assert engine.active_slots == 0 and not engine._ahead.rows.any()
        assert engine.kv_stats()["kv_blocks_used"] == 0
    stats = engine.kv_stats()
    assert (stats["decode_steps_total"], stats["decode_steps_ahead_total"],
            stats["decode_outputs_masked_total"]) == (6, 6, 0)


# ----------------------------------------------------------------------
# what the scheduler calls a step's time
# ----------------------------------------------------------------------

def test_scheduler_times_a_step_by_the_spacing_of_its_returns(trainer):
    """`engine.step()` alone is the wait for a step dispatched a call
    earlier, so the histogram, the Retry-After estimate and the tokens/s
    gauge take the spacing of successive returns, which holds the host's
    work between two calls too; the first step after a turn without one has
    only its own call."""
    engine = make_engine(trainer, num_slots=1, max_new=8)
    sched = Scheduler(engine, max_wait_s=0.0)
    sched._running = True  # no loop thread: its turns are made by hand
    sched.submit(prompt(90, 9), 8)
    sched._admit()
    sched._decode_once()  # compiles
    sched._step_returned = None  # what the loop does in a turn that does not decode

    def step_time(host_work):
        before = sched.metrics.histograms_snapshot().get("decode_step_latency_seconds", (0, 0, 0.0, 0))[2]
        time.sleep(host_work)  # stands for emit and admit, while the device runs the step in flight
        sched._decode_once()
        return sched.metrics.histograms_snapshot()["decode_step_latency_seconds"][2] - before

    assert step_time(0.2) < 0.2  # nothing returned in the turn before: the call alone
    assert step_time(0.2) >= 0.2
    assert sched._decode_ewma > 0.04  # a fifth of the 0.2 s, beside steps of a millisecond
    sched._step_returned = None
    assert step_time(0.2) < 0.2


# ----------------------------------------------------------------------
# an error of a step
# ----------------------------------------------------------------------

@pytest.mark.parametrize("at", ["fetch", "dispatch"])
def test_step_error_costs_that_steps_token_and_no_other(trainer, monkeypatch, at):
    """A fetch that raises loses its own step's token, as a failed step
    always did, and not the one dispatched behind it: that step stays in
    flight and the next call returns it. A dispatch that raises with a step
    in flight loses nothing: the next call dispatches again and fetches the
    step that was waiting."""
    engine = make_engine(trainer, num_slots=1, max_new=6)
    p = prompt(80, 9)
    want = direct_generate(trainer, p.tolist(), 6)[0]
    decode, get, n = engine._decode_fn, jax.device_get, {"fetch": 0, "dispatch": 0}

    def failing(kind, fn, nth):
        def call(*args):
            n[kind] += 1
            if kind == at and n[kind] == nth:
                raise RuntimeError("injected")
            return fn(*args)
        return call

    engine._decode_fn = failing("dispatch", decode, 3)  # step 3's, in call 2
    monkeypatch.setattr(jax, "device_get", failing("fetch", get, 2))  # step 2's, in call 2
    engine.insert_requests([(p, 6)], [0])
    got, errors = [], 0
    for _ in range(7):
        try:
            tok, _, emitted, finished = engine.step()
        except RuntimeError:
            errors += 1
            continue
        assert emitted[0]
        got.append(int(tok[0]))
        if finished[0]:
            break
    assert errors == 1
    assert got == (want[:1] + want[2:] if at == "fetch" else want)


# ----------------------------------------------------------------------
# (e) new weights
# ----------------------------------------------------------------------

def test_set_params_takes_effect_behind_the_step_in_flight(trainer):
    """`set_params` between two calls: the step in flight ends on the old
    weights and the next to be dispatched runs the new. Token j+1 is drawn
    by the step that emits token j, so with the swap after call 1 (step 2 in
    flight) tokens 1-3 and their logprobs are the old weights' to the bit,
    and the fourth is the first the new weights drew."""
    p = prompt(60, 9)
    new = jax.tree_util.tree_map(lambda x: x * 1.5, trainer.params)

    def run(swap_after):
        engine = make_engine(trainer, num_slots=1, max_new=6)
        engine.insert_requests([(p, 6)], [0])
        lps = []
        for call in range(1, 6):
            lps.append(float(engine.step()[1][0]))
            if call == swap_after:
                assert engine.set_params(new) == 1
        return lps

    old, swapped = run(swap_after=None), run(swap_after=1)
    assert swapped[:3] == old[:3]
    assert swapped[3] != old[3]


def test_drain_leaves_no_active_row_in_flight(trainer):
    """What the checkpoint watcher does before a swap: after
    `Scheduler.drain()` every row of the step in flight has finished or been
    reclaimed, so no request decodes across two checkpoints."""
    engine = make_engine(trainer, num_slots=2, max_new=6, kv_paging=True, kv_block_size=8)
    sched = Scheduler(engine, max_wait_s=0.0).start()
    try:
        reqs = [sched.submit(prompt(70 + i, 9 + i), 6) for i in range(3)]
        deadline = time.monotonic() + 300
        while reqs[0].first_token_time is None and time.monotonic() < deadline:
            time.sleep(0.001)  # drain with rows decoding, not before the first admission
        assert sched.drain(timeout_s=300)
        assert engine.active_slots == 0
        assert engine._ahead is not None and not engine._ahead.rows.any()
        assert np.asarray(engine._pool["active"]).sum() == 0  # and the device agrees
        version = engine.set_params(trainer.params)
        sched.resume_admission()
        assert all(r.wait(300) for r in reqs)
    finally:
        sched.stop()
    assert version == 1
    for r in reqs:
        assert_fresh_batch(trainer, r)
