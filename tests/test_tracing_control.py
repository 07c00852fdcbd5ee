"""The one tracing control (trlx_tpu/observability/tracing.py `start` / `stop`
/ `active` / `span`) and the span sites of the trainer, the loader, the
scheduler and the engine.

A profiler session started and stopped in the running process writes an
xplane file whose host plane holds the program's `trlx:` spans; on the CPU
there is no device plane, the host plane is the same. Checked here: the
names, that children lie inside their parents on their thread, the
attributes that join spans, that a site that raises still closes, that a
session changes no output, and that every timed site feeds each of its
sinks once.
"""

import glob
import json
import os
import sys
import threading
import types

import jax
import numpy as np
import pytest

from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.inference import InferenceEngine, Scheduler
from trlx_tpu.observability import tracing
from trlx_tpu.ops.sampling import GenerationConfig
from trlx_tpu.pipeline import MiniBatchIterator
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.trainer.ppo_trainer import PPOTrainer

MAX_NEW = 4
PROMPTS = ["hello world", "jax tpu", "ppo", "trace"] * 2
N_ROLLOUTS, CHUNK = 8, 4


def _config(tmp_path, **train):
    return default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=4, total_steps=4, tracker=None,
                   checkpoint_dir=str(tmp_path), seed=11, **train),
        method=dict(num_rollouts=N_ROLLOUTS, chunk_size=CHUNK, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=True)),
    )


def _trainer(tmp_path, reward_fn=None, **train):
    reward_fn = reward_fn or (lambda samples, **kw: [float(len(s)) for s in samples])
    trainer = PPOTrainer(_config(tmp_path, **train), reward_fn=reward_fn)
    trainer.add_prompt_pipeline(
        PromptPipeline(PROMPTS, max_prompt_length=8, tokenizer=trainer.tokenizer))
    return trainer


def run_cycle(trainer):
    """make_experience, then one pass of train_minibatch over the store;
    returns every optimizer step's loss."""
    trainer.store.clear_history()
    trainer.make_experience(N_ROLLOUTS, trainer.iter_count)
    losses = []
    loader = trainer.create_train_dataloader()
    for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
        losses.append(float(trainer.train_minibatch(minibatch)["losses"]["total_loss"]))
        trainer.iter_count += 1
    return losses


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    trainer = _trainer(tmp_path_factory.mktemp("ctl"))
    run_cycle(trainer)  # compile outside every session
    return trainer


def read_spans(log_dir):
    """{thread line: [(name, start_ns, end_ns, attrs)]} of the `trlx:` spans
    on the host plane of the newest xplane file under `log_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, f"no .xplane.pb under {log_dir}"
    by_line = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                     for ev in line.events if ev.name.startswith(tracing.SPAN_PREFIX)]
            if spans:
                by_line[(line.name, i)] = spans
    return by_line


def named(spans, name):
    return [s for s in spans if s[0] == tracing.SPAN_PREFIX + name]


def assert_inside(spans, child, parent):
    """Every `child` span lies inside some `parent` span of the same thread."""
    kids, parents = named(spans, child), named(spans, parent)
    assert kids and parents, (child, parent, sorted({s[0] for s in spans}))
    for _, s, e, _ in kids:
        assert any(ps <= s and e <= pe for _, ps, pe, _ in parents), (child, parent)


def test_ppo_cycle_spans_share_the_profilers_trace(trainer, tmp_path):
    assert not tracing.active()
    assert tracing.start(str(tmp_path)) == str(tmp_path)
    assert tracing.active()
    step = trainer.iter_count
    run_cycle(trainer)
    assert tracing.stop() == str(tmp_path)
    assert not tracing.active()

    lines = read_spans(str(tmp_path))
    spans = next(v for v in lines.values() if named(v, "ppo.make_experience"))
    n_chunks = N_ROLLOUTS // CHUNK
    for name, count in (("ppo.make_experience", 1), ("ppo.generate_dispatch", n_chunks),
                        ("ppo.rollout_fetch", n_chunks), ("ppo.rollout_process", n_chunks),
                        ("ppo.host_process", n_chunks), ("ppo.host_decode", n_chunks),
                        ("ppo.reward", n_chunks), ("ppo.score_dispatch", n_chunks),
                        ("ppo.train_minibatch", N_ROLLOUTS // 4)):
        got = named(spans, name)
        assert len(got) == count, (name, len(got))
        assert all(e >= s for _, s, e, _ in got)  # every span closed
    for child, parent in (("ppo.generate_dispatch", "ppo.make_experience"),
                          ("ppo.rollout_fetch", "ppo.make_experience"),
                          ("ppo.rollout_process", "ppo.make_experience"),
                          ("ppo.host_process", "ppo.rollout_process"),
                          ("ppo.host_decode", "ppo.host_process"),
                          ("ppo.reward", "ppo.host_process"),
                          ("ppo.score_dispatch", "ppo.rollout_process")):
        assert_inside(spans, child, parent)
    # the attributes that join spans
    assert named(spans, "ppo.make_experience")[0][3]["step"] == step
    assert [s[3]["chunk"] for s in named(spans, "ppo.generate_dispatch")] == list(range(n_chunks))
    assert [s[3]["chunk"] for s in named(spans, "ppo.rollout_fetch")] == list(range(n_chunks))
    assert all(s[3]["rows"] == CHUNK for s in named(spans, "ppo.generate_dispatch"))
    assert [s[3]["step"] for s in named(spans, "ppo.train_minibatch")] == [step, step + 1]
    # the loaders' collate: the prompt loader's inside make_experience, one
    # a chunk, the store's loader's after it, one a minibatch
    (_, lo, hi, _), = named(spans, "ppo.make_experience")
    collates = named(spans, "pipeline.collate")
    assert [s[3]["rows"] for s in collates if lo <= s[1] and s[2] <= hi] == [CHUNK] * n_chunks
    assert [s[3]["rows"] for s in collates if s[1] >= hi] == [4] * (N_ROLLOUTS // 4)
    # dispatch runs ahead: chunk 1 is dispatched before chunk 0 is fetched
    assert named(spans, "ppo.generate_dispatch")[1][1] < named(spans, "ppo.rollout_fetch")[0][1]


@pytest.fixture(scope="module")
def paged_engine(trainer):
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                               eos_token_id=10_000, pad_token_id=trainer.tokenizer.pad_token_id)
    policy = {"lm": trainer.params["lm"]}
    from trlx_tpu.models import CausalLMPolicy

    return InferenceEngine(
        CausalLMPolicy(trainer.model_cfg), trainer.model_cfg, policy, gen_cfg,
        num_slots=2, max_prompt_len=16, kv_paging=True, kv_block_size=8)


def test_scheduler_and_engine_spans(paged_engine, tmp_path):
    scheduler = Scheduler(paged_engine, max_wait_s=0.0)
    scheduler.start()
    try:
        # compile the prefill and decode programs outside the session
        assert scheduler.submit(np.arange(1, 6, dtype=np.int32)).wait(120)
        dispatch0 = paged_engine._dispatches
        tracing.start(str(tmp_path))
        reqs = [scheduler.submit(np.arange(1, 4 + i, dtype=np.int32)) for i in range(3)]
        assert all(r.wait(120) for r in reqs)
        tracing.stop()
    finally:
        scheduler.stop()
    assert all(r.ok and len(r.token_ids) == MAX_NEW for r in reqs)

    lines = read_spans(str(tmp_path))
    spans = next(v for v in lines.values() if named(v, "engine.step"))
    for child, parent in (("engine.dispatch", "engine.step"), ("engine.fetch", "engine.step"),
                          ("engine.step", "sched.decode_once"), ("sched.emit", "sched.decode_once"),
                          ("sched.insert_batch", "sched.admit"),
                          ("engine.insert", "sched.insert_batch")):
        assert_inside(spans, child, parent)
    steps = named(spans, "engine.step")
    # a call fetches one step and dispatches the next ahead of that fetch;
    # a call with nothing in flight dispatches its own step first
    dispatches = named(spans, "engine.dispatch")
    queued = counter_spans(spans, "engine.queued")
    assert len(steps) == len(named(spans, "engine.fetch")) == sum(q["ahead"] for q in queued)
    assert len(steps) < len(dispatches) <= 2 * len(steps)
    # a step says who it is in a counter span's name, not in attributes: the
    # engine's count of decode dispatches joins a dispatch to its fetch,
    # consecutive, from where the engine stood when the session began
    assert not any(s[3] for s in steps + dispatches + named(spans, "engine.fetch"))
    seqs = [q["seq"] for q in queued]
    assert len(seqs) == len(dispatches)
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs))) and seqs[0] > dispatch0
    assert seqs[-1] == paged_engine._dispatches
    fetched = [f["seq"] for f in counter_spans(spans, "engine.fetched")]
    assert len(fetched) == len(steps) and set(fetched) <= set(seqs)
    # an admission's numbers live once, in the counter span in front of its
    # first program's dispatch; the spans round it carry none
    inserts = named(spans, "sched.insert_batch")
    assert not any(s[3] for s in inserts + named(spans, "engine.insert"))
    counts = counter_spans(spans, "sched.insert")
    assert len(counts) == len(inserts) and sum(c["rows"] for c in counts) == len(reqs)
    assert all(c["padded_tokens"] % paged_engine.prompt_bucket == 0 for c in counts)
    for (_, start, _, _), (_, lo, hi, _) in zip(spans_named(spans, "sched.insert"), inserts):
        assert lo <= start <= hi
    assert all(1 <= s[3]["rows"] <= 2 for s in named(spans, "sched.decode_once"))
    assert scheduler.metrics.get("prefill_batches_total") >= len(inserts)


def spans_named(spans, name):
    """The counter spans `trlx:<name> k=v ..`, whole, in start order."""
    return sorted((s for s in spans if s[0].startswith(tracing.SPAN_PREFIX + name + " ")),
                  key=lambda s: s[1])


def counter_spans(spans, name):
    """[{key: number}] of the counter spans `trlx:<name> k=v ..`, in start order."""
    return [{k: float(v) if "." in v else int(v)
             for k, v in (kv.split("=", 1) for kv in s[0].split()[1:])}
            for s in spans_named(spans, name)]


def engine_spans(log_dir):
    """The thread's spans that hold the engine's, in start order (a span
    that contains another starts first or, on a tie, ends last)."""
    spans = next(v for v in read_spans(log_dir).values() if named(v, "engine.dispatch"))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def traced(tmp_path, fn):
    tracing.start(str(tmp_path))
    try:
        fn()
    finally:
        tracing.stop()
    return engine_spans(str(tmp_path))


def idle(engine):
    """Every slot freed: whatever is in flight speaks for nobody."""
    engine.release_slots(list(range(engine.num_slots)))


def prompt_of(n, seed=0):
    return np.random.RandomState(seed).randint(1, 200, size=n).astype(np.int32)


def first_call(engine):
    engine.insert_requests([(prompt_of(5), MAX_NEW)], [0])
    engine.step()


def steady_run(engine):
    engine.insert_requests([(prompt_of(5), MAX_NEW), (prompt_of(9, 1), MAX_NEW)], [0, 1])
    for _ in range(MAX_NEW):
        engine.step()


def release_reclaim_refill(engine):
    """Each of the three between a step's dispatch and the fetch of it."""
    engine.insert_requests([(prompt_of(5), MAX_NEW), (prompt_of(9, 1), MAX_NEW)], [0, 1])
    engine.step()
    engine.release_slots([1])
    engine.step()
    engine.insert_requests([(prompt_of(7, 2), MAX_NEW)], [1])
    engine.step()
    engine.reclaim_slots([0])
    engine.step()


def all_rows_disowned(engine):
    """The step in flight loses its last row: the next call drops it and
    dispatches its own step first, so one `seq` is queued and never fetched."""
    engine.insert_requests([(prompt_of(5), MAX_NEW)], [0])
    engine.step()
    engine.release_slots([0])
    engine.insert_requests([(prompt_of(9, 1), MAX_NEW)], [1])
    engine.step()
    engine.step()


# scenario -> (calls of `step`, seqs queued and never fetched before the last call's own)
PIPELINES = {"first_call": (first_call, 1, 0), "steady_run": (steady_run, MAX_NEW, 0),
             "release_reclaim_refill": (release_reclaim_refill, 4, 0),
             "all_rows_disowned": (all_rows_disowned, 3, 1)}


@pytest.mark.parametrize("scenario", list(PIPELINES))
def test_every_fetched_seq_was_queued_once_and_earlier(paged_engine, tmp_path, scenario):
    run, calls, dropped = PIPELINES[scenario]
    idle(paged_engine)
    spans = traced(tmp_path, lambda: run(paged_engine))
    idle(paged_engine)
    mine = [s for s in spans if s[0].split()[0] in (
        "trlx:engine.queued", "trlx:engine.dispatch", "trlx:engine.fetch", "trlx:engine.fetched")]
    kinds = [s[0].split()[0].rpartition(".")[2] for s in mine]
    # the counter span stands directly in front of its dispatch, and
    # directly behind the fetch that waited: nothing of the four between
    for i, kind in enumerate(kinds):
        if kind == "queued":
            assert kinds[i + 1] == "dispatch" and mine[i][2] <= mine[i + 1][1]
        if kind == "fetched":
            assert kinds[i - 1] == "fetch" and mine[i - 1][2] <= mine[i][1]
    assert kinds.count("queued") == kinds.count("dispatch")
    assert kinds.count("fetched") == kinds.count("fetch") == calls
    queued = {q["seq"]: s for q, s in zip(counter_spans(spans, "engine.queued"),
                                          spans_named(spans, "engine.queued"))}
    seqs = sorted(queued)
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))  # each dispatch once, in order
    assert seqs[-1] == paged_engine._dispatches
    fetched = [f["seq"] for f in counter_spans(spans, "engine.fetched")]
    assert fetched == sorted(set(fetched)) and set(fetched) <= set(seqs)  # once each, in order
    for seq, span in zip(fetched, spans_named(spans, "engine.fetched")):
        assert queued[seq][2] <= span[1]  # queued before it was fetched
    # what was queued and not fetched: the step still in flight at the end,
    # and a step whose every row was disowned
    assert len(seqs) - len(fetched) == 1 + dropped
    aheads = [q["ahead"] for q in counter_spans(spans, "engine.queued")]
    if scenario == "first_call":
        assert kinds == ["queued", "dispatch", "queued", "dispatch", "fetch", "fetched"]
        assert aheads == [0, 1] and fetched == [seqs[0]]
    elif scenario == "steady_run":
        assert kinds[6:] == ["queued", "dispatch", "fetch", "fetched"] * (MAX_NEW - 1)
        assert fetched == seqs[:-1] and aheads == [0] + [1] * MAX_NEW
        assert [q["rows"] for q in counter_spans(spans, "engine.queued")][:2] == [2, 2]
    elif scenario == "release_reclaim_refill":
        assert fetched == seqs[:-1]
        # rows with a request when each step was queued: the release, the
        # refill and the reclaim show one dispatch later
        assert [q["rows"] for q in counter_spans(spans, "engine.queued")] == [2, 2, 1, 2, 1]
    else:
        assert fetched == [seqs[0], seqs[2], seqs[3]]
        assert aheads == [0, 1, 0, 1, 1]


def test_insert_counter_spans_add_up_to_the_counters(paged_engine, tmp_path):
    idle(paged_engine)
    scheduler = Scheduler(paged_engine, max_wait_s=0.0)
    names = ("prefill_batches_total", "prefill_rows_total", "prefill_tokens_total",
             "prefill_padded_tokens_total", "prefill_head_positions_total")
    scheduler.start()
    try:
        assert scheduler.submit(prompt_of(5)).wait(120)
        before = [scheduler.metrics.get(n) for n in names]
        assert before[:3] == [1, 1, 5] and before[3:] == [paged_engine.prompt_bucket, 1]
        tracing.start(str(tmp_path))
        reqs = [scheduler.submit(prompt_of(3 + 2 * i, i)) for i in range(5)]
        assert all(r.wait(120) for r in reqs)
        tracing.stop()
    finally:
        scheduler.stop()
    delta = [scheduler.metrics.get(n) - b for n, b in zip(names, before)]
    counts = counter_spans(engine_spans(str(tmp_path)), "sched.insert")
    assert [sum(c[k] for c in counts)
            for k in ("calls", "rows", "prompt_tokens", "padded_tokens", "head_positions")] == delta
    # a padded row's one position goes through the head, whatever the width it is dispatched at
    assert len(reqs) <= delta[4] < delta[3]
    assert delta[1] == len(reqs) and delta[2] == sum(len(r.prompt_ids) for r in reqs)
    assert all(c["calls"] == 1 and c["pad_tokens"] == c["padded_tokens"] - c["prompt_tokens"] for c in counts)


@pytest.mark.parametrize("kv_paging", [True, False], ids=["paged", "dense"])
def test_padding_of_a_batch_over_two_buckets(trainer, tmp_path, kv_paging):
    """Lengths 3, 5, 7 share the bucket of 8 (three rows are dispatched as
    four) and 12 takes the bucket of 16 alone: 4 x 8 + 1 x 16 positions for
    27 prompt tokens, and the head over the 4 + 1 rows' last positions."""
    from trlx_tpu.models import CausalLMPolicy

    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False, eos_token_id=10_000,
                               pad_token_id=trainer.tokenizer.pad_token_id)
    engine = InferenceEngine(
        CausalLMPolicy(trainer.model_cfg), trainer.model_cfg, {"lm": trainer.params["lm"]}, gen_cfg,
        num_slots=4, max_prompt_len=16, prompt_bucket=8, kv_paging=kv_paging, kv_block_size=8)
    rows = [(prompt_of(n, n), MAX_NEW) for n in (3, 12, 5, 7)]
    tracing.start(str(tmp_path))
    try:
        counts = engine.insert_requests(rows, [0, 1, 2, 3])
    finally:
        tracing.stop()
    assert counts == (4, 27, 48, 5)
    spans = [s for v in read_spans(str(tmp_path)).values() for s in v]
    assert counter_spans(spans, "sched.insert") == [
        {"calls": 1, "rows": 4, "prompt_tokens": 27, "padded_tokens": 48, "pad_tokens": 21,
         "head_positions": 5}]
    # in front of the first of the admission's two programs
    programs = sorted(named(spans, "engine.insert"), key=lambda s: s[1])
    assert len(programs) == 2 and spans_named(spans, "sched.insert")[0][2] <= programs[0][1]


def test_a_shared_prefix_is_not_counted_as_prefilled(trainer):
    """`prompt_tokens` is what the prefill programs compute: a prompt that
    finds its first two blocks in the prefix store prefills the rest, and
    `pad_tokens` stays the padding of what was dispatched."""
    from trlx_tpu.models import CausalLMPolicy

    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False, eos_token_id=10_000,
                               pad_token_id=trainer.tokenizer.pad_token_id)
    engine = InferenceEngine(
        CausalLMPolicy(trainer.model_cfg), trainer.model_cfg, {"lm": trainer.params["lm"]}, gen_cfg,
        num_slots=2, max_prompt_len=24, prompt_bucket=8, kv_paging=True, kv_block_size=8,
        prefix_cache=True)
    prompt = prompt_of(20)
    assert engine.insert_requests([(prompt, MAX_NEW)], [0]) == (1, 20, 24, 1)
    # blocks [0, 16) are resident under their keys: 4 tokens left, one bucket of 8
    assert engine.insert_requests([(prompt, MAX_NEW)], [1]) == (1, 4, 8, 1)


def serve(engine, seeds):
    scheduler = Scheduler(engine, max_wait_s=0.0)
    scheduler.start()
    try:
        reqs = [scheduler.submit(prompt_of(4 + i, i)) for i in seeds]
        assert all(r.wait(120) for r in reqs)
    finally:
        scheduler.stop()
    return [(r.token_ids, np.asarray(r.token_logprobs)) for r in reqs]


def test_no_session_formats_no_counter_span(paged_engine, monkeypatch):
    """Off a session the engine and the scheduler never reach
    `tracing.counters`, which formats its name whoever listens."""
    def raises(name, **values):
        raise AssertionError(f"tracing.counters({name!r}) called with no session active")

    idle(paged_engine)
    monkeypatch.setattr(tracing, "counters", raises)
    assert not tracing.active()
    out = serve(paged_engine, range(4))
    assert all(len(tokens) == MAX_NEW for tokens, _ in out)


def test_a_session_changes_no_token_and_no_logprob(paged_engine, tmp_path):
    """A tracing session changes no token and no arithmetic. The tokens are
    compared exactly. The float32 logprobs are held to 4 units in the last
    place, not to the bit: the CPU backend splits a reduction over its thread
    pool by what is free at the time, so two `serve` calls of ONE program
    beside five busy test workers can differ by one such unit (PR 42 saw it
    with the long files collected first; alone they are bit-equal). A session
    that changed the arithmetic would move a token or many units."""
    idle(paged_engine)
    plain = serve(paged_engine, range(4))
    idle(paged_engine)
    tracing.start(str(tmp_path))
    try:
        under_session = serve(paged_engine, range(4))
    finally:
        tracing.stop()
    for (tokens, logprobs), (tokens_s, logprobs_s) in zip(plain, under_session):
        assert tokens == tokens_s
        # (the scheduler hands the engine's float32 values out as Python floats)
        np.testing.assert_array_max_ulp(np.float32(logprobs), np.float32(logprobs_s), maxulp=4)
    assert counter_spans(engine_spans(str(tmp_path)), "engine.queued")  # and the session listened


def test_step_pipeline_reader_on_its_fixture():
    """The benchmark's reader of the `seq=` counter spans, loaded as
    `bench/run.py` loads it, on the trace built by hand beside it (the
    cases are bench/tests/test_step_pipeline.py's; this one keeps the reader
    under `pytest tests/`)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
    sys.path[:0] = [p for p in (bench,) if p not in sys.path]
    from benchlib.files import load_module

    reader = load_module("metrics/readers/step_pipeline.py")
    with open(os.path.join(bench, "trace", "fixture_step_pipeline.json")) as f:
        trace = json.load(f)
    lines = []
    ctx = types.SimpleNamespace(log=lines.append)
    got = {stat: reader.read({"trace": trace}, {"stat": stat}, ctx)
           for stat in ("slack_ms", "host_loop_ms", "fetch_late_ms")}
    assert got == pytest.approx({"slack_ms": 7.6, "host_loop_ms": 3.15, "fetch_late_ms": 0.35})
    assert any("identity over 3 steps" in ln and "= 12.6667 ms" in ln for ln in lines)
    # the names the engine writes are the names the reader looks for
    assert (reader.QUEUED, reader.FETCHED) == ("trlx:engine.queued ", "trlx:engine.fetched ")


@pytest.mark.parametrize("jax_as_installed", [True, False], ids=["own_stop", "another_jax"])
def test_stop_writes_the_xplane_and_nothing_else(tmp_path, monkeypatch, jax_as_installed):
    """`stop()` ends the session itself where JAX keeps it as the installed
    one does: the one file readers open, and not the gzipped JSON copy of the
    whole trace that `jax.profiler.stop_trace()` adds (most of a stop's time
    after seconds of a busy device). Under a JAX that keeps its session
    otherwise the stop is the public one, copy and all. Either way readers
    find the spans and the profiler is free for the next session."""
    if not jax_as_installed:
        monkeypatch.setattr(tracing, "_own_session", lambda: None)
    for name in ("a", "b"):
        tracing.start(str(tmp_path / name))
        with tracing.span("unit.work"):
            pass
        out = tracing.stop()
        files = [f for _, _, fs in os.walk(out) for f in fs]
        assert sum(f.endswith(".xplane.pb") for f in files) == 1
        assert (len(files) == 1) == jax_as_installed
        assert any(s[0] == "trlx:unit.work" for line in read_spans(out).values() for s in line)


@pytest.mark.parametrize("case", ["nothing_open", "another_shape", "no_state", "another_release"])
def test_own_session_is_taken_by_release_and_shape(tmp_path, monkeypatch, case):
    """The private place is used only under a JAX release the stop was run
    against on the chip and while it looks as it does there: with no session
    open, a state without its `reset`, no state at all, or a session open
    under another release, the control stops the public way."""
    from jax._src import profiler as jax_profiler

    if case == "nothing_open":
        assert tracing._own_session() is None
        return
    if case == "another_shape":
        monkeypatch.setattr(jax_profiler, "_profile_state", object())
    elif case == "no_state":
        monkeypatch.delattr(jax_profiler, "_profile_state")
    if case != "another_release":
        assert tracing._own_session() is None
        return
    tracing.start(str(tmp_path / "a"))
    try:
        assert tracing._own_session() is jax_profiler._profile_state
        monkeypatch.setattr(jax, "__version__", "0.10.0")
        assert tracing._own_session() is None
    finally:
        out = tracing.stop()  # the public stop, copy and all
    files = [f for _, _, fs in os.walk(out) for f in fs]
    assert sum(f.endswith(".xplane.pb") for f in files) == 1 and len(files) > 1


def test_second_start_raises_and_stop_needs_a_session(tmp_path):
    with pytest.raises(RuntimeError, match="no tracing session"):
        tracing.stop()
    tracing.start(str(tmp_path / "a"))
    try:
        with pytest.raises(RuntimeError, match="already active"):
            tracing.start(str(tmp_path / "b"))
        assert tracing.active()  # the refused start left the session as it was
    finally:
        assert tracing.stop() == str(tmp_path / "a")
    assert not os.path.exists(tmp_path / "b")


def test_control_is_safe_from_any_thread(tmp_path):
    """Started on one thread and stopped on another, with a span on a third;
    of two racing starts exactly one wins."""
    errors, won = [], []

    def start(i):
        try:
            won.append(tracing.start(str(tmp_path / f"s{i}")))
        except RuntimeError as e:
            errors.append(e)

    threads = [threading.Thread(target=start, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert len(won) == 1 and len(errors) == 3

    def work():
        with tracing.span("test.thread", step=7):
            pass

    worker = threading.Thread(target=work)
    worker.start()
    worker.join(30)
    stopped = []
    stopper = threading.Thread(target=lambda: stopped.append(tracing.stop()))
    stopper.start()
    stopper.join(30)
    assert stopped == won and not tracing.active()
    spans = [s for v in read_spans(won[0]).values() for s in v]
    assert named(spans, "test.thread")[0][3]["step"] == 7


def test_a_site_that_raises_still_closes(tmp_path):
    def bad_reward(samples, **kw):
        raise ValueError("reward model down")

    trainer = _trainer(tmp_path / "ckpt", reward_fn=bad_reward, tracing=True)
    tracing.start(str(tmp_path / "trace"))
    try:
        with pytest.raises(ValueError, match="reward model down"):
            trainer.make_experience(N_ROLLOUTS, 0)
    finally:
        tracing.stop()
    spans = [s for v in read_spans(str(tmp_path / "trace")).values() for s in v]
    for name in ("ppo.reward", "ppo.host_process", "ppo.rollout_process", "ppo.make_experience"):
        assert len(named(spans, name)) == 1, name
    assert_inside(spans, "ppo.reward", "ppo.make_experience")
    # and the timeline got the phases of the sites that were open
    phases = [s["name"] for s in trainer._timeline.spans]
    assert phases == ["rollout_generate", "host_reward", "rollout_score", "rollout_process",
                      "make_experience"]


def test_a_session_changes_no_output(tmp_path):
    """Two trainers from one seed, one cycle each, one under a session: the
    rollouts stored and the losses are bitwise the same."""
    out = {}
    for traced in (False, True):
        trainer = _trainer(tmp_path / f"ckpt{int(traced)}")
        if traced:
            tracing.start(str(tmp_path / "trace"))
        try:
            losses = run_cycle(trainer)
        finally:
            if traced:
                tracing.stop()
        out[traced] = (losses, [(e.query_tensor, e.response_tensor, e.logprobs, e.rewards)
                                for e in trainer.store.history])
    assert out[False][0] == out[True][0]
    assert len(out[False][1]) == N_ROLLOUTS
    for a, b in zip(out[False][1], out[True][1]):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_each_site_feeds_each_sink_once(tmp_path):
    """With `train.tracing` on, the phases of the timeline (the names the
    goodput ledger knows) come from the same sites as the spans, once each;
    the `time/*` stats come from the same clock reads."""
    trainer = _trainer(tmp_path, tracing=True)
    logged = {}
    trainer.tracker.log = lambda stats, step=None: logged.update(stats)
    run_cycle(trainer)
    n_chunks = N_ROLLOUTS // CHUNK
    by_name = {}
    for s in trainer._timeline.spans:
        by_name.setdefault(s["name"], []).append(s)
    assert {k: len(v) for k, v in by_name.items()} == {
        "rollout_generate": n_chunks, "host_reward": n_chunks, "rollout_score": n_chunks,
        "rollout_process": n_chunks, "make_experience": 1, "train_minibatch": N_ROLLOUTS // 4}
    assert by_name["make_experience"][0]["attrs"]["step"] == 0
    assert [s["attrs"]["rows"] for s in by_name["rollout_generate"]] == [CHUNK] * n_chunks
    assert all(s["attrs"]["degraded"] is False for s in by_name["rollout_generate"])
    # the trainer's stats are means over the chunks, in ms, of the same spans
    reward_ms = 1e3 * np.mean([s["dur"] for s in by_name["host_reward"]])
    process_ms = 1e3 * np.mean([s["dur"] for s in by_name["rollout_process"]])
    assert logged["time/rollout_score"] == pytest.approx(reward_ms)
    assert logged["time/rollout_time"] == pytest.approx(process_ms)
    # dispatch to samples on the host: never shorter than the fetch alone
    fetch_ms = 1e3 * np.mean([s["dur"] for s in by_name["rollout_generate"]])
    assert logged["time/rollout_generate"] >= fetch_ms
    assert logged["throughput/rollout_tokens_per_s"] > 0


def test_profile_dir_goes_through_the_control(tmp_path, monkeypatch):
    calls = []
    real_start, real_stop = tracing.start, tracing.stop
    monkeypatch.setattr(tracing, "start", lambda d=None: calls.append(("start", d)) or real_start(d))
    monkeypatch.setattr(tracing, "stop", lambda: calls.append(("stop",)) or real_stop())
    profile_dir = str(tmp_path / "profile")
    trainer = _trainer(tmp_path / "ckpt", profile_dir=profile_dir, profile_start=0,
                       profile_stop=1, eval_interval=100, checkpoint_interval=100)
    trainer.add_eval_pipeline(
        PromptPipeline(PROMPTS[:2], max_prompt_length=8, tokenizer=trainer.tokenizer))
    trainer.learn()
    assert calls == [("start", profile_dir), ("stop",)]
    assert not tracing.active()
    spans = [s for v in read_spans(profile_dir).values() for s in v]
    assert named(spans, "ppo.train_minibatch")
