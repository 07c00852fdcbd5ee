"""The build account (trlx_tpu/observability/compile_ledger.py): what the
process spends building programs, heard from `jax.monitoring`, always on.

Checked here: one row a program with one event of each kind, under the
name inside `jit(...)`; nested traces of `jnp` functions count no second
twice; a second call builds nothing; a mark keeps its first totals; a
tracing session's stop writes the account as counter spans whose names
parse by the benchmark reader's grammar, and off a session nothing is
formatted; a ledger's process totals are the account's; `ledgered_jit`
without a ledger is `jax.jit`.

The account is the process's, so every test names its own functions and
marks and reads differences.
"""

import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

import trlx_tpu  # noqa: F401  (installs the account)
from trlx_tpu.observability import compile_ledger, tracing
from trlx_tpu.observability.compile_ledger import CompileLedger, account, ledgered_jit

KINDS = ("trace", "lower", "backend")


def test_import_installs_one_account_for_the_process():
    assert compile_ledger.install_monitoring() is account() is compile_ledger.install_monitoring()


def test_a_fresh_jit_leaves_one_event_of_each_kind_under_one_name():
    def build_account_decode(x, w):
        return jnp.tanh(jnp.matmul(x, w))  # both jitted themselves: their traces nest

    x = jnp.ones((4, 4))  # its own helper programs are built before the reading
    before = account().totals()
    t0 = before["at_s"]
    jax.jit(build_account_decode)(x, x).block_until_ready()
    after = account().totals()

    rows = account().programs()
    assert "jit(build_account_decode)" not in rows
    row = rows["build_account_decode"]
    assert row["events"] == dict.fromkeys(KINDS, 1)
    assert all(row["seconds"][k] > 0 for k in KINDS)
    assert t0 <= row["first_at"] < row["last_at"] <= after["at_s"]
    # the outermost trace alone: `matmul` and `tanh` add no second to the process's
    assert after["programs"] - before["programs"] == 1
    assert after["builds"] - before["builds"] == 1
    for k in KINDS:
        assert after[f"{k}_s"] - before[f"{k}_s"] == pytest.approx(row["seconds"][k], abs=1e-9)


def test_a_second_call_builds_nothing():
    f = jax.jit(lambda x: x * 5 - 1)
    x = jnp.arange(3.0)
    f(x)
    before = account().totals()
    f(x).block_until_ready()
    after = account().totals()
    assert {k: after[k] for k in after if k != "at_s"} == {k: before[k] for k in before if k != "at_s"}


def test_program_names_are_one_key_and_hold_no_blank():
    name = compile_ledger._program_name
    assert name("decode") == name("jit(decode)") == name("pmap(decode)") == "decode"
    assert name("jit(<lambda>)") == "<lambda>"
    assert name("<unnamed wrapped function>") == "<unnamed_wrapped_function>"
    assert name("") == "?"


def test_traces_on_two_threads_nest_each_on_its_own():
    acct = compile_ledger.BuildAccount()
    span, start = compile_ledger._TRACE_SPAN, acct._made_at

    def other():
        acct._on_start(span, start + 1.0, fun_name="theirs")
        acct._on_span(span, start + 1.0, start + 3.0, fun_name="theirs")

    acct._on_start(span, start, fun_name="mine")
    thread = threading.Thread(target=other)
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive()
    acct._on_start(span, start + 4.0, fun_name="inner")
    acct._on_span(span, start + 4.0, start + 5.0, fun_name="inner")
    acct._on_span(span, start, start + 6.0, fun_name="mine")
    assert {n: r["seconds"]["trace"] for n, r in acct.programs().items()} == {
        "theirs": pytest.approx(2.0), "mine": pytest.approx(6.0)}
    # a release that sends no start: every trace is heard as outermost
    acct._on_span(span, start + 7.0, start + 7.5, fun_name="bare")
    assert acct.totals()["trace_s"] == pytest.approx(8.5)


def test_mark_keeps_the_first_time_only_and_excludes_what_is_built_after():
    x = jnp.arange(4.0)
    jax.jit(lambda x: x + 11)(x)
    account().mark("test.ready")
    at_mark = account().marks()["test.ready"]
    assert at_mark["builds"] == account().totals()["builds"]
    jax.jit(lambda x: x + 12)(x).block_until_ready()
    account().mark("test.ready")
    assert account().marks()["test.ready"] == at_mark
    assert account().totals()["builds"] == at_mark["builds"] + 1
    assert account().totals()["at_s"] > at_mark["at_s"]


def read_counter_spans(log_dir, span):
    """[{key: text}] of the `trlx:<span> k=v ...` spans of the newest xplane
    file under `log_dir`, in the trace's order, parsed as
    bench/metrics/readers/span_counters.py parses them."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    prefix = tracing.SPAN_PREFIX + span + " "
    events = [(ev.start_ns, ev.name) for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU" for line in plane.lines for ev in line.events
              if ev.name.startswith(prefix)]
    return [dict(kv.split("=", 1) for kv in name[len(prefix):].split()) for _, name in sorted(events)]


def test_stop_writes_the_account_into_the_session(tmp_path):
    x = jnp.arange(5.0)
    account().mark("test.session")
    tracing.start(str(tmp_path))
    try:
        jax.jit(lambda x: x * 13)(x).block_until_ready()  # built inside the session
    finally:
        tracing.stop()
    end = account().totals()

    totals = {t["mark"]: t for t in read_counter_spans(str(tmp_path), "build.total")}
    assert {"test.session", "end"} <= set(totals)
    keys = {"mark", "programs", "builds", "trace_s", "lower_s", "backend_s", "cache_hits",
            "cache_misses", "cache_read_s", "saved_s", "at_s"}
    assert all(set(t) == keys for t in totals.values())
    assert int(totals["end"]["builds"]) == end["builds"] == int(totals["test.session"]["builds"]) + 1
    assert float(totals["end"]["trace_s"]) == pytest.approx(end["trace_s"], abs=1e-4)
    assert float(totals["test.session"]["at_s"]) < float(totals["end"]["at_s"])

    rows = read_counter_spans(str(tmp_path), "build.program")
    assert 0 < len(rows) <= compile_ledger._PROGRAMS_WRITTEN + 1
    assert all(set(r) == {"name", "builds", "trace_s", "lower_s", "backend_s", "first_at_s", "last_at_s"}
               for r in rows)
    weight = [float(r["trace_s"]) + float(r["lower_s"]) + float(r["backend_s"])
              for r in rows if r["name"] != "(others)"]
    assert weight == sorted(weight, reverse=True)  # heaviest first
    assert sum(int(r["builds"]) for r in rows) == end["builds"]

    events = read_counter_spans(str(tmp_path), "build.event")
    assert [(e["kind"], e["name"]) for e in events] == [(k, "<lambda>") for k in KINDS]
    assert all(float(e["ms"]) > 0 for e in events)


def test_programs_past_the_written_count_are_summed_as_others(monkeypatch):
    acct = compile_ledger.BuildAccount()
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    for i in range(compile_ledger._PROGRAMS_WRITTEN + 3):
        acct._on_span(lower, acct._made_at + i, acct._made_at + i + 1 + i, fun_name=f"jit(f{i})")
    written = []
    monkeypatch.setattr(tracing, "counters", lambda name, /, **values: written.append((name, values)))
    acct.write()
    rows = [v for n, v in written if n == "build.program"]
    assert len(rows) == compile_ledger._PROGRAMS_WRITTEN + 1
    assert rows[0]["name"] == f"f{compile_ledger._PROGRAMS_WRITTEN + 2}"
    assert rows[-1] == {"name": "(others)", "builds": 0, "trace_s": 0.0, "lower_s": 6.0,
                        "backend_s": 0.0, "first_at_s": 0.0, "last_at_s": 5.0}
    assert [v["mark"] for n, v in written if n == "build.total"] == ["end"]


def test_off_a_session_nothing_is_formatted(monkeypatch):
    assert not tracing.active()

    def formatted(*args, **kwargs):
        raise AssertionError("a counter span was formatted off a session")

    monkeypatch.setattr(tracing, "counters", formatted)
    jax.jit(lambda x: x - 17)(jnp.arange(3.0)).block_until_ready()
    account().mark("test.off_session")


def test_a_ledgers_process_totals_are_the_accounts():
    ledger = CompileLedger()
    f = ledger.jit(lambda x: x * 19, "nineteen")
    f(jnp.arange(3.0)).block_until_ready()
    built, snap, stats = account().totals(), ledger.snapshot(), ledger.drain_stats()
    assert snap["functions"]["nineteen"]["compiles"] == 1
    assert snap["backend_compile_s"] == pytest.approx(built["backend_s"], abs=1e-6)
    assert snap["trace_s"] == pytest.approx(built["trace_s"], abs=1e-6) and snap["trace_s"] > 0
    assert snap["lower_s"] == pytest.approx(built["lower_s"], abs=1e-6)
    assert snap["persistent_cache"] == {"hits": built["cache_hits"], "misses": built["cache_misses"]}
    for key in ("trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses", "programs"):
        assert stats[f"compile/{key}"] == pytest.approx(built[key])
    # another ledger reads the same process: no copy each
    assert CompileLedger().snapshot()["backend_compile_s"] == snap["backend_compile_s"]
    # /metrics: the process's series are the account's, a ledger prints its own
    prom = account().render_prometheus()
    for series in ("build_trace_seconds_total", "build_lower_seconds_total",
                   "build_backend_seconds_total", "build_cache_misses_total", "build_programs_total"):
        assert f"\ntrlx_tpu_{series} " in prom
    assert "trlx_tpu_compiles_total" in ledger.render_prometheus()
    assert "build_" not in ledger.render_prometheus()


def test_ledgered_jit_without_a_ledger_is_exactly_jax_jit():
    fn = lambda x: x * 23 + 1  # noqa: E731
    off = ledgered_jit(fn, name="plain", ledger=None)
    assert type(off) is type(jax.jit(fn))
    assert not hasattr(off, "_ledgered") and off.__wrapped__ is fn


def test_the_persistent_cache_is_heard(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        def twin():  # a new function each call, so a new trace; the same program's text
            def build_account_cached(x):
                return x * 29 + 3
            return jax.jit(build_account_cached)

        x = jnp.arange(6.0)
        before = account().totals()
        twin()(x).block_until_ready()  # a miss, written
        missed = account().totals()
        twin()(x).block_until_ready()  # read back
        hit = account().totals()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert (missed["cache_misses"] - before["cache_misses"], missed["cache_hits"] - before["cache_hits"]) == (1, 0)
    assert (hit["cache_misses"] - missed["cache_misses"], hit["cache_hits"] - missed["cache_hits"]) == (0, 1)
    assert hit["cache_read_s"] > missed["cache_read_s"] == before["cache_read_s"]
    # the read is inside the backend's seconds
    row = account().programs()["build_account_cached"]
    assert row["events"]["backend"] == 2
    assert hit["cache_read_s"] - missed["cache_read_s"] <= hit["backend_s"] - missed["backend_s"]
