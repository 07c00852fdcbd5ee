"""Goodput ledger + SLO burn-rate engine + exposition-format tests.

Covers ISSUE 15's unit surface (the end-to-end acceptance run lives in
scripts/goodput_slo_smoke.py, gated in tier-1):

- GoodputLedger: exclusive (containment-aware) attribution sums to wall,
  compile split via first-call spans, rewind/degraded/quarantine waste
  causes, steady-window MFU plumbing against the shared FLOP model;
- SLOEngine: burn-rate math on synthetic streams with an injected
  clock, the fast/slow multi-window AND, min_events suppression,
  registry snapshot-diff ingestion, flight-recorder alert transitions,
  and budget exhaustion firing EXACTLY one postmortem bundle;
- metrics.py satellites: label-value escaping, +Inf/_sum/_count on
  labeled histograms, OpenMetrics exemplar rendering, and HELP/TYPE
  dedup at registry-concatenation points.

Everything here is host-side and jax-free.
"""

import json
import os
import time

import pytest

from trlx_tpu.inference.metrics import (
    NAMESPACE,
    InferenceMetrics,
    dedupe_metadata,
)
from trlx_tpu.observability import FlightRecorder, postmortem
from trlx_tpu.observability.flops import flops_per_sample
from trlx_tpu.observability.goodput import WASTE_CAUSES, GoodputLedger
from trlx_tpu.observability.slo import SLO, SLOEngine, default_slos


class _TinyCfg:
    d_model = 8
    n_layers = 2
    d_ff = 16
    vocab_size = 32


# ----------------------------------------------------------------------
# GoodputLedger attribution
# ----------------------------------------------------------------------


def _ledger(age_s=100.0):
    led = GoodputLedger(n_chips=1, peak_flops=1e12)
    led.t_start = time.monotonic() - age_s  # spans below sit inside the run
    return led


def test_ledger_exclusive_nesting_sums_to_wall():
    led = _ledger()
    t0 = time.monotonic() - 90.0
    # spans arrive at END time, children strictly before parents
    led.observe_phase("host_reward", t0 + 1.0, t0 + 2.0)
    led.observe_phase("rollout_score", t0 + 0.5, t0 + 2.5)
    led.observe_phase("rollout_generate", t0 + 3.0, t0 + 5.0)
    led.observe_phase("make_experience", t0, t0 + 6.0)
    led.observe_phase("train_minibatch", t0 + 6.0, t0 + 7.0, first=True)
    led.observe_phase("train_minibatch", t0 + 7.0, t0 + 8.0)
    snap = led.snapshot()
    sec = snap["seconds"]
    # nested spans charge only their exclusive part
    assert sec["reward_rtt"] == pytest.approx(1.0)
    assert sec["rollout_score"] == pytest.approx(1.0)  # 2.0 minus the RTT
    assert sec["rollout_generate"] == pytest.approx(2.0)
    assert sec["rollout_other"] == pytest.approx(2.0)  # make_experience rest
    assert sec["compile"] == pytest.approx(1.0)  # first-call split out
    assert sec["train"] == pytest.approx(1.0)
    # the invariant: per-cause seconds sum to wall exactly (other_host
    # absorbs the unattributed remainder)
    assert sum(sec.values()) == pytest.approx(snap["wall_s"], rel=1e-6)
    assert sec["other_host"] > 80.0
    assert snap["productive_s"] == pytest.approx(4.0)


def test_ledger_rewind_window_is_waste_until_next_train_step():
    led = _ledger()
    t0 = time.monotonic() - 50.0
    led.observe_phase("rollout_generate", t0, t0 + 1.0)
    led.note_rewind()
    led.observe_phase("sentinel_restore", t0 + 1.0, t0 + 1.5)
    # re-rollout while repaying the rewind: charged to waste
    led.observe_phase("rollout_generate", t0 + 2.0, t0 + 3.0)
    led.observe_phase("rollout_score", t0 + 3.0, t0 + 3.5)
    # first completed train step marks the debt repaid
    led.observe_phase("train_minibatch", t0 + 3.5, t0 + 4.0)
    led.observe_phase("rollout_generate", t0 + 4.0, t0 + 5.0)
    snap = led.snapshot()
    sec = snap["seconds"]
    assert snap["rewinds"] == 1
    assert sec["waste/rewind"] == pytest.approx(0.5 + 1.0 + 0.5)
    assert sec["rollout_generate"] == pytest.approx(2.0)  # before + after
    assert snap["wasted_s"] == pytest.approx(2.0)
    assert 0.0 < snap["goodput_fraction"] < 1.0


def test_ledger_degraded_chunks_and_quarantine_move_not_add():
    led = _ledger()
    t0 = time.monotonic() - 40.0
    led.observe_phase("rollout_generate", t0, t0 + 2.0,
                      attrs={"degraded": True})
    led.observe_phase("rollout_generate", t0 + 2.0, t0 + 6.0)
    before = led.snapshot()
    assert before["seconds"]["waste/fleet_degraded"] == pytest.approx(2.0)
    led.note_quarantine(rows=3, seconds=1.5)
    after = led.snapshot()
    sec = after["seconds"]
    assert sec["waste/quarantined"] == pytest.approx(1.5)
    assert sec["rollout_generate"] == pytest.approx(2.5)  # moved, not added
    assert after["quarantined_rows"] == 3
    # the move keeps the sum-to-wall invariant
    assert sum(sec.values()) == pytest.approx(after["wall_s"], rel=1e-6)
    assert set(WASTE_CAUSES) >= {"waste/fleet_degraded", "waste/quarantined"}


def test_ledger_work_accounting_prices_with_shared_flop_model():
    # peak_flops=1.0 keeps the toy model's MFU above the 6-decimal
    # rounding in snapshot()
    led = GoodputLedger(n_chips=1, peak_flops=1.0)
    led.t_start = time.monotonic() - 100.0
    # work noted before configure_unit_flops is silently dropped
    led.note_rollout_chunk(8)
    assert led.snapshot()["flops_total"] == 0.0
    unit = flops_per_sample(_TinyCfg, n_prompt=4, n_new=4, ppo_epochs=1,
                            unfrozen=1)
    led.configure_unit_flops(_TinyCfg, n_prompt=4, n_new=4, unfrozen=1)
    led.note_rollout_chunk(8)
    led.note_train_rows(4)
    led.note_train_rows(4)  # second epoch revisits the rows
    snap = led.snapshot()
    expect = 8 * (unit["generate"] + unit["score"]) + 8 * unit["train"]
    assert snap["flops_total"] == pytest.approx(expect)
    assert snap["tokens_total"] == pytest.approx(8 * 8)
    assert snap["samples_total"] == pytest.approx(8)
    # MFU plumbing: flops / steady wall / chips / peak, self-consistent
    assert snap["mfu"] == pytest.approx(
        snap["flops_total"] / snap["steady_window_s"], rel=1e-3)
    assert snap["tokens_per_sec_per_chip"] == pytest.approx(
        snap["tokens_total"] / snap["steady_window_s"], rel=1e-2)


def test_ledger_steady_window_excludes_warmup_work():
    led = GoodputLedger(n_chips=1, peak_flops=1.0)
    led.t_start = time.monotonic() - 100.0
    led.configure_unit_flops(_TinyCfg, n_prompt=4, n_new=4, unfrozen=1)
    led.note_rollout_chunk(4)
    # a compile that ends in the future: all work so far becomes warmup
    now = time.monotonic()
    led.observe_phase("train_minibatch", now, now + 5.0, first=True)
    snap = led.snapshot()
    assert snap["mfu"] == pytest.approx(0.0)  # nothing in the steady window
    assert snap["mfu_overall"] > 0.0  # lifetime view still counts it
    assert snap["flops_total"] > 0.0


def test_ledger_prometheus_and_json_artifact(tmp_path):
    led = _ledger()
    t0 = time.monotonic() - 10.0
    led.observe_phase("rollout_generate", t0, t0 + 1.0)
    text = led.render_prometheus(ns="g")
    assert 'g_seconds_total{cause="rollout_generate"} 1.0' in text
    assert 'g_seconds_total{cause="other_host"}' in text
    assert "g_mfu " in text and "g_fraction " in text
    # one TYPE per metric name even before any dedup pass
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE ")]
    assert len(types) == len({ln.split()[2] for ln in types})

    path = led.write(str(tmp_path / "nested" / "goodput.json"))
    with open(path) as f:
        snap = json.load(f)
    assert snap["seconds"]["rollout_generate"] == pytest.approx(1.0)
    assert not os.path.exists(path + ".tmp")


# ----------------------------------------------------------------------
# SLO burn-rate engine
# ----------------------------------------------------------------------


def _engine(clk, **slo_over):
    spec = dict(name="lat", kind="latency", target=0.9, threshold_s=1.0,
                fast_window_s=60.0, slow_window_s=600.0, burn_alert=2.0,
                min_events=5)
    spec.update(slo_over)
    return SLOEngine(slos=[SLO(**spec)], clock=lambda: clk[0])


def _window(report, name, wname):
    slo = next(s for s in report["slos"] if s["name"] == name)
    return slo, next(w for w in slo["windows"] if w["window"] == wname)


def test_burn_rate_math_and_multi_window_and():
    clk = [1000.0]
    eng = _engine(clk)
    for i in range(10):
        eng.record(latency_s=2.0 if i < 3 else 0.1)  # 3/10 bad, budget 0.1
    report = eng.evaluate()
    slo, fast = _window(report, "lat", "fast")
    _, slow = _window(report, "lat", "slow")
    assert fast["events"] == 10 and fast["bad"] == 3
    assert fast["burn_rate"] == pytest.approx(3.0)  # 0.3 / 0.1
    assert fast["alerting"] and slow["alerting"]
    assert slo["burning"] is True

    # 2 minutes of clean traffic: the fast window recovers (only fresh
    # events remain inside it), the slow window dilutes below the alert
    # threshold, and the multi-window AND clears the alert
    clk[0] += 120.0
    for _ in range(10):
        eng.record(latency_s=0.1)
    report = eng.evaluate()
    slo, fast = _window(report, "lat", "fast")
    _, slow = _window(report, "lat", "slow")
    assert fast["events"] == 10 and fast["bad"] == 0
    assert not fast["alerting"]
    assert slow["events"] == 20 and slow["bad"] == 3
    assert slow["burn_rate"] == pytest.approx(1.5)
    assert not slow["alerting"]
    assert slo["burning"] is False


def test_min_events_suppresses_cold_start_alerts():
    clk = [0.0]
    eng = _engine(clk, min_events=5)
    for _ in range(4):
        eng.record(latency_s=9.0)  # 100% bad but below min_events
    slo, fast = _window(eng.evaluate(), "lat", "fast")
    assert fast["burn_rate"] == pytest.approx(10.0)
    assert not fast["alerting"] and not slo["burning"]
    eng.record(latency_s=9.0)  # fifth event arms it
    slo, fast = _window(eng.evaluate(), "lat", "fast")
    assert fast["alerting"] and slo["burning"]


def test_latency_slo_ignores_inapplicable_events():
    clk = [0.0]
    eng = _engine(clk)
    eng.record(ok=False, rejected=True)  # no latency: not a latency event
    eng.record(ttft_s=0.2)
    _, fast = _window(eng.evaluate(), "lat", "fast")
    assert fast["events"] == 0


def test_alert_transitions_hit_flight_recorder():
    clk = [0.0]
    rec = FlightRecorder("test-slo", capacity=32)
    eng = SLOEngine(slos=[SLO("lat", "latency", target=0.9, threshold_s=1.0,
                              min_events=5, fast_window_s=60,
                              slow_window_s=600)],
                    recorder=rec, clock=lambda: clk[0])
    for _ in range(6):
        eng.record(latency_s=5.0)
    eng.evaluate()
    kinds = [e["kind"] for e in rec.snapshot()]
    assert kinds.count("slo_alert") == 2  # one per window
    clk[0] += 700.0  # both windows age out
    eng.evaluate()
    kinds = [e["kind"] for e in rec.snapshot()]
    assert kinds.count("slo_clear") == 2
    # the 100%-bad stream also exhausted the lifetime budget exactly once
    assert kinds.count("slo_budget_exhausted") == 1
    eng.evaluate()  # steady state: no repeated transition spam
    assert len(rec.snapshot()) == 5


def test_budget_exhaustion_fires_exactly_one_postmortem(tmp_path):
    postmortem.reset_triggers()
    try:
        clk = [0.0]
        pm_dir = str(tmp_path / "pm")
        eng = SLOEngine(
            slos=[SLO("avail", "availability", target=0.5, min_events=5)],
            postmortem_dir=pm_dir, clock=lambda: clk[0],
            metrics_config={"replicas": 2},
        )
        for _ in range(6):
            eng.record(ok=False)  # 100% bad, budget 0.5 -> spent 2.0
        report = eng.evaluate()
        budget = report["slos"][0]["budget"]
        assert budget["exhausted"] and budget["spent_fraction"] >= 1.0
        eng.evaluate()  # still exhausted: must not dump again
        eng.evaluate()
        bundles = sorted(os.listdir(pm_dir))
        assert len(bundles) == 1, bundles
        with open(os.path.join(pm_dir, bundles[0], "trigger.json")) as f:
            trig = json.load(f)
        assert trig["trigger"] == "slo-budget-exhausted"
        assert trig["detail"]["slo"] == "avail"
        with open(os.path.join(pm_dir, bundles[0], "config.json")) as f:
            assert json.load(f)["replicas"] == 2
    finally:
        postmortem.reset_triggers()


def test_ingest_registry_diffs_histograms_and_counters():
    clk = [0.0]
    slos = [
        SLO("lat", "latency", target=0.9, threshold_s=0.5, min_events=1),
        SLO("avail", "availability", target=0.9, min_events=1),
        SLO("rej", "rejection", target=0.9, min_events=1),
    ]
    eng = SLOEngine(slos=slos, clock=lambda: clk[0])
    m = InferenceMetrics(num_slots=4)
    # threshold 0.5 sits on a bucket edge: <=0.5 judged good, above bad
    m.observe("request_latency_seconds", 0.3)
    m.observe("request_latency_seconds", 0.4,
              labels={"replica": "r1"})  # label sets merge
    m.observe("request_latency_seconds", 2.0)
    m.inc('requests_total{outcome="eos"}', 2)
    m.inc('requests_total{outcome="deadline"}')
    m.inc("requests_rejected_total")
    n = eng.ingest_registry(m)
    assert n == 3 + 3 + 1
    report = eng.evaluate()
    _, lat = _window(report, "lat", "fast")
    assert (lat["events"], lat["bad"]) == (3, 1)
    _, avail = _window(report, "avail", "fast")
    # the 3 synthesized latency events count as successful completions
    # under availability, alongside the 3 outcome-counter events
    assert (avail["events"], avail["bad"]) == (6, 1)
    _, rej = _window(report, "rej", "fast")
    # rejection applies to every event incl. the rejected one
    assert rej["bad"] == 1
    # cursor advance: a second ingest with nothing new emits nothing
    assert eng.ingest_registry(m) == 0
    m.observe("request_latency_seconds", 9.0)
    assert eng.ingest_registry(m) == 1


def test_render_prometheus_series_shape():
    clk = [0.0]
    eng = _engine(clk)
    for _ in range(6):
        eng.record(latency_s=5.0)
    text = eng.render_prometheus(ns="x")
    assert '# TYPE x_slo_burn_rate gauge' in text
    assert 'x_slo_burn_rate{slo="lat",window="fast"} 10.0' in text
    assert 'x_slo_burn_rate{slo="lat",window="slow"} 10.0' in text
    assert 'x_slo_burning{slo="lat"} 1' in text
    assert 'x_slo_budget_spent_fraction{slo="lat"} 10.0' in text


def test_default_slos_cover_the_promised_kinds():
    kinds = {s.kind for s in default_slos()}
    assert kinds == {"latency", "ttft", "availability", "rejection"}
    names = [s.name for s in default_slos()]
    assert "latency_p99" in names and "availability" in names


# ----------------------------------------------------------------------
# metrics.py: escaping, labeled histograms, exemplars, dedup
# ----------------------------------------------------------------------


def test_label_values_escape_exposition_metacharacters():
    m = InferenceMetrics(num_slots=1)
    m.set_gauge("weird", 1.0, labels={"path": 'a"b\\c\nd'})
    line = next(ln for ln in m.render().splitlines()
                if ln.startswith(f"{NAMESPACE}_weird"))
    assert line == f'{NAMESPACE}_weird{{path="a\\"b\\\\c\\nd"}} 1.0'


def test_labeled_histogram_renders_inf_sum_count():
    m = InferenceMetrics(num_slots=1)
    m.observe("lat", 0.003, labels={"tenant": "a"})
    m.observe("lat", 99.0, labels={"tenant": "a"})  # lands in +Inf
    m.observe("lat", 0.003, labels={"tenant": "b"})
    text = m.render()
    assert text.count(f"# TYPE {NAMESPACE}_lat histogram") == 1
    # cumulative counts, labels folded with le
    assert f'{NAMESPACE}_lat_bucket{{tenant="a",le="0.005"}} 1' in text
    assert f'{NAMESPACE}_lat_bucket{{tenant="a",le="+Inf"}} 2' in text
    assert f'{NAMESPACE}_lat_bucket{{tenant="b",le="+Inf"}} 1' in text
    assert f'{NAMESPACE}_lat_sum{{tenant="a"}} {0.003 + 99.0}' in text
    assert f'{NAMESPACE}_lat_count{{tenant="a"}} 2' in text
    assert f'{NAMESPACE}_lat_count{{tenant="b"}} 1' in text


def test_histogram_exemplars_link_buckets_to_traces():
    m = InferenceMetrics(num_slots=1)
    m.observe("request_latency_seconds", 0.3)  # untraced: no exemplar
    m.observe("request_latency_seconds", 0.31, trace_id="tr-1")
    m.observe("request_latency_seconds", 0.32, trace_id="tr-2")  # last wins
    m.observe("request_latency_seconds", 99.0, trace_id="tr-inf")
    text = m.render()
    lines = [ln for ln in text.splitlines() if "_bucket{" in ln]
    le05 = next(ln for ln in lines if 'le="0.5"' in ln)
    assert '# {trace_id="tr-2"} 0.32 ' in le05
    inf = next(ln for ln in lines if 'le="+Inf"' in ln)
    assert '# {trace_id="tr-inf"} 99.0 ' in inf
    # buckets that never saw a traced observation carry no exemplar
    assert "# {" not in next(ln for ln in lines if 'le="0.001"' in ln)
    # exemplars are a bucket-line suffix only: sum/count stay plain
    assert "# {" not in next(ln for ln in text.splitlines()
                             if "_sum" in ln)


def test_dedupe_metadata_on_concatenated_registries():
    a, b = InferenceMetrics(num_slots=1), InferenceMetrics(num_slots=2)
    for m in (a, b):
        m.inc("requests_total")
        m.observe("lat", 0.01)
    text = dedupe_metadata(a.render() + b.render())
    for metric in (f"{NAMESPACE}_requests_total", f"{NAMESPACE}_lat",
                   f"{NAMESPACE}_slots_total"):
        assert sum(1 for ln in text.splitlines()
                   if ln.startswith(f"# TYPE {metric} ")) == 1, metric
    # sample lines from BOTH registries survive
    assert text.count(f"{NAMESPACE}_requests_total 1.0") == 2
    assert f"{NAMESPACE}_slots_total 1.0" in text
    assert f"{NAMESPACE}_slots_total 2.0" in text
