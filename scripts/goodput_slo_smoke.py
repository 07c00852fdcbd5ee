"""CI smoke: goodput ledger attribution + fleet SLO burn-rate alerting.

Two independent checks, both CPU-only and dependency-free:

1. **Goodput ledger** — a short traced PPO run with the health sentinel
   ON and an injected two-step loss spike (forcing one rewind) must
   produce a `goodput.json` whose per-cause seconds sum to the measured
   wall time within 5%, with jit compile split out, the injected rewind
   attributed to `waste/rewind`, `goodput/*` stats flushed through the
   tracker on every stats step, and a ledger FLOP total that agrees with
   the offline per-cycle FLOP model within 10% (i.e. the live MFU
   and the offline MFU agree over the same window).

2. **Fleet SLO engine** — a supervised 2-replica fleet where one replica
   serves correct-but-slow answers (FaultInjector mode="slow") must
   drive `slo_burn_rate{slo="latency_p99"}` above its alert threshold:
   the supervisor's HTTP `GET /debug/slo` reports the SLO as burning,
   the burn-rate gauge appears on `/metrics`, and a latency-histogram
   bucket exemplar on a replica's own `/metrics` carries a trace_id
   resolvable through that replica's `GET /debug/trace`.

Artifacts (goodput.json + both /metrics scrapes + /debug/slo) are
copied under --artifact-dir (default logs/goodput_slo_smoke) so CI can
upload them on failure.

Run from the repo root: JAX_PLATFORMS=cpu python scripts/goodput_slo_smoke.py
"""

import json
import os
import re
import shutil
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from trlx_tpu import resilience  # noqa: E402
from trlx_tpu.data.default_configs import default_ppo_config  # noqa: E402
from trlx_tpu.inference.supervisor import FleetSupervisor, ThreadReplica  # noqa: E402
from trlx_tpu.observability.flops import flops_per_cycle  # noqa: E402
from trlx_tpu.observability.slo import SLO  # noqa: E402
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline  # noqa: E402
from trlx_tpu.trainer.ppo_trainer import PPOTrainer  # noqa: E402
from trlx_tpu.utils import set_seed  # noqa: E402

MAX_NEW = 6
SLOW_S = 0.6  # injected per-request handler delay on the slow replica
N_REQUESTS = 24


def _http_get(url: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _save(artifact_dir: str, name: str, text: str) -> None:
    os.makedirs(artifact_dir, exist_ok=True)
    with open(os.path.join(artifact_dir, name), "w") as f:
        f.write(text)


# ----------------------------------------------------------------------
# Part 1: goodput ledger on a sentinel-rewind PPO run
# ----------------------------------------------------------------------


def goodput_config(workdir: str):
    return default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(
            seq_length=32, batch_size=8, epochs=8, total_steps=8,
            checkpoint_interval=100, eval_interval=100,
            tracker="jsonl",
            logging_dir=os.path.join(workdir, "logs"),
            checkpoint_dir=os.path.join(workdir, "ckpts"),
            seed=7,
            tracing=True,
            trace_dir=os.path.join(workdir, "traces"),
            # sentinel tuned like sentinel_chaos_smoke: two consecutive
            # spiked steps trip a rewind to the pinned last_good
            sentinel=True, grad_skip_threshold=50.0, sentinel_window=8,
            sentinel_warmup=2, sentinel_skip_after=2,
            sentinel_rewind_after=2, sentinel_good_steps=1,
            sentinel_pin_interval=1, max_rewinds=4,
            sentinel_cooldown_steps=4,
        ),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=2,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=False)),
    )


def check_goodput(artifact_dir: str) -> str:
    workdir = tempfile.mkdtemp(prefix="goodput_smoke_")
    config = goodput_config(workdir)
    set_seed(config.train.seed)

    trainer = PPOTrainer(
        config, reward_fn=lambda samples, **kw: [float(len(s)) for s in samples]
    )
    trainer.fault_injector = resilience.FaultInjector(
        loss_spike_steps=[4, 5], spike_scale=1e4
    )
    max_prompt_length = config.train.seq_length - MAX_NEW
    prompts = ["hello world", "jax tpu", "ppo", "goodput"] * 2
    trainer.add_prompt_pipeline(
        PromptPipeline(prompts, max_prompt_length, trainer.tokenizer)
    )
    trainer.add_eval_pipeline(
        PromptPipeline(prompts, max_prompt_length, trainer.tokenizer)
    )
    trainer.learn()

    gp_path = os.path.join(config.train.trace_dir, "goodput.json")
    assert os.path.exists(gp_path), "learn() left no goodput.json artifact"
    shutil.copy(gp_path, os.path.join(artifact_dir, "goodput.json"))
    with open(gp_path) as f:
        snap = json.load(f)

    # every wall-clock second attributed: causes sum to wall within 5%
    total = sum(snap["seconds"].values())
    assert abs(total - snap["wall_s"]) <= 0.05 * snap["wall_s"], (
        f"cause seconds sum {total:.3f}s vs wall {snap['wall_s']:.3f}s"
    )
    # compile split out of steady-state train/rollout time
    assert snap["seconds"].get("compile", 0.0) > 0.0, (
        f"no compile time split out: {snap['seconds']}"
    )
    # the injected sentinel rewind is attributed as waste
    assert snap["rewinds"] >= 1, "fault injection produced no rewind"
    assert snap["seconds"].get("waste/rewind", 0.0) > 0.0, (
        f"rewind happened but no waste/rewind seconds: {snap['seconds']}"
    )
    assert snap["wasted_s"] > 0.0 and snap["goodput_fraction"] < 1.0

    # live FLOP accounting agrees with the offline per-cycle
    # model: the ledger priced every noted sample/row with
    # flops_per_sample; the offline model prices whole cycles. Same
    # config => totals must agree (within 10%, covering the partial
    # cycle a rewind replays).
    n_rollouts = config.method.num_rollouts
    cycles = snap["samples_total"] / n_rollouts
    tokens_per_sample = snap["tokens_total"] / max(snap["samples_total"], 1)
    n_prompt = int(round(tokens_per_sample)) - MAX_NEW
    fc = flops_per_cycle(
        trainer.model_cfg, n_prompt, MAX_NEW, n_rollouts,
        config.method.ppo_epochs,
        unfrozen=trainer.model_cfg.n_layers - trainer.split,
        window_ok=(trainer._window_loss_ok()
                   and getattr(trainer.model_cfg, "moe_experts", 0) == 0),
        fast_path=False,
    )
    offline_flops = fc["total"] * cycles
    live_flops = snap["flops_total"]
    assert offline_flops > 0 and live_flops > 0, (live_flops, offline_flops)
    rel = abs(live_flops - offline_flops) / offline_flops
    assert rel <= 0.10, (
        f"ledger FLOPs {live_flops:.3e} vs offline bench model "
        f"{offline_flops:.3e} ({rel:.1%} apart; same wall => same MFU gap)"
    )

    # goodput/* and timing/* flushed through the tracker every stats step
    rows = []
    for name in os.listdir(config.train.logging_dir):
        if name.endswith(".metrics.jsonl"):
            with open(os.path.join(config.train.logging_dir, name)) as f:
                rows += [json.loads(line) for line in f if line.strip()]
    goodput_rows = [r for r in rows if "goodput/wall_s" in r]
    assert len(goodput_rows) >= 2, (
        f"goodput/* flushed {len(goodput_rows)}x; want every stats step"
    )
    assert any("timing/train_minibatch_ms" in r for r in rows), (
        "timing/* stats missing from the tracker stream"
    )
    assert goodput_rows[-1].get("goodput/waste_rewind_s", 0.0) > 0.0, (
        "waste/rewind never surfaced through tracker stats"
    )
    final_loss = [r for r in rows if "losses/total_loss" in r][-1][
        "losses/total_loss"]
    assert np.isfinite(final_loss), f"non-finite final loss {final_loss}"

    return (
        f"goodput OK: wall {snap['wall_s']:.1f}s, causes sum {total:.1f}s, "
        f"compile {snap['seconds']['compile']:.1f}s, waste/rewind "
        f"{snap['seconds']['waste/rewind']:.2f}s, ledger-vs-offline FLOP "
        f"gap {rel:.1%}, {len(goodput_rows)} tracker flushes"
    )


# ----------------------------------------------------------------------
# Part 2: fleet SLO burn rate + trace exemplars
# ----------------------------------------------------------------------


def slo_config(workdir: str):
    return default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=4, total_steps=2, tracker=None,
                   checkpoint_dir=os.path.join(workdir, "ckpts"), seed=11),
        method=dict(num_rollouts=8, chunk_size=4,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=False)),
        inference=dict(num_slots=4, max_prompt_len=32, max_new_tokens=MAX_NEW,
                       max_wait_s=0.0, tracing=True, trace_sample_rate=1.0),
    )


def check_fleet_slo(artifact_dir: str) -> str:
    workdir = tempfile.mkdtemp(prefix="slo_smoke_")
    trainer = PPOTrainer(slo_config(workdir),
                         reward_fn=lambda samples, **kw: [0.0] * len(samples))
    # tight SLO so an injected 600ms handler delay is a clear violation;
    # small windows/min_events so ~24 requests carry the verdict
    slos = [
        SLO("latency_p99", "latency", target=0.99, threshold_s=0.25,
            fast_window_s=30.0, slow_window_s=120.0, burn_alert=2.0,
            min_events=5,
            description="99% of fleet dispatches within 250ms"),
        SLO("availability", "availability", target=0.999, min_events=5),
    ]
    sup = FleetSupervisor(
        replica_factory=lambda i: ThreadReplica(
            lambda: trainer.serve(host="127.0.0.1", port=0, background=True)
        ),
        num_replicas=2,
        router_kwargs=dict(hedge=False, replica_retries=0, slos=slos,
                           probe_timeout_s=2.0),
        probe_interval_s=0.2, tick_s=0.05, metrics_port=0,
        start_timeout_s=120.0,
    )
    sup.start()
    try:
        sup.wait_ready()
        router = sup.router
        # warm both replicas (compile prefill/decode) before timing
        for rep in router.replicas:
            router._post(rep, {"prompt_ids": [104, 105],
                               "max_new_tokens": MAX_NEW})
        # latency fault: replica 0 answers correctly but SLOW — visible
        # only in router-side dispatch wall time (the handler sleeps
        # before the scheduler ever sees the request)
        slow_server = sup.seats[0].handle.server
        slow_server.fault_injector = resilience.FaultInjector(
            rate=1.0, mode="slow", slow_s=SLOW_S
        )
        for i in range(N_REQUESTS):
            router.generate_one([104, 101, 108 + (i % 8)],
                                max_new_tokens=MAX_NEW)

        # --- supervisor HTTP /debug/slo reports the burn ---------------
        base = f"http://127.0.0.1:{sup.metrics_port}"
        slo_report = _http_get(base + "/debug/slo")
        _save(artifact_dir, "fleet_debug_slo.json", slo_report)
        report = json.loads(slo_report)
        p99 = next(s for s in report["slos"] if s["name"] == "latency_p99")
        fast = next(w for w in p99["windows"] if w["window"] == "fast")
        assert fast["events"] >= 5, f"too few SLO events: {fast}"
        assert fast["burn_rate"] >= p99["burn_alert"], (
            f"latency_p99 fast burn {fast['burn_rate']} below alert "
            f"threshold {p99['burn_alert']}"
        )
        assert p99["burning"], f"latency_p99 not burning: {p99['windows']}"

        # --- burn-rate gauge on the fleet /metrics ---------------------
        fleet_metrics = _http_get(base + "/metrics")
        _save(artifact_dir, "fleet_metrics.prom", fleet_metrics)
        burn_lines = [
            ln for ln in fleet_metrics.splitlines()
            if ln.startswith('trlx_tpu_fleet_slo_burn_rate{slo="latency_p99"')
        ]
        assert burn_lines, "slo_burn_rate{latency_p99} series missing"
        assert any(float(ln.rsplit(" ", 1)[1]) >= 2.0 for ln in burn_lines), (
            f"no window above burn_alert: {burn_lines}"
        )
        # exactly one TYPE line per metric after registry concatenation
        type_names = [ln.split(" ")[3 - 1] for ln in
                      fleet_metrics.splitlines() if ln.startswith("# TYPE ")]
        dupes = {n for n in type_names if type_names.count(n) > 1}
        assert not dupes, f"duplicate TYPE metadata after concat: {dupes}"

        # --- p99-bucket exemplar resolvable via /debug/trace -----------
        rep_url = sup.seats[1].url  # the healthy replica (also traced)
        rep_metrics = _http_get(rep_url + "/metrics")
        _save(artifact_dir, "replica_metrics.prom", rep_metrics)
        exemplars = re.findall(
            r'request_latency_seconds_bucket\{[^}]*\} \d+ '
            r'# \{trace_id="([^"]+)"\}', rep_metrics)
        assert exemplars, "no exemplar on any request_latency bucket"
        traces = json.loads(_http_get(rep_url + "/debug/trace?last=512"))
        known = {t["trace_id"] for t in traces["traces"]}
        resolvable = set(exemplars) & known
        assert resolvable, (
            f"exemplar trace_ids {set(exemplars)} not resolvable among "
            f"{len(known)} /debug/trace entries"
        )
    finally:
        sup.stop()

    return (
        f"fleet SLO OK: latency_p99 fast burn {fast['burn_rate']:.1f} "
        f"(alert {p99['burn_alert']}), {fast['bad']}/{fast['events']} bad "
        f"dispatches, {len(resolvable)} exemplar trace_id(s) resolved"
    )


def main():
    artifact_dir = (sys.argv[sys.argv.index("--artifact-dir") + 1]
                    if "--artifact-dir" in sys.argv
                    else os.path.join("logs", "goodput_slo_smoke"))
    os.makedirs(artifact_dir, exist_ok=True)
    msg1 = check_goodput(artifact_dir)
    print(msg1)
    msg2 = check_fleet_slo(artifact_dir)
    print(msg2)
    print("goodput+slo smoke OK")


if __name__ == "__main__":
    main()
