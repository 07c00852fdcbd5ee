"""CI smoke: the fused Pallas paged-attention decode kernel serving a
short CPU PPO run end to end. A 2-cycle supervised-fleet run generates
through paged replicas with `decode_kernel: interpret` (Pallas interpret
mode on CPU — the real kernel arithmetic, no TPU required) and
`tracing: true` so every replica engine carries a CompileLedger.

Passes when:
  - the run completes with no chunk degraded to local generation and a
    finite final loss;
  - every serving replica counted kernel dispatches and ZERO fallbacks
    (gpt2-tiny paged decode is a supported shape);
  - cycle 2 compiled NOTHING on any replica (the kernel dispatch is
    shape-stable: no retrace between cycles);
  - an unsupported shape (bloom-tiny: ALiBi) serves the same greedy
    tokens as `decode_kernel: xla` while counting an `alibi` fallback
    per dispatch instead of crashing.

Run from the repo root: JAX_PLATFORMS=cpu python scripts/paged_attention_smoke.py
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from trlx_tpu.data.default_configs import default_ppo_config  # noqa: E402
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline  # noqa: E402
from trlx_tpu.trainer.ppo_trainer import PPOTrainer  # noqa: E402
from trlx_tpu.utils import set_seed  # noqa: E402

FLEET_SIZE = 2
MAX_NEW = 4
KV_BLOCK = 8


def build_config(workdir: str):
    return default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(
            seq_length=32, batch_size=4, epochs=2, total_steps=2,
            checkpoint_interval=100, eval_interval=100,
            tracker="jsonl",
            logging_dir=os.path.join(workdir, "logs"),
            checkpoint_dir=os.path.join(workdir, "ckpts"),
            seed=13,
            rollout_backend="fleet",
            rollout_fleet_supervised=True,
            rollout_fleet_size=FLEET_SIZE,
            rollout_fleet_kwargs=dict(replica_retries=1, hedge=False),
            rollout_fleet_supervisor_kwargs=dict(
                tick_s=0.02, probe_interval_s=0.1, unhealthy_after=2,
                respawn_backoff_s=0.2, respawn_backoff_max_s=1.0,
                sync_interval_s=3600.0, start_timeout_s=300.0,
            ),
        ),
        method=dict(num_rollouts=8, chunk_size=4, ppo_epochs=2,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=False)),
        inference=dict(num_slots=4, max_prompt_len=32, max_new_tokens=MAX_NEW,
                       max_wait_s=0.0,
                       kv_paging=True, kv_block_size=KV_BLOCK,
                       decode_kernel="interpret", tracing=True),
    )


def run_fleet_cycles():
    workdir = tempfile.mkdtemp(prefix="paged_attention_smoke_")
    config = build_config(workdir)
    set_seed(config.train.seed)

    prompts = ["summarize this passage: " + tag
               for tag in ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]]

    # one snapshot per reward call: (cycle index, per-seat kv_stats,
    # per-seat compile-ledger counts)
    snapshots = []

    def reward_fn(samples, **kw):
        sup = trainer._rollout_supervisor
        if sup is not None:
            kv, compiles = {}, {}
            for seat in sup.seats:
                server = getattr(seat.handle, "server", None)
                if server is not None and hasattr(server, "engine"):
                    kv[seat.url] = server.engine.kv_stats()
                    ledger = server.engine.compile_ledger
                    if ledger is not None:
                        compiles[seat.url] = dict(ledger.counts())
            snapshots.append((trainer.iter_count, kv, compiles))
        return [float(len(s)) for s in samples]

    trainer = PPOTrainer(config, reward_fn=reward_fn)
    max_prompt_length = config.train.seq_length - MAX_NEW
    trainer.add_prompt_pipeline(
        PromptPipeline(prompts, max_prompt_length, trainer.tokenizer)
    )
    trainer.add_eval_pipeline(
        PromptPipeline(prompts, max_prompt_length, trainer.tokenizer)
    )
    trainer.learn()

    rows = []
    for name in os.listdir(config.train.logging_dir):
        if name.endswith(".metrics.jsonl"):
            with open(os.path.join(config.train.logging_dir, name)) as f:
                rows += [json.loads(line) for line in f if line.strip()]
    final_loss = [r for r in rows if "losses/total_loss" in r][-1]["losses/total_loss"]

    assert trainer.iter_count == config.train.total_steps, (
        f"run stopped at step {trainer.iter_count} / {config.train.total_steps}"
    )
    degraded = sum(r.get("fleet/degraded_chunks", 0.0) for r in rows)
    assert degraded == 0.0, (
        f"{degraded:.0f} chunk(s) fell back to local generation — the kernel "
        "engine failed to serve"
    )
    assert np.isfinite(final_loss), f"non-finite final loss: {final_loss}"

    assert snapshots and snapshots[-1][1], (
        "no kv_stats captured: replicas are not running the paged engine"
    )
    _, kv_final, compiles_final = snapshots[-1]
    dispatches = sum(s.get("kv_kernel_dispatches", 0) for s in kv_final.values())
    fallbacks = {}
    for s in kv_final.values():
        for reason, n in s.get("kv_kernel_fallbacks", {}).items():
            fallbacks[reason] = fallbacks.get(reason, 0) + n
    assert dispatches > 0, f"kernel never dispatched: {kv_final}"
    assert not fallbacks, (
        f"unexpected fallbacks on a supported shape: {fallbacks}"
    )

    # cycle 2 compiles nothing: per-replica ledger counts at the end of
    # cycle 1 (last snapshot with iter_count == 0) must equal the final
    # counts — any delta is a decode retrace between identical cycles
    cycle1 = [c for it, _, c in snapshots if it == 0][-1]
    assert compiles_final, "tracing on but no compile ledgers captured"
    for url, counts in compiles_final.items():
        before = cycle1.get(url)
        assert before is not None, f"{url}: replica (re)spawned mid-run"
        assert counts == before, (
            f"{url}: cycle 2 compiled something: {before} -> {counts}"
        )
    kernel_sites = [fn for c in compiles_final.values() for fn in c
                    if "[interpret]" in fn or "[pallas]" in fn]
    assert kernel_sites, (
        f"no kernel-mode decode site in the ledgers: {compiles_final}"
    )
    return dispatches, final_loss


def run_unsupported_shape():
    """bloom-tiny uses ALiBi: the kernel must fall back per dispatch with
    a counted reason and serve the gather path's exact greedy tokens."""
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:bloom-tiny",
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    tr = SFTTrainer(config)
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                               eos_token_id=10_000,
                               pad_token_id=tr.tokenizer.pad_token_id)

    def decode(decode_kernel):
        eng = InferenceEngine(
            tr.model, tr.model_cfg, tr.params, gen_cfg,
            num_slots=2, max_prompt_len=32, kv_paging=True,
            kv_block_size=KV_BLOCK, decode_kernel=decode_kernel,
        )
        eng.insert_requests([(np.arange(40, 55, dtype=np.int32), MAX_NEW)], [0])
        toks = []
        for _ in range(MAX_NEW):
            t, lp, v, f = eng.step()
            if v[0]:
                toks.append(int(t[0]))
            if f[0]:
                break
        return toks, eng.kv_stats()

    kernel_toks, kernel_stats = decode("interpret")
    gather_toks, _ = decode("xla")
    n_alibi = kernel_stats.get("kv_kernel_fallbacks", {}).get("alibi", 0)
    assert n_alibi >= 1, f"no counted alibi fallback: {kernel_stats}"
    assert kernel_stats.get("kv_kernel_dispatches", 0) == 0, kernel_stats
    assert kernel_toks == gather_toks, (
        f"fallback diverged from gather path: {kernel_toks} vs {gather_toks}"
    )
    return n_alibi


def main():
    dispatches, final_loss = run_fleet_cycles()
    n_alibi = run_unsupported_shape()
    print(
        f"paged attention smoke OK: {FLEET_SIZE} replicas served 2 cycles "
        f"via the interpret-mode kernel ({dispatches} dispatches, 0 "
        f"fallbacks, cycle 2 compiled nothing, final loss {final_loss:.4f}); "
        f"bloom-tiny counted {n_alibi} alibi fallback(s) and matched the "
        f"gather path"
    )


if __name__ == "__main__":
    main()
