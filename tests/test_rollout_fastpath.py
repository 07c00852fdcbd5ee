"""Rollout fast path (method.capture_rollout_stats): the sampling loop
captures per-token policy logprobs, values, and the hydra-split
activations, so scoring shrinks to the frozen-reference suffix.

Parity here is TOLERANCE-based: the captured stats come from the cached
decode steps while the scorer's come from one batched forward, so they
agree to float32 numerics, not bit-for-bit. The flag-OFF path stays
bit-identical to the classic sampler — that is pinned by
tests/test_sampling.py and tests/test_pipelined_cycle.py, not here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.models.transformer import position_ids
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.ppo_trainer import PPOTrainer
from trlx_tpu.utils.modeling import logprobs_of_labels

MAX_NEW = 6
SUPPRESS = [i for i in range(259) if not (32 <= i < 127 or i == 258)]

GEN_KWARGS = {
    "greedy": dict(max_new_tokens=MAX_NEW, do_sample=False,
                   suppress_tokens=SUPPRESS),
    "temperature": dict(max_new_tokens=MAX_NEW, do_sample=True,
                        temperature=0.7, suppress_tokens=SUPPRESS),
    "top_k": dict(max_new_tokens=MAX_NEW, do_sample=True, top_k=5,
                  suppress_tokens=SUPPRESS),
}


def _make_trainer(tmp_path, bucket=True, **method):
    method = {
        "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 2,
        "capture_rollout_stats": True,
        "gen_kwargs": dict(max_new_tokens=MAX_NEW, do_sample=True,
                           suppress_tokens=SUPPRESS),
        **method,
    }
    config = default_ppo_config().evolve(
        # float32: these are TOLERANCE tests between the cached-decode and
        # batched forwards; bf16 rounding alone is ~1e-2 at this scale
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=4, tracker=None,
                   checkpoint_dir=str(tmp_path), seed=11,
                   bucket_generation=bucket),
        method=dict(**method),
    )
    trainer = PPOTrainer(
        config,
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
    )
    pipeline = PromptPipeline(["hello world", "jax tpu", "ppo", "fast"] * 2,
                              max_prompt_length=8, tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer


@pytest.fixture(scope="module")
def trainer_nb(tmp_path_factory):
    """Shared no-bucketing trainer for the numeric parity tests (bucketed
    generation left-pads columns, which would add masked-attention noise
    on top of the decode-vs-batched deviation these tests measure)."""
    return _make_trainer(tmp_path_factory.mktemp("fastpath_nb"), bucket=False)


@pytest.fixture(scope="module")
def trainer_b(tmp_path_factory):
    """Shared default (bucketed) trainer for the dispatch/cycle tests."""
    return _make_trainer(tmp_path_factory.mktemp("fastpath_b"))


def _prompts(trainer, n=8, q=8):
    pad = trainer.tokenizer.pad_token_id
    rng = np.random.default_rng(17)
    ids = rng.integers(97, 123, size=(n, q)).astype(np.int32)
    mask = np.ones_like(ids)
    ids[0, :2] = pad  # one left-padded row
    mask[0, :2] = 0
    return ids, mask


def _capture_rollout(trainer, gen_kwargs):
    out = trainer.generate(*_prompts(trainer), gen_kwargs, capture=True)
    samples = np.asarray(out["samples"])
    q = samples.shape[1] - np.asarray(out["response_tokens"]).shape[1]
    return out, samples, q


@pytest.mark.parametrize("mode", sorted(GEN_KWARGS))
def test_captured_stats_match_batched_forward(trainer_nb, mode):
    """out["logprobs"]/out["values"] from the capture sampler == the
    batched scoring forward's response windows, on every real (non-pad)
    label position, across greedy / temperature / top-k sampling."""
    trainer = trainer_nb
    pad = trainer.tokenizer.pad_token_id
    out, samples, q = _capture_rollout(trainer, GEN_KWARGS[mode])
    assert out["logprobs"].shape == (samples.shape[0], MAX_NEW)
    assert out["values"].shape == (samples.shape[0], MAX_NEW)

    params = merge_params(trainer.train_params, trainer.frozen_params)
    amask = (samples != pad).astype(np.int32)
    logits, values, _ = jax.jit(trainer.model.apply)(
        {"params": params}, jnp.asarray(samples), jnp.asarray(amask),
        position_ids(jnp.asarray(amask)),
    )
    lp_full = np.asarray(
        logprobs_of_labels(logits[:, :-1], jnp.asarray(samples[:, 1:]))
    )
    start = q - 1
    labels = samples[:, q:q + MAX_NEW]
    valid = labels != pad
    assert valid.any()
    np.testing.assert_allclose(
        np.asarray(out["logprobs"])[valid],
        lp_full[:, start:start + MAX_NEW][valid], atol=5e-4,
    )
    np.testing.assert_allclose(
        np.asarray(out["values"])[valid],
        np.asarray(values)[:, start:start + MAX_NEW][valid], atol=5e-4,
    )


def test_fast_score_matches_spec_score(trainer_nb):
    """The fast scorer (frozen-ref suffix over captured activations) ==
    the speculative scorer (full policy/value/ref re-forward) on every
    real label position: logprobs, values, and the log-ratio the rewards
    are built from."""
    trainer = trainer_nb
    assert trainer._fast_rollout_available()
    pad = trainer.tokenizer.pad_token_id
    out, samples, q = _capture_rollout(trainer, GEN_KWARGS["temperature"])

    trimmed = trainer._build_spec_trim_fn(q, MAX_NEW)(jnp.asarray(samples))
    # suppressed-to-printable sampling round-trips exactly, so both
    # scorers see identical tokens
    np.testing.assert_array_equal(np.asarray(trimmed), samples[:, q:])

    lp_s, v_s, lr_s, kl_s = trainer._build_spec_fwd_fn(q, MAX_NEW)(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(samples), trimmed,
    )
    lp_f, v_f, lr_f, kl_f = trainer._build_fast_fwd_fn(q, MAX_NEW)(
        trainer.ref_params, jnp.asarray(samples), out["h_split"],
        out["logprobs"], out["values"],
    )
    valid = samples[:, q:q + MAX_NEW] != pad
    for fast, spec in ((lp_f, lp_s), (v_f, v_s), (lr_f, lr_s)):
        np.testing.assert_allclose(
            np.asarray(fast)[valid], np.asarray(spec)[valid], atol=5e-4
        )
    # mean_kl definitions differ only on non-label positions (documented
    # in _build_fast_fwd_fn); both must be finite and close here
    np.testing.assert_allclose(float(kl_f), float(kl_s), atol=1e-3)


def test_fast_dispatch_contract_matches_spec(trainer_b):
    """_dispatch_fast_score returns the same 5-handle contract as
    _dispatch_spec_score, so the cycle's merge/arbitration is shared."""
    trainer = trainer_b
    batch, out = trainer.dispatch_rollout_generation()
    assert "logprobs" in out and "values" in out and "h_split" in out
    fast = trainer._dispatch_fast_score(out)
    assert len(fast) == 5
    trimmed, lp, v, lr, mean_kl = fast
    assert lp.shape == v.shape == lr.shape
    assert np.isfinite(float(mean_kl))


def test_pipelined_cycle_fast_path_end_to_end(trainer_b):
    """Three pipelined cycles with capture_rollout_stats on: the fast
    double-buffer schedule produces finite losses one cycle late, never
    falls back to the classic scorer, and actually trains."""
    trainer = trainer_b
    assert trainer._fast_rollout_available()
    p0 = jax.device_get(next(iter(trainer.train_params.values())))
    loss0, pending = trainer.pipelined_cycle()
    assert loss0 is None
    loss1, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    loss2, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss2, float) and np.isfinite(loss2)
    assert np.isfinite(float(np.asarray(pending[2][0])))
    p1 = jax.device_get(next(iter(trainer.train_params.values())))
    assert not np.allclose(p0, p1)
    assert np.isfinite(trainer.mean_kl)
    assert getattr(trainer, "spec_fallbacks", 0) == 0


def test_fast_gate_flag_off(trainer_b):
    """Flag off -> the fast path is never taken (the classic/speculative
    scorers stay in charge; bit-identity is pinned elsewhere)."""
    trainer = trainer_b
    assert trainer.config.method.capture_rollout_stats
    assert trainer._fast_rollout_available()
    on_config = trainer.config
    try:
        trainer.config = trainer.config.evolve(
            method=dict(capture_rollout_stats=False)
        )
        assert not trainer._fast_rollout_available()
    finally:
        trainer.config = on_config


def test_engine_logprobs_match_batched_forward():
    """The continuous-batching engine's fused per-step sampler reports a
    logprob for every emitted token; greedy outputs across slot buckets
    must match a fresh batched forward's logprobs_of_labels."""
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.inference import InferenceEngine, Scheduler
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(model_path="random:gpt2-tiny",
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    trainer = SFTTrainer(config)
    gen_cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False,
        eos_token_id=trainer.tokenizer.eos_token_id,
        pad_token_id=trainer.tokenizer.pad_token_id,
    )
    engine = InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=2, max_prompt_len=64,
    )
    sched = Scheduler(engine, max_wait_s=0.0).start()
    rng = np.random.RandomState(3)
    # three prompts spanning both prompt-length buckets (<=32 and <=64)
    prompts = [rng.randint(0, 255, size=n).tolist() for n in (5, 37, 12)]
    try:
        reqs = [sched.submit(p, 8) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert r.wait(120), "request timed out"
            assert len(r.token_logprobs) == len(r.token_ids)
            full = np.asarray([p + r.token_ids], np.int32)
            res = jax.jit(trainer.model.apply)(
                {"params": trainer.params}, jnp.asarray(full),
                jnp.ones_like(jnp.asarray(full)),
            )
            logits = res[0] if isinstance(res, tuple) else res
            lp = np.asarray(
                logprobs_of_labels(logits[:, :-1], jnp.asarray(full[:, 1:]))
            )[0]
            want = lp[len(p) - 1:len(p) - 1 + len(r.token_ids)]
            np.testing.assert_allclose(r.token_logprobs, want, atol=5e-4)
    finally:
        sched.stop()
