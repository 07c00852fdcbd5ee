"""Low-sync pipelined PPO cycle (single blocking host fetch per iteration).

A blocking device->host fetch stalls dispatch until the device drains; the
classic cycle pays three (samples, score outputs, loss). The pipelined cycle keeps
logprobs/values/REWARDS on device (`_build_score_reward_fn` constructs the
per-token rewards in-graph), trains all inner epochs straight from the
device chunk, and bundles the one remaining fetch with the next chunk's
samples. These tests pin the in-graph reward construction to the classic
numpy block (`_chunk_to_elements`) element-for-element, and run the cycle
end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.trainer.ppo_trainer import PPOTrainer


def _make_trainer(tmp_path, reward_fn=None, **method):
    method = {
        "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 2,
        "gen_kwargs": dict(max_new_tokens=6, do_sample=True),
        **method,
    }
    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=32, batch_size=8, total_steps=4, tracker=None,
                   checkpoint_dir=str(tmp_path), seed=7),
        method=dict(**method),
    )
    trainer = PPOTrainer(
        config,
        reward_fn=reward_fn or (lambda samples, **kw: [float(len(s)) for s in samples]),
    )
    pipeline = PromptPipeline(["hello world", "jax tpu", "ppo", "cycle"] * 2,
                              max_prompt_length=8, tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer


def _synthetic_chunk(trainer, n=8, q=6, r=6, dense=False):
    pad_id = trainer.tokenizer.pad_token_id
    rng = np.random.default_rng(3)
    prompts = rng.integers(97, 123, size=(n, q)).astype(np.int32)
    prompts[0, :2] = pad_id  # left-padded query row
    sample_outputs = rng.integers(97, 123, size=(n, r)).astype(np.int32)
    sample_outputs[1, 4:] = pad_id  # short response
    sample_outputs[2, :] = pad_id   # degenerate empty response
    if dense:
        S = 4
        scores = rng.normal(size=(n, S)).astype(np.float32)
        scores[3, 2:] = -np.inf  # ragged dense rows
    else:
        scores = rng.normal(size=(n, 1)).astype(np.float32)
    scores_mask = scores != -np.inf
    scores = np.where(scores_mask, scores, -np.inf)
    return prompts, sample_outputs, scores, scores_mask


@pytest.mark.parametrize("dense", [False, True])
def test_score_reward_parity(tmp_path, dense):
    """In-graph chunk == classic numpy elements, collated."""
    trainer = _make_trainer(tmp_path)
    pad_id = trainer.tokenizer.pad_token_id
    prompts, sample_outputs, scores, scores_mask = _synthetic_chunk(
        trainer, dense=dense
    )
    n, q = prompts.shape
    r = sample_outputs.shape[1]

    # classic path: score fn -> host fetch -> numpy element slicing -> collate
    trainer._build_score_fn()
    all_tokens = np.concatenate([prompts, sample_outputs], axis=1)
    logprobs, values, log_ratio, mean_kl_c, _ = jax.device_get(trainer._score_fn(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(all_tokens),
    ))
    clean_scores = np.where(scores_mask, scores, 0.0).astype(np.float32)
    elements = trainer._chunk_to_elements(
        prompts, sample_outputs, None, clean_scores, scores_mask,
        logprobs, values, log_ratio,
    )
    from trlx_tpu.native import ppo_collate

    cq, cr, clp, cv, crw = ppo_collate(elements, q, r, r, pad_id, True)

    # pipelined path: everything in-graph
    scalar = not dense
    if scalar:
        scores_eff = clean_scores
    else:
        scores_eff = np.zeros((n, r), np.float32)
        w = min(scores.shape[1], r)
        scores_eff[:, :w] = clean_scores[:, :w]
    fn = trainer._build_score_reward_fn(scalar)
    chunk, mean_kl_p, _ = jax.device_get(fn(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(prompts), jnp.asarray(sample_outputs),
        jnp.asarray(scores_eff), jnp.float32(trainer.kl_ctl.value),
    ))

    np.testing.assert_array_equal(np.asarray(chunk.query_tensors), cq)
    np.testing.assert_array_equal(np.asarray(chunk.response_tensors), cr)
    np.testing.assert_allclose(np.asarray(chunk.logprobs), clp, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunk.values), cv, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunk.rewards), crw, atol=1e-5)
    np.testing.assert_allclose(float(mean_kl_p), float(mean_kl_c), rtol=1e-5)


def test_pipelined_cycle_end_to_end(tmp_path):
    """Three cycles: losses arrive one cycle late, KL controller moves,
    params update. Sampling is suppressed to printable ASCII + eos (the
    trained-model condition: outputs decode and re-encode losslessly), so
    this also exercises the speculative scorer end-to-end and asserts it
    never fell back. (Unsuppressed random bytes are NOT round-trippable —
    invalid UTF-8 becomes U+FFFD on the host — and correctly fall back;
    test_spec_fallback_on_mismatch covers that arbitration.)"""
    suppress = [i for i in range(259) if not (32 <= i < 127 or i == 258)]
    trainer = _make_trainer(
        tmp_path,
        gen_kwargs=dict(max_new_tokens=6, do_sample=True,
                        suppress_tokens=suppress),
    )
    assert trainer._spec_path_available()
    p0 = jax.device_get(next(iter(trainer.train_params.values())))
    loss0, pending = trainer.pipelined_cycle()
    assert loss0 is None  # first cycle has no previous loss
    loss1, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    loss2, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss2, float) and np.isfinite(loss2)
    # final cycle's loss is fetchable from the pending handles
    final_loss = float(np.asarray(pending[2][0]))
    assert np.isfinite(final_loss)
    p1 = jax.device_get(next(iter(trainer.train_params.values())))
    assert not np.allclose(p0, p1)
    assert np.isfinite(trainer.mean_kl)
    assert getattr(trainer, "spec_fallbacks", 0) == 0


def _make_seq2seq_trainer(tmp_path):
    from trlx_tpu.data.configs import (
        ModelConfig, OptimizerConfig, ParallelConfig, SchedulerConfig,
        TokenizerConfig, TrainConfig, TRLConfig,
    )
    from trlx_tpu.trainer.ppo_trainer import PPOConfig

    config = TRLConfig(
        train=TrainConfig(
            seq_length=16, epochs=2, total_steps=4, batch_size=8,
            checkpoint_interval=100, eval_interval=100,
            pipeline="PromptPipeline", trainer="PPOTrainer", tracker=None,
            checkpoint_dir=str(tmp_path / "s2s"), seed=3,
        ),
        model=ModelConfig(
            model_path="random:t5-tiny", model_arch_type="seq2seq",
            num_layers_unfrozen=1,
            model_extra_configs=dict(decoder_start_token_id=8),
        ),
        tokenizer=TokenizerConfig(tokenizer_path="char:abcdefgh"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="constant"),
        method=PPOConfig(
            name="PPOConfig", num_rollouts=8, chunk_size=8, ppo_epochs=2,
            init_kl_coef=0.01, target=None, horizon=1000, gamma=1.0, lam=0.95,
            cliprange=0.2, cliprange_value=0.2, vf_coef=1.0, scale_reward=None,
            ref_mean=None, ref_std=None, cliprange_reward=10,
            gen_kwargs=dict(max_new_tokens=6, top_k=0, top_p=1.0, do_sample=True),
        ),
        parallel=ParallelConfig(),
    )
    trainer = PPOTrainer(
        config, reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples]
    )
    pipeline = PromptPipeline(["ab", "cd", "ef", "gh"] * 2,
                              max_prompt_length=8, tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer


def test_seq2seq_score_reward_parity(tmp_path):
    """The seq2seq in-graph score+reward chunk == classic numpy elements
    (decoder-relative windows, start token at position 0)."""
    trainer = _make_seq2seq_trainer(tmp_path)
    pad_id = trainer.tokenizer.pad_token_id
    rng = np.random.default_rng(5)
    n, q, r = 8, 6, 6
    prompts = rng.integers(0, 8, size=(n, q)).astype(np.int32)
    outputs = [list(rng.integers(0, 8, size=rng.integers(1, r + 1))) for _ in range(n)]
    outputs[2] = []  # degenerate empty response
    sample_outputs = np.full((n, 1 + r), pad_id, np.int32)
    sample_outputs[:, 0] = 8  # decoder start
    for i, o in enumerate(outputs):
        sample_outputs[i, 1:1 + len(o)] = o
    scores = rng.normal(size=(n, 1)).astype(np.float32)
    scores_mask = np.ones_like(scores, bool)

    trainer._build_score_fn()
    logprobs, values, log_ratio, mean_kl_c, _ = jax.device_get(trainer._score_fn(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(prompts), jnp.asarray(sample_outputs),
    ))
    elements = trainer._chunk_to_elements(
        prompts, sample_outputs, outputs, scores, scores_mask,
        logprobs, values, log_ratio,
    )
    from trlx_tpu.native import ppo_collate

    cq, cr, clp, cv, crw = ppo_collate(elements, q, 1 + r, r, pad_id, True)

    fn = trainer._build_score_reward_fn(True)
    chunk, mean_kl_p, _ = jax.device_get(fn(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(prompts), jnp.asarray(sample_outputs),
        jnp.asarray(scores), jnp.float32(trainer.kl_ctl.value),
    ))
    np.testing.assert_array_equal(np.asarray(chunk.query_tensors), cq)
    np.testing.assert_array_equal(np.asarray(chunk.response_tensors), cr)
    np.testing.assert_allclose(np.asarray(chunk.logprobs), clp, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunk.values), cv, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunk.rewards), crw, atol=1e-5)
    np.testing.assert_allclose(float(mean_kl_p), float(mean_kl_c), rtol=1e-5)


def test_seq2seq_pipelined_cycle_end_to_end(tmp_path):
    """The pipelined cycle runs seq2seq end-to-end (no speculative scorer
    there — the HF-style retokenize is not id-local for T5-style models)."""
    trainer = _make_seq2seq_trainer(tmp_path)
    assert not trainer._spec_path_available()
    p0 = jax.device_get(next(iter(trainer.train_params.values())))
    loss0, pending = trainer.pipelined_cycle()
    assert loss0 is None
    loss1, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert np.isfinite(float(np.asarray(pending[2][0])))
    p1 = jax.device_get(next(iter(trainer.train_params.values())))
    assert not np.allclose(p0, p1)


def test_pipelined_cycle_multi_chunk(tmp_path):
    """num_rollouts = 2 x chunk_size (VERDICT r3 item 7): the cycle
    collects two device-resident chunks per iteration and trains on their
    concatenation; losses stay finite, params move, and the optimizer sees
    num_rollouts/batch_size steps per inner epoch."""
    trainer = _make_trainer(tmp_path, num_rollouts=16, chunk_size=8)
    it0 = trainer.iter_count
    p0 = jax.device_get(next(iter(trainer.train_params.values())))
    loss0, pending = trainer.pipelined_cycle()
    assert loss0 is None
    loss1, pending = trainer.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert np.isfinite(float(np.asarray(pending[2][0])))
    # 16 rollouts / batch 8 = 2 steps x 2 ppo epochs per cycle, 2 cycles
    assert trainer.iter_count - it0 == 2 * 2 * 2
    p1 = jax.device_get(next(iter(trainer.train_params.values())))
    assert not np.allclose(p0, p1)


def test_device_retokenize_matches_host_roundtrip(tmp_path):
    """The speculative trim is exactly the host decode->encode round trip,
    across the shapes that matter: junk (vocab-padding) ids dropped with
    left-compaction, eos restored only on early stop, mid-sequence
    specials dropped, full-budget rows untouched."""
    trainer = _make_trainer(tmp_path)
    tok = trainer.tokenizer
    pad, eos, bos = tok.pad_token_id, tok.eos_token_id, tok.bos_token_id
    max_new = 6
    raw = np.array([
        [104, 105, 106, 107, 108, 109],     # full budget, all plain
        [104, 105, eos, pad, pad, pad],     # early stop at eos
        [104, 50000, 105, 301, 106, 107],   # junk vocab-padding ids
        [bos, 104, bos, 105, eos, pad],     # mid-sequence specials
        [eos, pad, pad, pad, pad, pad],     # immediate stop (empty)
        [104, 105, 106, 107, 108, eos],     # eos as the final token
    ], dtype=np.int32)
    q = 4
    prompts = np.full((raw.shape[0], q), 104, np.int32)

    device = np.asarray(tok.device_retokenize(jnp.asarray(raw), max_new))

    samples = np.concatenate([prompts, raw], axis=1)
    _, host_out, *_ = trainer._host_process_chunk(
        {"input_ids": prompts, "attention_mask": (prompts != pad).astype(np.int32)},
        samples,
    )
    np.testing.assert_array_equal(device, host_out)


def test_spec_score_matches_classic(tmp_path):
    """The speculative scorer's chunk == the fused score+reward fn's chunk
    on the same raw samples (same forward, same merge math)."""
    trainer = _make_trainer(tmp_path)
    tok = trainer.tokenizer
    pad, eos = tok.pad_token_id, tok.eos_token_id
    n, q, r = 8, 6, 6
    rng = np.random.default_rng(9)
    prompts = rng.integers(97, 123, size=(n, q)).astype(np.int32)
    raw = rng.integers(97, 123, size=(n, r)).astype(np.int32)
    raw[1, 3] = eos
    raw[1, 4:] = pad
    raw[2, 0] = eos
    raw[2, 1:] = pad
    samples = np.concatenate([prompts, raw], axis=1)
    scores_eff = rng.normal(size=(n, 1)).astype(np.float32)
    kl_coef = np.float32(trainer.kl_ctl.value)

    trim_fn = trainer._build_spec_trim_fn(q, r)
    spec_fn = trainer._build_spec_fwd_fn(q, r)
    trimmed = trim_fn(jnp.asarray(samples))
    lp, v, lr, mean_kl_s = spec_fn(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(samples), trimmed,
    )
    merge = trainer._build_spec_merge_fn(True)
    chunk_s = jax.device_get(merge(
        jnp.asarray(prompts), trimmed, lp, v, lr,
        jnp.asarray(scores_eff), kl_coef,
    ))

    classic = trainer._build_score_reward_fn(True)
    chunk_c, mean_kl_c, _ = jax.device_get(classic(
        trainer.train_params, trainer.frozen_params, trainer.ref_params,
        jnp.asarray(prompts), trimmed,
        jnp.asarray(scores_eff), kl_coef,
    ))

    for field in ("query_tensors", "response_tensors", "logprobs", "values",
                  "rewards"):
        np.testing.assert_allclose(
            np.asarray(getattr(chunk_s, field)),
            np.asarray(getattr(chunk_c, field)), atol=1e-6,
        )
    np.testing.assert_allclose(float(mean_kl_s), float(mean_kl_c), rtol=1e-5)


def test_spec_fallback_on_mismatch(tmp_path):
    """A stop-sequence config disables the speculative path entirely; a
    forced trim mismatch falls back to the classic fused scorer and counts
    it."""
    trainer = _make_trainer(tmp_path)
    # force a mismatch: pretend the device trim produced something else
    orig = trainer.tokenizer.device_retokenize
    trainer.tokenizer.device_retokenize = lambda ids, m: orig(ids, m) * 0 + 104
    loss0, pending = trainer.pipelined_cycle()
    loss1, pending = trainer.pipelined_cycle(pending)
    assert trainer.spec_fallbacks >= 1
    assert np.isfinite(float(np.asarray(pending[2][0])))

    # stop sequences -> no speculative path at all
    trainer2 = _make_trainer(tmp_path)
    trainer2.stop_sequences = ["zz"]
    assert not trainer2._spec_path_available()
