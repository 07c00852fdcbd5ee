"""CI smoke: a 2-cycle PPO loop with speculative decode + int8 frozen-trunk
decode ON (tiny random model, CPU). Passes when the loop completes with a
finite loss, ZERO speculative-decode fallbacks (the gate must accept the
smoke configuration — a silent fallback would make the CI step vacuous),
and at least one speculative round actually executed.

Run from the repo root: JAX_PLATFORMS=cpu python scripts/spec_decode_smoke.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB, EOS, N_PROMPT = 1024, 258, 16


def build_trainer():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    # Random weights emit arbitrary ids; the speculative rollout scorer
    # needs the decode->encode round trip to be the identity, as it is for
    # a trained model's text, so sampling is held to printable ASCII + eos.
    allowed = set(range(32, 127)) | {EOS}
    config = default_ppo_config().evolve(
        # num_layers_unfrozen 1: gpt2-tiny has two blocks, and a 2-of-2
        # split leaves no frozen trunk to draft from
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs=dict(vocab_size=VOCAB, attn_impl="flash")),
        train=dict(seq_length=128, batch_size=8, tracker=None,
                   fuse_inner_epoch=True, fuse_all_inner_epochs=True),
        method=dict(num_rollouts=16, chunk_size=16, speculative_decode=True, quantize_frozen_trunk=True,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True,
                                    suppress_tokens=[i for i in range(VOCAB) if i not in allowed])),
    )
    trainer = PPOTrainer(
        config, reward_fn=lambda samples, prompts, outputs, **kw: [float(o.count("e") - o.count("z"))
                                                                   for o in outputs])
    rng = np.random.default_rng(0)
    prompts = ["".join(chr(c) for c in rng.integers(97, 123, size=N_PROMPT)) for _ in range(256)]
    trainer.add_prompt_pipeline(PromptPipeline(prompts, max_prompt_length=N_PROMPT, tokenizer=trainer.tokenizer))
    return trainer


def main():
    trainer = build_trainer()
    _, pending = trainer.pipelined_cycle()
    _, pending = trainer.pipelined_cycle(pending)
    loss = float(np.asarray(pending[2][0]))

    rounds = int(getattr(trainer, "spec_decode_rounds", 0))
    accepted = int(getattr(trainer, "spec_decode_accepted", 0))
    fallbacks = int(getattr(trainer, "spec_decode_fallbacks", 0))
    k = int(trainer.config.method.spec_k)

    assert np.isfinite(loss), f"non-finite loss after 2 spec-decode cycles: {loss}"
    assert fallbacks == 0, (
        f"speculative decode fell back {fallbacks}x — the smoke config must "
        "pass the gate, otherwise this step tests nothing"
    )
    assert rounds > 0, "no speculative rounds ran"
    print(
        f"spec-decode smoke OK: loss {loss:.4f}, {rounds} rounds, "
        f"accept rate {accepted / (k * rounds):.2f} at k={k}, 0 fallbacks"
    )


if __name__ == "__main__":
    main()
