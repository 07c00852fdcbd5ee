"""Long-context training benchmark: single-chip tokens/s + MFU at 8k-16k.

The reference's longest context is 2048 (every shipped NeMo config pins
encoder_seq_length 2048; Megatron SP only shards activations within a TP
group — SURVEY.md §5.7), so there is no reference number to normalize
against and the value stands on its own. This
measures the regime the ring/flash kernels exist for — full fwd+bwd
language-model training steps (CE over the 50,257 vocab) at GPT-2-small
shape with `attn_impl="flash"` and per-block rematerialization, where
attention is the dominant FLOP term (4·L·t·d per token ≈ 2.4× the matmul
term at t=16k).

Timing: pipelined dispatch of N steps with one final host sync (a blocking
fetch per step would stall dispatch). Its own command and its own process:
`python bench_longctx.py`.

Prints ONE JSON line per sequence length:
  {"metric": "longctx_train_tokens_per_sec_per_chip", "seq_len": ...,
   "value": ..., "unit": "tokens/s/chip", "device": {...}}
plus "mfu_estimate" on a device kind observability/flops.py has a peak
row for.
"""

import json
import os
import sys
import time

import numpy as np

from trlx_tpu.observability.flops import chip_peak_flops


def run(seq_len: int, batch: int, n_steps: int = 5, smoke: bool = False,
        attn_impl: str = "flash"):
    import jax
    import jax.numpy as jnp
    import optax

    from trlx_tpu.models import config_from_preset
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.trainer.sft_trainer import causal_lm_ce_loss

    preset = "gpt2-tiny" if smoke else "gpt2-small"
    vocab = 1024 if smoke else 50257
    cfg = config_from_preset(
        preset, vocab_size=vocab, max_seq_len=seq_len,
        attn_impl=attn_impl, remat_blocks=True,
    )
    model = TransformerLM(cfg)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, vocab, size=(batch, seq_len)).astype(np.int32))
    mask = jnp.ones((batch, seq_len), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0), tokens[:1, :128], mask[:1, :128]
    )["params"]

    optimizer = optax.adamw(1e-5)
    opt_state = optimizer.init(params)

    def loss_fn(params, tokens, mask):
        logits, _, _ = model.apply({"params": params}, tokens, mask)
        loss, _ = causal_lm_ce_loss(logits, tokens, mask)
        return loss

    def step(params, opt_state, tokens, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))

    # warmup (compile) + drain
    params, opt_state, loss = step(params, opt_state, tokens, mask)
    _ = float(np.asarray(loss))
    t0 = time.time()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, tokens, mask)
    _ = float(np.asarray(loss))
    elapsed = time.time() - t0

    tokens_per_step = batch * seq_len
    tps = tokens_per_step * n_steps / elapsed

    # FLOPs/step: fwd = T(L·blk + head) + L·4·(t/2)·d per token;
    # bwd ≈ 2× fwd (all layers trainable); remat re-runs each block's
    # forward once more in the backward (+1× the block terms, not the head)
    d, L, dff, V, t = cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.vocab_size, seq_len
    blk = 8 * d * d + 4 * d * dff
    att = 4 * (t / 2) * d
    head = 2 * d * V
    fwd = tokens_per_step * (L * (blk + att) + head)
    remat = tokens_per_step * L * (blk + att)
    flops_step = 3 * fwd + remat
    device = jax.devices()[0]
    record = {
        "metric": "longctx_train_tokens_per_sec_per_chip",
        "seq_len": seq_len,
        "batch": batch,
        "attn_impl": attn_impl,
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": jax.device_count()},
    }
    try:
        record["mfu_estimate"] = round(
            flops_step * n_steps / elapsed / chip_peak_flops(), 4)
    except LookupError:
        pass  # no peak row for this device kind: no MFU
    print(json.dumps(record))
    sys.stderr.write(
        f"[bench_longctx] {preset} vocab {vocab} seq {seq_len} batch {batch}: "
        f"{n_steps} steps in {elapsed:.2f}s, est {flops_step / 1e12:.2f}T/step "
        f"(attention share {L * att / (L * (blk + att) + head):.0%})\n"
    )
    return record


def main():
    import jax

    # persistent XLA compile cache (same dir as bench.py): the 8k/16k
    # flash fwd+bwd graphs take minutes to compile cold, seconds warm
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("TRLX_TPU_XLA_CACHE",
                                     "/tmp/trlx_tpu_xla_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

    smoke = "--smoke" in sys.argv
    if smoke:
        run(512, 2, n_steps=2, smoke=True)
        return
    impl = "flash"
    if "--impl" in sys.argv:
        # e.g. "blockwise" (pure-XLA scan flash): compiles fast but its
        # scan backward banks the O(t) carry per kv block, so it only fits
        # HBM at moderate sequence lengths — useful for comparisons, NOT
        # as an 8k cold-cache fallback (measured: 49G needed at 8k/b4)
        impl = sys.argv[sys.argv.index("--impl") + 1]
    if "--seq" in sys.argv:  # single-length mode
        seq = int(sys.argv[sys.argv.index("--seq") + 1])
        run(seq, max(2, 32768 // seq), attn_impl=impl)
        return
    run(8192, 4, attn_impl=impl)
    if "--8k-only" not in sys.argv:
        run(16384, 2, attn_impl=impl)


if __name__ == "__main__":
    main()
