"""FLOP model for PPO cycles — the itemized estimate the live goodput
ledger (`trlx_tpu/observability/goodput.py`) prices a running trainer's
samples with, and the one a whole cycle is priced with offline.

One model for both, so a running trainer's live MFU and an offline
per-cycle estimate (`scripts/goodput_slo_smoke.py` holds the two within
10%) move together with any change made here.

Dependency-free at import time: `chip_peak_flops()` imports jax lazily,
so this module can be imported by host-only tooling.
"""

# bf16 peak FLOP/s per chip by device kind (dense; no sparsity).
PEAK_FLOPS = [
    ("v5 lite", 197e12),  # TPU v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6", 918e12),  # trillium
]


def chip_peak_flops() -> float:
    """Peak dense bf16 FLOP/s of device 0, by device_kind lookup. A device
    kind with no row (a CPU included) raises LookupError: an MFU priced at
    some other chip's peak is not a measurement."""
    import jax

    kind = jax.devices()[0].device_kind
    for tag, peak in PEAK_FLOPS:
        if tag in kind.lower():
            return peak
    raise LookupError(f"no peak-FLOP/s row for device kind {kind!r}")


def layer_matmul_flops(model_cfg, i: int) -> float:
    """Matmul FLOPs a token of layer i's forward. A config that names no
    layer kinds keeps the family-blind estimate it always had (8 d^2 + 4 d
    d_ff). One that does is counted as built: GQA projections at the layer's
    own query heads and the stated head width (and the per-head output
    gate), or the short convolution's in/out projections and taps; a dense
    (gated) MLP, or the router plus the experts a token meets HERE: top_k x
    held / experts of width moe_d_ff (the active experts, not the resident
    ones) and the shared expert whole. A latent-attention layer is counted
    decompressed (a forward's and a prefill's form; an absorbed decode step
    trades the per-head keys and values for two products of heads x
    qk_nope x kv_lora and heads x kv_lora x v)."""
    d = model_cfg.d_model
    if not getattr(model_cfg, "layer_types", ()):
        return 8 * d * d + 4 * d * model_cfg.d_ff
    if model_cfg.layer_op(i) == "conv":
        op = 2 * d * 3 * d + 2 * d * d + 2 * model_cfg.conv_kernel * d
    elif model_cfg.layer_op(i) == "linear_attention":
        # q, k, v, the decay's and the output projection (5 of d x heads x
        # width), the write strength and the output gate a head, the three
        # convolutions' taps, and the recurrence itself: 7 d_k d_v a head a
        # token (`ops/linear_attention.kda_step`), whatever the context
        c, hw = model_cfg, model_cfg.n_heads * model_cfg.head_dim
        op = 2 * 5 * d * hw + 2 * 2 * d * c.n_heads + 2 * c.conv_kernel * c.kda_width \
            + 7 * c.n_heads * c.head_dim * c.head_dim
    elif _latent_of(model_cfg, i) is not None:
        # decompressed, as a forward or a prefill runs it: the query's
        # low-rank pair (or its one full-rank product), the latent and shared
        # rotary key, the per-head keys and values out of the latent, the
        # output projection; where an index chooses the positions, its
        # queries, its one key and its heads' weights
        c, heads = _latent_of(model_cfg, i), _layer_heads(model_cfg, i)
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        query = d * c.q_lora_rank + c.q_lora_rank * heads * qk if c.q_lora_rank else d * heads * qk
        op = 2 * (query + d * c.width
                  + c.kv_lora_rank * heads * (c.qk_nope_head_dim + c.v_head_dim) + heads * c.v_head_dim * d)
        if c.index_topk:
            op += 2 * (c.q_lora_rank * c.index_heads * c.index_head_dim + d * (c.index_head_dim + c.index_heads))
        if getattr(model_cfg, "attn_gate", "none") == "per_head":
            op += 2 * d * heads
    else:
        heads = _layer_heads(model_cfg, i)
        op = 4 * d * heads * model_cfg.head_dim + 4 * d * model_cfg.kv_heads * model_cfg.head_dim
        if getattr(model_cfg, "attn_gate", "none") == "per_head":
            op += 2 * d * heads
        if model_cfg.layer_op(i) == "ssm_attention":
            # beside the attention: the SSM's in and out projections, its convolution's
            # taps, and the recurrence itself: 5 N P a head a token (decay, write, read:
            # `ops/ssd.ssd_step`), whatever the context
            c, d_ssm = model_cfg, model_cfg.ssm_heads * model_cfg.ssm_head_dim
            op += 2 * d * (d_ssm + c.ssm_width + c.ssm_heads) + 2 * d_ssm * d \
                + 2 * c.ssm_conv_kernel * c.ssm_width + 5 * c.ssm_heads * c.ssm_state * c.ssm_head_dim
    mats = 3 if model_cfg.glu else 2
    if model_cfg.layer_ffn(i) == "dense":
        return op + 2 * mats * d * model_cfg.d_ff
    active = model_cfg.moe_top_k * model_cfg.experts_held / model_cfg.moe_experts
    shared = 2 * mats * d * getattr(model_cfg, "moe_shared_d_ff", 0)
    return op + 2 * d * model_cfg.moe_experts + active * 2 * mats * d * model_cfg.expert_d_ff + shared


def _latent_of(model_cfg, i: int):
    """Layer i's `LatentSpec`, None for a layer that is no latent attention."""
    return model_cfg.latent_of(model_cfg.layer_op(i)) if hasattr(model_cfg, "latent_of") else None


def _layer_heads(model_cfg, i: int) -> int:
    heads = getattr(model_cfg, "layer_heads", ())
    latent = _latent_of(model_cfg, i)
    return heads[i] if heads else latent.n_heads if latent is not None else model_cfg.n_heads


def layer_attention_flops(model_cfg, i: int, ctx: float) -> float:
    """Score and probability-times-value FLOPs a token of layer i at a
    context of `ctx` keys: 4 x keys x heads x head width, the keys a sliding
    layer's window at most, 0 for a layer that is no attention. For a config
    that names no layer kinds this is the 4 ctx d it always was."""
    if not getattr(model_cfg, "layer_types", ()):
        return 4 * ctx * model_cfg.d_model
    kind = model_cfg.layer_op(i)
    if kind in ("conv", "linear_attention"):  # nothing grows with the context
        return 0.0
    latent = _latent_of(model_cfg, i)
    if latent is not None:
        # scores at the query/key width, values at their own, over the keys a band or an index's
        # choice leaves; the index itself scores every key with each of its heads
        keys = min(ctx, latent.window or ctx, latent.index_topk or ctx)
        index = 2 * ctx * latent.index_heads * latent.index_head_dim if latent.index_topk else 0
        return index + 2 * keys * _layer_heads(model_cfg, i) * (
            latent.qk_nope_head_dim + latent.qk_rope_head_dim + latent.v_head_dim)
    window = model_cfg.window_of(kind)
    keys = ctx if window is None else min(ctx, window)
    return 4 * keys * _layer_heads(model_cfg, i) * model_cfg.head_dim


def flops_per_cycle(model_cfg, n_prompt, n_new, n_rollouts, ppo_epochs,
                    unfrozen, window_ok: bool = True,
                    fast_path: bool = False) -> dict:
    """Itemized FLOP estimate for one PPO cycle (documented approximations;
    used only for the MFU estimate, never for vs_baseline).

    Per-token forward cost at context c:
      L*(8 d^2 + 4 d d_ff)   block matmuls (qkvo 2*4d^2 + mlp 2*2*d*d_ff)
      + L*4*c*d              attention scores + prob@V
      + 2 d V                lm_head logits
    For a model whose layers differ (`layer_types`, experts) the block term
    is summed layer by layer (`layer_matmul_flops`) and so is the attention
    term (`layer_attention_flops`: the attention layers only, each at its
    own query heads, a sliding one at its window at most).
    Backward stops at the freeze split (grads are taken w.r.t. the
    trainable partition only, base_trainer.py grad_fn; XLA prunes below):
    dX through the lm_head matmul + the `unfrozen` top blocks, plus dW
    over those same blocks (the tied embedding is frozen, so the head
    contributes dX but no dW). Generation decode counts the lm_head every
    step and prefill counts it on all prompt positions (that is what the
    engine computes)."""
    d, L, V = model_cfg.d_model, model_cfg.n_layers, model_cfg.vocab_size
    T = n_prompt + n_new
    head = 2 * d * V
    per_layer = [layer_matmul_flops(model_cfg, i) for i in range(L)]

    def fwd(tokens, avg_ctx, layers=L, with_head=True):
        """`layers` of the stack: its top ones (the unfrozen suffix)."""
        lo, hi = L - layers, L
        attention = sum(layer_attention_flops(model_cfg, i, avg_ctx) for i in range(lo, hi))
        # a looped stack runs every layer `loop_steps` times a token, the head once
        passes = getattr(model_cfg, "loop_steps", 1)
        return tokens * (passes * (sum(per_layer[lo:hi]) + attention) + (head if with_head else 0))

    # generation: prefill the prompt, then n_new cached decode steps
    gen = fwd(n_prompt, n_prompt / 2) + fwd(n_new, n_prompt + n_new / 2)
    if fast_path:
        # fast rollout path: policy logprobs + values were captured inside
        # the sampling loop (already counted under gen), so score is ONLY
        # the frozen-reference suffix resumed from the captured split
        # activations, with the unembedding windowed to the n_new response
        # positions the KL reads
        score = fwd(T, T / 2, layers=unfrozen, with_head=False) + n_new * head
    else:
        # scoring: full policy+value fwd, plus the in-graph frozen-reference
        # branch re-running the top `unfrozen` blocks + lm_head
        score = fwd(T, T / 2) + fwd(T, T / 2, layers=unfrozen)
    # one train step: fwd + dX/dW over the unfrozen top. What a step
    # REQUIRES of the frozen blocks below is nothing: their state for these
    # tokens is what the scorer's forward already computed, so a trunk that
    # a schedule runs again (the PPO trainer's once-a-cycle cache fill; the
    # whole forward of every step where `_trunk_cache_available` says no)
    # is recomputation and is not charged, like rematerialization.
    # When the r5 windowed head applies (ppo_trainer
    # `forward(window=...)` — no MoE, no deeper value branch, no soft prompt),
    # the 2·d·V unembedding (fwd + dX) only covers the n_new response
    # positions the loss reads; otherwise the step really computes the
    # full-width head and the estimate must charge all T positions.
    head_tokens = n_new if window_ok else T
    train_fwd = fwd(T, T / 2, layers=unfrozen, with_head=False)
    train = (train_fwd + head_tokens * head
             + fwd(T, T / 2, layers=unfrozen, with_head=False) + head_tokens * head
             + fwd(T, T / 2, layers=unfrozen, with_head=False))
    per_sample = gen + score + ppo_epochs * train
    return {
        "generate": n_rollouts * gen,
        "score": n_rollouts * score,
        "train": n_rollouts * ppo_epochs * train,
        "total": n_rollouts * per_sample,
    }


def flops_per_sample(model_cfg, n_prompt, n_new, ppo_epochs, unfrozen,
                     **kwargs) -> dict:
    """Per-SAMPLE itemization — `flops_per_cycle` at n_rollouts=1. The
    goodput ledger accumulates per-sample costs chunk by chunk (rollout
    chunks and train minibatches arrive at different row counts), so it
    needs the unit cost rather than the whole-cycle total."""
    return flops_per_cycle(model_cfg, n_prompt, n_new, 1, ppo_epochs,
                           unfrozen, **kwargs)
