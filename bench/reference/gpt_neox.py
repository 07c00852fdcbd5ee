"""Plain reference for the GPT-NeoX family (EleutherAI/pythia-*), after the
published architecture (Black et al. 2022, "GPT-NeoX-20B", and the HF
`GPTNeoXForCausalLM` config keys):

    h_0 = embed_in[tokens]
    h_{l+1} = h_l + attn(LN1(h_l)) + mlp(LN2(h_l))        (use_parallel_residual)
    attn: q, k, v = dense(x); rotary on the first rotary_pct * head_dim
          dims of q and k (rotate_half convention, base rotary_emb_base);
          causal softmax attention; dense
    mlp:  dense_4h_to_h(gelu(dense_h_to_4h(x))), exact gelu
    logits = embed_out(final_layer_norm(h_L)), untied, no bias

Departures: q, k and v are three matrices here and one fused `query_key_value`
in the published checkpoint (the same linear map); positions count real
tokens from the left padding on (what HF generate passes as `position_ids`).
Weights come in the flax layout of the tree the benchmark makes from the
seed; `sizes` are the published config's own keys.
"""

import functools

import jax
import jax.numpy as jnp

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job: the root mean square over some hundreds of
# tokens of |program logprob - reference logprob| (natural log). Each sits at
# the geometric middle of two readings taken on the chip at pythia-1.4b's
# full size (PERF.md section 2 has the table; my chip runs, PR 24):
#   scorer   sound <= 0.01051 over 33 seeds;  reference-in-int8 control >= 0.03112
#   sampler  sound <= 0.01059 over 19 seeds;  program's int8 trunk >= 0.02625
#   engine   sound <= 0.00860 over 24 seeds;  reference-in-int8 control >= 0.03019
# `kv_bytes_rel`: the bytes of the engine's pool against the stated cache type,
# sound 2.1e-5 (mask and tables), int8 cache 0.484.
LIMITS = {
    "ppo": {"scorer_logprob_rms": 0.018, "sampler_logprob_rms": 0.017},
    "serve": {"engine_logprob_rms": 0.016, "kv_bytes_rel": 0.02},
}


def rotary(x, positions, rotary_dim, base):
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [b, t, rd/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :]
    x1, x2 = rot[..., : rotary_dim // 2], rot[..., rotary_dim // 2:]
    rotated = rot * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([rotated, rest], -1)


@functools.partial(jax.jit, static_argnames=("heads", "rotary_dim", "base", "eps", "int8"))
def layer(h, p, mask, positions, *, heads, rotary_dim, base, eps, int8=False):
    b, t, d = h.shape
    x = ops.layer_norm(h, p["ln_attn"], eps)
    q, k, v = (ops.dense(x, p["attn"][n], int8).reshape(b, t, heads, d // heads)
               for n in ("q_proj", "k_proj", "v_proj"))
    q, k = rotary(q, positions, rotary_dim, base), rotary(k, positions, rotary_dim, base)
    attn = ops.dense(ops.causal_attention(q, k, v, mask).reshape(b, t, d), p["attn"]["o_proj"], int8)
    y = ops.layer_norm(h, p["ln_mlp"], eps)
    mlp = ops.dense(ops.gelu_exact(ops.dense(y, p["mlp"]["up_proj"], int8)), p["mlp"]["down_proj"], int8)
    return h + attn + mlp


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logprobs(h, ln_f, lm_head, tokens, *, eps, int8=False):
    logits = ops.dense(ops.layer_norm(h, ln_f, eps), lm_head, int8)
    return ops.logprobs_of_next(logits, tokens)


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]). `int8`
    computes every dense product in int8: the control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    heads = sizes["num_attention_heads"]
    head_dim = sizes["hidden_size"] // heads
    static = dict(heads=heads, rotary_dim=int(head_dim * sizes["rotary_pct"]),
                  base=float(sizes["rotary_emb_base"]), eps=float(sizes["layer_norm_eps"]), int8=int8)
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i in range(sizes["num_hidden_layers"]):
        h = layer(h, lm[f"block_{i}"], mask, positions, **static)
    return head_logprobs(h, lm["ln_f"], lm["lm_head"], tokens, eps=static["eps"], int8=int8)
