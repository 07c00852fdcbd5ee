"""Plain reference for GPT-2 (openai-community/gpt2-*), after Radford et al.
2019 and the HF `GPT2LMHeadModel` config keys:

    h_0 = wte[tokens] + wpe[positions]
    a   = h_l + attn(ln_1(h_l));  h_{l+1} = a + mlp(ln_2(a))
    attn: q, k, v = dense(x); causal softmax attention; dense
    mlp:  c_proj(gelu_new(c_fc(x))), the tanh approximation
    logits = ln_f(h_L) @ wte^T, tied

Departures: q, k and v are three matrices here and one fused `c_attn` in the
published checkpoint; positions count real tokens from the left padding on.
Weights come in the flax layout of the tree the benchmark makes from the
seed; `sizes` are the published config's own keys.
"""

import functools

import jax
import jax.numpy as jnp

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct` (see the note in gpt_neox.py and PERF.md section 2), from
# readings on the chip at gpt2-xl's full size (my chip runs, PR 24): 48 bf16
# layers put the sound program further from float32 than pythia's 24.
#   scorer   sound <= 0.01329 over 16 seeds (206 tokens each);  reference-in-int8 >= 0.02713
#   sampler  sound <= 0.01300 over 16 seeds (128 tokens each);  program's int8 trunk >= 0.02469
# No serve cell has run at this size: its limits come with such a cell.
LIMITS = {
    "ppo": {"scorer_logprob_rms": 0.019, "sampler_logprob_rms": 0.018},
}


@functools.partial(jax.jit, static_argnames=("heads", "eps", "int8"))
def layer(h, p, mask, *, heads, eps, int8=False):
    b, t, d = h.shape
    x = ops.layer_norm(h, p["ln_attn"], eps)
    q, k, v = (ops.dense(x, p["attn"][n], int8).reshape(b, t, heads, d // heads)
               for n in ("q_proj", "k_proj", "v_proj"))
    h = h + ops.dense(ops.causal_attention(q, k, v, mask).reshape(b, t, d), p["attn"]["o_proj"], int8)
    y = ops.layer_norm(h, p["ln_mlp"], eps)
    return h + ops.dense(ops.gelu_tanh(ops.dense(y, p["mlp"]["up_proj"], int8)), p["mlp"]["down_proj"], int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logprobs(h, ln_f, wte, tokens, *, eps, int8=False):
    logits = ops.dense(ops.layer_norm(h, ln_f, eps), {"kernel": ops.f32(wte).T}, int8)
    return ops.logprobs_of_next(logits, tokens)


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]). `int8`
    computes every dense product in int8: the control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    eps = float(sizes["layer_norm_epsilon"])
    positions = ops.positions_from_mask(mask)
    wte = lm["embed_tokens"]["embedding"]
    h = ops.f32(jnp.asarray(wte)[tokens]) + ops.f32(jnp.asarray(lm["embed_pos"]["embedding"])[positions])
    for i in range(sizes["n_layer"]):
        h = layer(h, lm[f"block_{i}"], mask, heads=sizes["n_head"], eps=eps, int8=int8)
    return head_logprobs(h, lm["ln_f"], wte, tokens, eps=eps, int8=int8)
