"""The plain operations the references share: float32, `highest` matmul
precision, no kernels, no cache. Weights arrive in the type the
configuration holds them in and are widened to float32 where they are used,
one layer at a time, so that a reference fits beside a serving arena."""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def fake_int8(x, axis):
    """x rounded to 255 levels of its largest magnitude along `axis`: what a
    symmetric int8 tensor holds, in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def dense(x, p, int8=False):
    """x @ kernel + bias, for a flax Dense leaf pair. `int8` is the control
    of `correct`: the same product with both operands held in int8 (weights
    by output channel, activations by row), the step below bfloat16."""
    w = f32(p["kernel"])
    if int8:
        x, w = fake_int8(x, -1), fake_int8(w, 0)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y + f32(p["bias"]) if "bias" in p else y


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])


def gelu_exact(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def positions_from_mask(mask):
    """Position of each real token among the real tokens of its row (left
    padding shifts nothing): cumsum(mask) - 1, floored at 0."""
    return jnp.clip(jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1, 0, None)


def causal_attention(q, k, v, mask):
    """softmax(q k^T / sqrt(d) + causal and key-padding mask) v.
    q, k, v: [b, t, heads, d]; mask: [b, t], 1 for a real token."""
    b, t, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(float(d))
    allowed = jnp.tril(jnp.ones((t, t), bool))[None, None] & mask[:, None, None, :].astype(bool)
    scores = jnp.where(allowed, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)


def logprobs_of_next(logits, tokens):
    """log softmax(logits[:, i]) at tokens[:, i + 1]: [b, t - 1]."""
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None].astype(jnp.int32), axis=-1)[..., 0]
