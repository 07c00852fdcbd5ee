"""Plain reference for ByteDance's Ouro family (`model_type` `ouro`, Ouro-2.6B:
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741),
written from the published `config.json` keys (h = hidden_size; H =
num_attention_heads over num_key_value_heads K/V heads of head_dim; T =
total_ut_steps; L = num_hidden_layers; N = RMSNorm, weight * x / rms(x),
rms_norm_eps; no bias but the gate's, no QK-norm, head untied):

    x = embed_tokens[tokens]
    for t in 0..T-1:                                  the SAME L layers' weights in every pass
        for l in 0..L-1:
            q, k, v = a Wq_l, a Wk_l, a Wv_l,  a = N(x; input_layernorm)
            q, k = rope(q, k)                         rotate-half over all of head_dim, base rope_theta;
                                                      a token's position is the same in every pass
            o = softmax(q k^T / sqrt(head_dim) + causal) v;  o = o Wo_l
                                                      keys and values are THIS (pass, layer)'s own
            x = x + N(o; input_layernorm_2)           the sandwich
            f = (silu(m Wg_l) * (m Wu_l)) Wd_l,  m = N(x; post_attention_layernorm)
            x = x + N(f; post_attention_layernorm_2)
        x = N(x; model.norm)                          after EVERY pass; fed on as the next pass's input
        h_t = x;  lam_t = sigmoid(h_t . w_gate + b_gate)          early_exit_gate, h -> 1
    p_t = lam_t prod_{j<t}(1 - lam_j) for t < T-1;  p_{T-1} = prod_{j<T-1}(1 - lam_j)
    logits = h_{T-1} W_head                           early_exit_threshold 1: every pass runs

Assumed, where the catalog's `config` does not settle it (`bench/configs/
ouro-2.6b.json` `assumed` has each with its reason): the two further norms
and where they sit, the norm between passes, the gate and the exit
distribution, keys and values a (pass, layer), no bias and no QK-norm, the
plain form of the norm, rotate-half rotary.

Departures from the published computation, each shared with the program:
positions count real tokens from the left padding on. Nothing else: every
layer, every pass and the whole vocabulary are computed. A Python loop over
passes and layers; one layer is one jitted function called T x L times (a
check in set-up compiles a layer and not a stack), its leaves widened to
float32 as they are used, so that the reference fits beside a serving pool.

`departure` (the tests' and `bench/tests/ouro_onchip.py`'s: what a wrong
program would compute) is one of "pass0_kv" (every pass attends to the keys
and values pass 0 made: a cache with one plane a layer), "no_pass_norm" (no
norm between passes, only before the head) and "no_sandwich" (the two further
norms left out).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job. `engine_logprob_rms`: the root mean square over
# 2 requests x 352 sampled tokens of |engine logprob - reference logprob|
# (natural log), the engine's prefill (four passes over the prompt, K/V into
# the arena's four pools) and then 352 paged decode steps of 192 kernel calls,
# against this file's full forward over 608 positions. Set between two
# readings taken on the chip at the cell's own sizes (my chip runs, PR 57;
# PERF.md section 2 has the table): the largest the sound program gave over
# its seeds, 0.2768 (the cell's run from the committed files alone, seed
# 987654321, two finished requests; 0.2045-0.2687 on its nine other runs;
# `bench/tests/ouro_onchip.py`, the shortest and the longest prompt of a seed,
# 0.2270 and 0.2194), and the reference computed in int8 against itself,
# 0.8003 at the least (seed 2147483659; 0.8154 on seed 11): the limit is just
# under the geometric middle (0.47), 66% over the largest sound reading of
# any run and 43% under the least control. A
# program whose passes all read pass 0's keys and values reads 1.02-1.06
# (`program_pass0`; the reference with that departure 1.64-1.76), no norm
# between passes 1.64-1.66, no sandwich norms 1.52-1.56: each over twice the
# limit. The sound reading is twenty times cell 3's (0.0086 through the same
# attention at 24 layers) because the SAME stack is applied four times: on
# seeded weights a pass amplifies what the pass before rounded (a CPU run of
# a 12-layer model of width 256 in bfloat16 reads 0.014 / 0.019 / 0.047 at 1 /
# 2 / 4 passes, and the int8 reference 3.2-4.2 times that at each, as here);
# the error is flat over the output's quarters (0.19-0.25) and wide (median
# 0.15, 99th percentile 0.57): rounding, not a few tokens. `kv_bytes_rel`: as
# `gpt_neox.py` has it (the int8 arena holds half the bytes and is refused by
# this limit alone: its logprobs read 0.34; a cache with one plane a layer
# would hold a quarter).
_SERVE = {"engine_logprob_rms": 0.46, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_loop": _SERVE}

DEPARTURES = ("pass0_kv", "no_pass_norm", "no_sandwich")


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def rotary(x, positions, theta: float):
    """Rotate-half over the whole head width; the frequencies are worked out on
    the host in float64 and rounded once."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [b, t, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "eps", "sandwich", "int8"))
def layer(x, p, mask, positions, kv=None, *, heads, kv_heads, head_dim, theta, eps, sandwich=True, int8=False):
    """One layer of one pass: (its output, the keys and values it made). `kv`
    given (the `pass0_kv` departure): attend to those instead of its own."""
    b, t, _ = x.shape
    a = rms_norm(x, p["ln_attn"], eps)
    q = rotary(ops.dense(a, p["attn"]["q_proj"], int8).reshape(b, t, heads, head_dim), positions, theta)
    k = rotary(ops.dense(a, p["attn"]["k_proj"], int8).reshape(b, t, kv_heads, head_dim), positions, theta)
    v = ops.dense(a, p["attn"]["v_proj"], int8).reshape(b, t, kv_heads, head_dim)
    made = (k, v)
    k, v = made if kv is None else kv
    group = heads // kv_heads
    o = ops.causal_attention(q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), mask)
    o = ops.dense(o.reshape(b, t, heads * head_dim), p["attn"]["o_proj"], int8)
    x = x + (rms_norm(o, p["ln_post_attn"], eps) if sandwich else o)
    m = rms_norm(x, p["ln_mlp"], eps)
    gate = ops.dense(m, p["mlp"]["gate_proj"], int8)
    f = ops.dense(gate / (1.0 + jnp.exp(-gate)) * ops.dense(m, p["mlp"]["up_proj"], int8), p["mlp"]["down_proj"], int8)
    return x + (rms_norm(f, p["ln_post_mlp"], eps) if sandwich else f), made


@functools.partial(jax.jit, static_argnames=("eps",))
def pass_end(x, ln_f, gate, *, eps):
    """(the pass's output under the final norm, the exit gate's logit on it [b, t])."""
    h = rms_norm(x, ln_f, eps)
    logit = jnp.zeros(h.shape[:2], jnp.float32) if gate is None else ops.dense(h, gate)[..., 0]
    return h, logit


def passes(lm, tokens, mask, sizes, int8=False, departure=None):
    """(h_{T-1} [b, t, h] under the final norm, the gate's logits [T, b, t])."""
    if departure not in (None, *DEPARTURES):
        raise ValueError(f"unknown departure {departure!r}")
    static = dict(heads=sizes["num_attention_heads"], kv_heads=sizes["num_key_value_heads"],
                  head_dim=sizes["head_dim"], theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
                  sandwich=departure != "no_sandwich", int8=int8)
    positions = ops.positions_from_mask(mask)
    x = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    n_passes, n_layers = int(sizes["total_ut_steps"]), int(sizes["num_hidden_layers"])
    first, logits = {}, []
    for t in range(n_passes):
        for i in range(n_layers):
            x, made = layer(x, lm[f"block_{i}"], mask, positions, first.get(i), **static)
            if departure == "pass0_kv" and t == 0:
                first[i] = made
        h, logit = pass_end(x, lm["ln_f"], lm.get("exit_gate"), eps=static["eps"])
        logits.append(logit)
        if departure != "no_pass_norm" or t == n_passes - 1:
            x = h
    return x, jnp.stack(logits)


def exit_pdf(lm, tokens, mask, sizes):
    """[b, t, T] float32: the share of a position that leaves after each pass."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        lam = jax.nn.sigmoid(passes(lm, tokens, mask, sizes)[1])  # [T, b, t]
    shares, stays = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        shares.append(lam[t] * stays)
        stays = stays * (1.0 - lam[t])
    return jnp.stack(shares + [stays], axis=-1)


@functools.partial(jax.jit, static_argnames=("int8",))
def head_logprobs(h, lm_head, tokens, *, int8=False):
    return ops.logprobs_of_next(ops.dense(h, lm_head, int8), tokens)


def logprobs(lm, tokens, mask, sizes, int8=False, departure=None):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]). `int8`
    computes every dense product in int8 (the control, never the reference)."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        h, _ = passes(lm, tokens, mask, sizes, int8, departure)
        return head_logprobs(h, lm["lm_head"], tokens, int8=int8)


def logits(lm, tokens, mask, sizes, departure=None):
    """[b, t, vocabulary] logits, for the tests: at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        return ops.dense(passes(lm, tokens, mask, sizes, departure=departure)[0], lm["lm_head"])
