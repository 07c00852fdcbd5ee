"""Plain reference for PowerInfer's SmallThinker family (`model_type`
`smallthinker`, SmallThinker-21BA3B-Instruct), written from the published
`config.json` keys (h = hidden_size; H = num_attention_heads query heads over
num_key_value_heads K/V heads of head_dim, so a query width H x head_dim that
is not h; N = RMSNorm, rms_norm_eps; no bias anywhere, no QK-norm, head untied):

    x_0 = embed_tokens[tokens]
    for every block l with input x:
        r   = x W_r                     W_r [h, moe_num_primary_experts], float32: the
                                        router reads the block's INPUT, before N_1 and
                                        before the attention
        sel = top_k(r), k = moe_num_active_primary_experts;  w = softmax(r[sel])
                                        (`moe_primary_router_apply_softmax`,
                                        `norm_topk_prob`: the softmax over the chosen is
                                        the full softmax renormalised over the chosen)
        a   = x + Attn_l(N_1(x))
        y   = a + sum_{e in sel} w_e W_down,e(relu(W_gate,e n) * W_up,e n),  n = N_2(a)
    logits = lm_head(N(x_L))

    Attn_l: q, k, v = n Wq, n Wk, n Wv; query head j reads K/V head j // (H / kv
        heads); scores q k^T / sqrt(head_dim), causal.
        `sliding_window_layout[l]` 0: every earlier position; 1: key j is visible
        to query i iff 0 <= i - j < sliding_window_size (the query's own position
        counted). `rope_layout[l]` 0: q and k are NOT rotated (NoPE); 1: rotary
        over all of head_dim, base rope_theta, rotate-half, no scaling.

Assumed, where the catalog's `config` does not settle it (`bench/configs/
smallthinker-21b-a3b.json` `assumed`): (a) the router reads the RAW residual
stream, not a normed copy of it ("router placed before attention" is all the
catalog says); (b) rotate-half rotary; (c) "sparse ReGLU" is relu(gate) * up,
nothing thresholded or predicted at run time; (d) the catalog's "secondary
experts" have no key in the published config and are not run.

Departures, each shared with the program: (1) positions count real tokens
from the left padding on. (2) the expert stacks lie side by side (`[fan_in,
experts held x fan_out]`), experts `expert_offset` (0) onward; the router
keeps its published width (read from its kernel) and the experts held are
read from the stacks, so the same code computes one chip's share and the
uncut layer: the sum runs over the selected experts held here. The cell
holds all 64. Attention is computed one K/V head's query heads and
`QUERY_BLOCK` queries at a time, so that `[heads, t, t]` float32 scores never
exist at once, and the head `VOCAB_BLOCK` rows of the vocabulary at a time.

`departure` (the tests' and `bench/tests/smallthinker_onchip.py`'s: what a
wrong program would compute) is one of "router_on_ffn_input" (the router
reads n, the feed-forward's normed input), "silu" (SiLU for ReLU) and
"rope_on_full" (the full layers rotate too).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job. `engine_logprob_rms`: the root mean square over
# 2 requests x 1,024 sampled tokens of |engine logprob - reference logprob|
# (natural log), the engine's prefill (flash forward, banded on the window
# layers; the experts a block of 4,096 positions at a time) and then 1,024 paged
# decode steps, against this file's full forward over 15,360 positions. Set
# between two readings taken on the chip at the cell's own sizes (my chip runs,
# PR 55; PERF.md section 2 has the table): the largest the sound program gave
# over its seeds, 0.1122 (the cell's own run of seed 3123456789, two finished
# requests; 0.0871-0.1102 on its fourteen other runs; `bench/tests/
# smallthinker_onchip.py`, the shortest and the longest prompt of a seed, 0.1030
# and 0.1074), and the reference computed in int8 against itself, 0.2007 at the
# least (seed 11; 0.2138 on seed 2147483659): the limit is the geometric middle
# of the tool's two readings, 31% over the largest sound reading of any run and
# 27% under the least control.
# The median token is 0.012 from the reference (bfloat16 through 8 layers) and
# the 99th percentile 0.46: a few tokens whose 6 of 64 experts differ on a
# near-tie, as in every expert cell. `kv_bytes_rel`: as `gpt_neox.py` has it
# (the int8 arena holds half the bytes).
LIMITS = {
    "serve": {"engine_logprob_rms": 0.147, "kv_bytes_rel": 0.02},
}

DEPARTURES = ("router_on_ffn_input", "silu", "rope_on_full")
QUERY_BLOCK = 512  # queries scored at once: [7, 512, 15360] float32 scores are 0.22 GB
VOCAB_BLOCK = 18992  # rows of the vocabulary unembedded at once: 151,936 / 8
HEAD_POSITIONS = 2048  # positions unembedded at once: [2048, 18992] float32 logits are 0.16 GB


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def rotary(x, positions, theta: float):
    """Rotate-half over the whole head width. The frequencies are worked out on
    the host in float64 and rounded once (a float32 power on the chip is off
    by ~1e-6 relative: 6e-3 rad at position 15,000 on the fast dimensions)."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [b, t, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def banded_attention(q, k, v, mask, window):
    """softmax(q k^T / sqrt(d) + causal, key-padding and window mask) v, one
    K/V head's group of query heads and `QUERY_BLOCK` queries at a time (two
    nested `lax.map`s: the blocks' scores exist one after another). q: [b, t,
    H, d]; k, v: [b, t, kv, d]; `window` None or the keys a query sees, its own
    among them."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    keys = jnp.arange(t)
    key_ok = mask.astype(bool)[:, None, None, :]  # [b, 1, 1, t]

    def one_kv_head(args):
        qg, kh, vh = args  # [b, t + pad, group, d], [b, t, d], [b, t, d]

        def one_block(blk):
            qb, first = blk  # [b, block, group, d], scalar
            i = first + jnp.arange(block)
            allowed = keys[None, :] <= i[:, None]
            if window is not None:
                allowed = allowed & (i[:, None] - keys[None, :] < window)
            scores = jnp.einsum("bqgd,bkd->bgqk", qb, kh, precision=ops.HIGHEST) / jnp.sqrt(float(d))
            probs = jax.nn.softmax(jnp.where(allowed[None, None] & key_ok, scores, -1e30), axis=-1)
            return jnp.einsum("bgqk,bkd->bqgd", probs, vh, precision=ops.HIGHEST)

        blocks = jnp.moveaxis(qg.reshape(b, -1, block, heads // kv, d), 1, 0)
        out = jax.lax.map(one_block, (blocks, jnp.arange(blocks.shape[0]) * block))  # [n, b, block, group, d]
        return jnp.moveaxis(out, 0, 1).reshape(b, -1, heads // kv, d)

    heads_first = lambda a: jnp.moveaxis(a, 2, 0)
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, t + pad, kv, heads // kv, d)
    out = jax.lax.map(one_kv_head, (heads_first(qg), heads_first(k), heads_first(v)))  # [kv, b, t + pad, group, d]
    return jnp.moveaxis(out, 0, 2).reshape(b, t + pad, heads, d)[:, :t]


def attention_op(x, p, mask, positions, *, heads, kv_heads, head_dim, theta, window, int8):
    b, t, _ = x.shape
    q = ops.dense(x, p["q_proj"], int8).reshape(b, t, heads, head_dim)
    k = ops.dense(x, p["k_proj"], int8).reshape(b, t, kv_heads, head_dim)
    v = ops.dense(x, p["v_proj"], int8).reshape(b, t, kv_heads, head_dim)
    if theta is not None:
        q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    a = banded_attention(q, k, v, mask, window)
    return ops.dense(a.reshape(b, t, heads * head_dim), p["o_proj"], int8)


def routing(x, p, top_k):
    """(sel [b, t, k], w [b, t, k]): the k largest logits and a softmax over them."""
    logits = jnp.matmul(x, ops.f32(p["router"]["kernel"]), precision=ops.HIGHEST)
    top, sel = jax.lax.top_k(logits, top_k)
    return sel, jax.nn.softmax(top, axis=-1)


def expert_ffn(n, sel, w, p, *, offset, act, int8):
    """The experts held, a plain loop with a mask: every expert computes every
    token, a token keeps what its selected experts gave. The experts held are
    the stacks'."""
    d = n.shape[-1]
    held = p["expert_down"]["kernel"].shape[1] // d
    width = p["expert_down"]["kernel"].shape[0]
    dense = lambda a, kernel: ops.dense(a, {"kernel": kernel}, int8)

    def one(g, y):
        block = lambda name, size: ops.f32(jax.lax.dynamic_slice_in_dim(p[name]["kernel"], g * size, size, axis=1))
        mine = jnp.where(sel == offset + g, w, 0.0).sum(-1)  # [b, t]
        gated = act(dense(n, block("expert_gate", width))) * dense(n, block("expert_up", width))
        return y + mine[..., None] * dense(gated, block("expert_down", d))

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(n))


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "window", "eps", "top_k",
                                             "offset", "silu", "route_on_ffn", "int8"))
def layer(x, p, mask, positions, *, heads, kv_heads, head_dim, theta, window, eps, top_k, offset=0, silu=False,
          route_on_ffn=False, int8=False):
    sel, w = routing(x, p["mlp"], top_k)  # from the block's input, before the norm and the attention
    a = x + attention_op(rms_norm(x, p["ln_attn"], eps), p["attn"], mask, positions, heads=heads,
                         kv_heads=kv_heads, head_dim=head_dim, theta=theta, window=window, int8=int8)
    n = rms_norm(a, p["ln_mlp"], eps)
    if route_on_ffn:  # the departure
        sel, w = routing(n, p["mlp"], top_k)
    act = (lambda z: z / (1.0 + jnp.exp(-z))) if silu else (lambda z: jnp.maximum(z, 0.0))
    return a + expert_ffn(n, sel, w, p["mlp"], offset=offset, act=act, int8=int8)


def layer_sizes(sizes, i: int, departure=None) -> dict:
    """The static sizes of block i from the published keys."""
    if departure not in (None, *DEPARTURES):
        raise ValueError(f"unknown departure {departure!r}")
    rotates = sizes["rope_layout"][i] or departure == "rope_on_full"
    return dict(
        heads=sizes["num_attention_heads"], kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        theta=float(sizes["rope_theta"]) if rotates else None,
        window=sizes["sliding_window_size"] if sizes["sliding_window_layout"][i] else None,
        eps=float(sizes["rms_norm_eps"]), top_k=sizes["moe_num_active_primary_experts"],
        offset=int(sizes.get("expert_offset", 0)), silu=departure == "silu",
        route_on_ffn=departure == "router_on_ffn_input")


def trunk(lm, tokens, mask, sizes, int8=False, departure=None):
    """The state under the final norm: tokens, mask [b, t] -> [b, t, h]."""
    positions = ops.positions_from_mask(mask)
    x = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i in range(sizes["num_hidden_layers"]):
        x = layer(x, lm[f"block_{i}"], mask, positions, int8=int8, **layer_sizes(sizes, i, departure))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logprobs(x, ln_f, lm_head, tokens, *, eps, int8=False):
    """log softmax(lm_head(N(x[:, i]))) at tokens[:, i + 1], `VOCAB_BLOCK` rows
    of the vocabulary and `HEAD_POSITIONS` positions at a time (the float32 head
    whole is 1.6 GB, the logits of 15,360 positions 9.3 GB): the log-sum-exp is
    carried over the vocabulary's blocks and the next token's logit picked from
    the block that holds it."""
    b, t, d = x.shape
    pad = -(t - 1) % HEAD_POSITIONS
    n = jnp.pad(rms_norm(x, ln_f, eps)[:, :-1], ((0, 0), (0, pad), (0, 0)))
    nxt = jnp.pad(tokens[:, 1:].astype(jnp.int32), ((0, 0), (0, pad)))
    split = lambda a: jnp.moveaxis(a.reshape(b, -1, HEAD_POSITIONS, *a.shape[2:]), 1, 0)  # [chunks, b, n, ...]
    vocab = lm_head["kernel"].shape[1]
    block = VOCAB_BLOCK if vocab % VOCAB_BLOCK == 0 else vocab

    def one(j, carry):
        lse, picked = carry  # [chunks, b, n] each
        w = ops.f32(jax.lax.dynamic_slice_in_dim(lm_head["kernel"], j * block, block, axis=1))

        def chunk(args):
            nc, tc = args  # [b, n, d], [b, n]
            logits = ops.dense(nc, {"kernel": w}, int8)  # [b, n, block]
            local = tc - j * block
            at = jnp.take_along_axis(logits, jnp.clip(local, 0, block - 1)[..., None], axis=-1)[..., 0]
            return jax.nn.logsumexp(logits, axis=-1), at, (local >= 0) & (local < block)

        here_lse, at, here = jax.lax.map(chunk, (split(n), split(nxt)))
        return jnp.logaddexp(lse, here_lse), jnp.where(here, at, picked)

    shape = (n.shape[1] // HEAD_POSITIONS, b, HEAD_POSITIONS)
    lse, picked = jax.lax.fori_loop(0, vocab // block, one, (jnp.full(shape, -jnp.inf), jnp.zeros(shape)))
    return jnp.moveaxis(picked - lse, 0, 1).reshape(b, -1)[:, : t - 1]


def logprobs(lm, tokens, mask, sizes, int8=False, departure=None):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]). `int8`
    computes every dense and expert product in int8 (the router stays in
    float32, as the configuration states): the control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        x = trunk(lm, tokens, mask, sizes, int8, departure)
        return head_logprobs(x, lm["ln_f"], lm["lm_head"], tokens, eps=float(sizes["rms_norm_eps"]), int8=int8)


def logits(lm, tokens, mask, sizes, departure=None):
    """[b, t, vocabulary] logits, for the tests: at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        x = trunk(lm, tokens, mask, sizes, departure=departure)
        return ops.dense(rms_norm(x, lm["ln_f"], float(sizes["rms_norm_eps"])), lm["lm_head"])
