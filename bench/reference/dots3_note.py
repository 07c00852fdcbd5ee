"""Plain reference for dots3-note-prev's language model (`model_type`
`dots3_note`, dots-studio, 288B-A17B), written from the published
`config.json` keys. With N an RMSNorm (`rms_norm_eps`), d `hidden_size`, no
bias on a dense product, pre-norm blocks:

    h_0 = embed_tokens[tokens]
    a = h + Attn(N_in(h));  h <- a + FFN(N_mlp(a));  logits = lm_head(N_f(h_L))    (untied)

Inside Attn, x is its normed input. Two attention layers, by `layer_types`:

    FULL ("full_attention": H `num_attention_heads`, rq `q_lora_rank`, dc `kv_lora_rank`, dn
        `qk_nope_head_dim`, dr `qk_rope_head_dim`, dv `v_head_dim`, theta `rope_theta`)
    SLIDING ("sliding_attention": the same keys with `swa_` in front, window `sliding_window_size`)

    c_q  = N(x W_qa) * sqrt(d / rq)                     [q_nope_h ; q_rope_h] = c_q W_qb     heads of dn + dr
    [c_kv ; k_r] = x W_kva       c = N(c_kv) * sqrt(d / dc)                                   dc + dr
    [k_nope_h ; v_h] = c W_kvb                                                                dn + dv a head
    q_rope_h, k_r rotated at the token's position (rotate-half, no scaling); k_r ONE vector for all heads
    s_h,t,j = (q_nope_h,t . k_nope_h,j + q_rope_h,t . k_r,j) / sqrt(dn + dr)
    p_h,t = softmax over j in A_t;  o_h,t = sum_j p_h,t,j v_h,j
    y_t = [sigmoid(x_t W_g)_h * o_h,t]_h W_o                                                  W_g [d, H]

    A_t (SLIDING) = { j <= t, t - j < window }
    A_t (FULL) = the `index_topk` positions j <= t with the largest I_t,j (all of them while there
        are no more), ties toward the later position:
        qI_t,g = c_q,t W_iq            `index_n_heads` heads g of D = `index_head_dim`
        kI_j   = LayerNorm(x_j W_ik)   ONE key of D a token (with a bias; eps 1e-6)
        the first D / 2 of the D rotated at the position (theta), on qI and kI alike
        w_t,g  = (x_t W_w)_g * index_n_heads^-1/2 * D^-1/2
        I_t,j  = sum_g w_t,g * relu(qI_t,g . kI_j)

FFN, layer < `first_k_dense_replace`: SwiGLU of `intermediate_size`; the other
layers `openpangu`'s expert layer as `pangu_ultra_moe.py` writes it (sigmoid
scores in float32, the `num_experts_per_tok` largest of score + selection
bias without groups, weights normalised, times `routed_scaling_factor`, one
shared expert added whole), whose functions this file calls.

The attention is computed the way it is published: per-head keys and values
decompressed from the latent, dense softmax under a mask of A_t, no cache
and no kernels. The program's cached steps run the ABSORBED form over the
cached latents and read the chosen ones only: one form against the other.

Assumed (each in `bench/configs/dots3-note-prev.json` under `assumed`): the
rescale as sqrt(hidden_size / rank) on each normed latent; the index after
DeepSeek-V3.2's released code without its Hadamard rotation and FP8 cast;
the index fed from the rescaled c_q; ties toward the later position; the
window counting the token itself; the sliding layers' gate the full layers'
form; the towers and the multi-token block not run.

Departures, each shared with the program: those of `pangu_ultra_moe.py` (the
experts held are ONE chip's of an expert-parallel deployment, read from the
stacks; positions count real tokens; `expert_bias` is a seeded leaf).
Memory: one layer is jitted at a time; the mask of A_t exists whole as
booleans ([t, t]: 630 MB at 25,088), the index's scores `QUERY_BLOCK` queries
and one index head at a time, the attention's scores one head and
`QUERY_BLOCK` queries at a time: 25,088 positions fit beside a serving arena.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")
plain = load_module("reference/pangu_ultra_moe.py")  # the norm, the rotary, the feed-forwards, the head
rms_norm, rotary, dense = plain.rms_norm, plain.rotary, plain.dense

# Limits of `correct`, by job (`serve_sparse_latent` is `serve` with another
# count of the cache's bytes and the leaves' two norm scales set as a trained
# checkpoint's: bench/jobs/serve_sparse_latent.py). `engine_logprob_rms`: the
# root mean square over the sampled tokens of 2 finished requests of |engine
# logprob - reference logprob| (natural log): the engine's prefill by query
# blocks and then 512 paged decode steps (the index's scores, the choice, the
# chosen latents; the banded latent kernel) against this file's full forward
# over 25,088 positions. Set from readings taken on the chip at the cell's own
# sizes (`bench/tests/dots3_onchip.py`, 2 prompts of 4,842 and 24,576 to 512
# tokens, and the cell's own runs; PERF.md section 2 has the table; my chip
# runs, PR 51):
#   sound <= 0.1502 a request (the longest prompt; 0.1165-0.1207 the shortest)
#   reference-in-int8 control >= 0.2089 (seed 3000000019; 0.2213 on seed 11)
# The limit is their geometric middle, 0.177, rounded down. Most of the sound
# reading is experts chosen otherwise on a near-tie of the router's scores, as
# in `laguna.py` (32 of 256 held here). Tried against it on the chip: the index
# left out 1.21-1.28, 1,024 positions kept for 2,048 1.10-1.14, the rescale left
# out 1.51 fail it; a window of 512 for 513, 0.131-0.137, it cannot refuse (the
# tests at tiny size do).
# `kv_bytes_rel`: as `gpt_neox.py` has it, against the planes
# `bench/jobs/serve_sparse_latent.py` counts.
_SERVE = {"engine_logprob_rms": 0.175, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_sparse_latent": _SERVE}

QUERY_BLOCK = 512  # queries whose scores exist at once


def _block(t: int) -> int:
    """The largest divisor of t up to `QUERY_BLOCK`."""
    return next(n for n in range(min(QUERY_BLOCK, t), 0, -1) if t % n == 0)


def rotate_first_half(x, positions, theta):
    """The first half of the last dimension rotated (rotate-half within it)."""
    half = x.shape[-1] // 2
    return jnp.concatenate([rotary(x[..., :half], positions, theta), x[..., half:]], -1)


def chosen(x, c_q, p, attendable, positions, *, heads, topk, theta, use_relu=True):
    """[t, t] bool: A_t of a FULL layer as a mask. The index's scores a block
    of queries and ONE index head at a time, `jax.lax.top_k` over the row
    read from its far end (so that equal scores keep the later position), the
    chosen columns scattered into the mask."""
    t = x.shape[0]
    n = _block(t)
    if t <= topk:
        return attendable
    w_q = ops.f32(p["wq_b"]["kernel"])
    dim = w_q.shape[1] // heads
    k_i = rotate_first_half(ops.layer_norm(dense(x, ops.f32(p["wk"]["kernel"]), False), p["k_norm"], 1e-6),
                            positions, theta)  # [t, D]
    w_all = dense(x, ops.f32(p["weights_proj"]["kernel"]), False) * (heads ** -0.5 * dim ** -0.5)  # [t, G]

    def block(args):
        cq, pos, w, allowed = args
        q_i = jnp.moveaxis(dense(cq, w_q, False).reshape(n, heads, dim), 1, 0)  # [G, n, D]
        q_i = rotate_first_half(q_i, pos, theta)

        def one_head(acc, qw):
            q_g, w_g = qw
            s = jnp.matmul(q_g, k_i.T, precision=ops.HIGHEST)
            return acc + w_g[:, None] * (jnp.maximum(s, 0.0) if use_relu else s), None

        scores, _ = jax.lax.scan(one_head, jnp.zeros((n, t), jnp.float32), (q_i, w.T))
        scores = jnp.where(allowed, scores, -jnp.inf)
        _, at = jax.lax.top_k(scores[:, ::-1], topk)
        mask = jnp.zeros((n, t), bool).at[jnp.arange(n)[:, None], t - 1 - at].set(True)
        return mask & allowed

    blocks = lambda a: a.reshape(t // n, n, *a.shape[1:])
    return jax.lax.map(block, (blocks(c_q), blocks(positions), blocks(w_all), blocks(attendable))).reshape(t, t)


def latent_attention(x, p, mask, positions, *, heads, q_rank, nope, rope, v_dim, theta, eps, rescale, window,
                     index_heads, index_topk, int8, use_relu=True):
    """One row: x [t, d], mask and positions [t]. Per-head keys and values, a
    head at a time (a scan that adds each head's gated part of the output
    projection), a head's scores `QUERY_BLOCK` queries at a time."""
    t, d = x.shape
    n = _block(t)
    lora = p["kv_b_proj"]["kernel"].shape[0]
    up = lambda rank: float(np.sqrt(d / rank)) if rescale else 1.0
    c_q = rms_norm(dense(x, ops.f32(p["q_a_proj"]["kernel"]), int8), p["q_a_norm"], eps) * up(q_rank)
    kv_a = dense(x, ops.f32(p["kv_a_proj"]["kernel"]), int8)
    c = rms_norm(kv_a[:, :lora], p["kv_a_norm"], eps) * up(lora)
    k_r = rotary(kv_a[:, lora:], positions, theta)  # [t, rope]: one for all heads
    gate = jax.nn.sigmoid(dense(x, ops.f32(p["gate_proj"]["kernel"]), int8))  # [t, heads]
    i = jnp.arange(t)
    allowed = (i[None, :] <= i[:, None]) & mask[None, :].astype(bool)
    if window is not None:
        allowed &= (i[:, None] - i[None, :]) < window
    if index_topk:
        allowed = chosen(x, c_q, p["indexer"], allowed, positions, heads=index_heads, topk=index_topk, theta=theta,
                         use_relu=use_relu)
    by_head = lambda name, width: jnp.moveaxis(p[name]["kernel"].reshape(-1, heads, width), 1, 0)
    w_o = p["o_proj"]["kernel"].reshape(heads, v_dim, -1)

    def one_head(y, w):
        w_qb, w_kvb, w_oh, g = w
        q = dense(c_q, ops.f32(w_qb), int8)  # [t, nope + rope]
        kv = dense(c, ops.f32(w_kvb), int8)  # [t, nope + v]
        q = jnp.concatenate([q[:, :nope], rotary(q[:, nope:], positions, theta)], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)

        def block(args):
            q_b, allowed_b = args
            scores = jnp.matmul(q_b, k.T, precision=ops.HIGHEST) / jnp.sqrt(float(nope + rope))
            probs = jax.nn.softmax(jnp.where(allowed_b, scores, -1e30), axis=-1)
            return jnp.matmul(probs, kv[:, nope:], precision=ops.HIGHEST)

        o = jax.lax.map(block, (q.reshape(t // n, n, -1), allowed.reshape(t // n, n, t))).reshape(t, v_dim)
        return y + dense(o * g[:, None], ops.f32(w_oh), int8), None

    y, _ = jax.lax.scan(one_head, jnp.zeros_like(x), (by_head("q_b_proj", nope + rope),
                                                      by_head("kv_b_proj", nope + v_dim), w_o, gate.T))
    return y


def dense_ffn(x, p, int8):
    """SwiGLU, a slice of its width at a time (`pangu_ultra_moe.dense_ffn`
    with a slice that divides this family's 13,824: 1,536)."""
    width = p["down_proj"]["kernel"].shape[0]
    n = next(n for n in range(min(plain.FFN_SLICE, width), 0, -1) if width % n == 0)

    def one(j, y):
        cols = lambda name: ops.f32(jax.lax.dynamic_slice_in_dim(p[name]["kernel"], j * n, n, axis=1))
        rows = ops.f32(jax.lax.dynamic_slice_in_dim(p["down_proj"]["kernel"], j * n, n, axis=0))
        return y + plain.glu(x, cols("gate_proj"), cols("up_proj"), rows, int8)

    return jax.lax.fori_loop(0, width // n, one, jnp.zeros_like(x))


_STATIC = ("is_dense", "heads", "q_rank", "nope", "rope", "v_dim", "theta", "eps", "rescale", "window",
           "index_heads", "index_topk", "top_k", "offset", "scaling", "int8", "use_relu")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(h, p, mask, positions, *, is_dense, eps, top_k, offset, scaling, int8=False, **attention):
    """One block over one row: h [t, d]."""
    h = h + latent_attention(rms_norm(h, p["ln_attn"], eps), p["attn"], mask, positions, eps=eps, int8=int8,
                             **attention)
    x = rms_norm(h, p["ln_mlp"], eps)
    if is_dense:
        return h + dense_ffn(x, p["mlp"], int8)
    return h + plain.expert_ffn(x, p["mlp"], top_k=top_k, offset=offset, scaling=scaling, int8=int8)


def attention_sizes(sizes, kind: str, departure=None) -> dict:
    """The static sizes of a layer of `kind` ("full_attention" |
    "sliding_attention") from the published keys. `departure` (the tests' and
    `bench/tests/dots3_onchip.py`'s: what a wrong program would compute) is
    one of "no_index", "topk_less" (one fewer; half on the chip), "window_less"
    (one shorter), "no_relu", "no_rescale", "swa_theta_as_full" (the sliding
    layers rotated at the full layers' base)."""
    swa = "swa_" if kind == "sliding_attention" else ""
    topk = int(sizes["index_topk"])
    window = int(sizes["sliding_window_size"])
    if departure == "topk_less":
        topk = topk - 1 if topk < 64 else topk // 2
    if departure == "window_less":
        window -= 1
    full = kind == "full_attention"
    return dict(
        heads=sizes[swa + "num_attention_heads"], q_rank=sizes[swa + "q_lora_rank"],
        nope=sizes[swa + "qk_nope_head_dim"], rope=sizes[swa + "qk_rope_head_dim"], v_dim=sizes[swa + "v_head_dim"],
        theta=float(sizes[("" if departure == "swa_theta_as_full" else swa) + "rope_theta"]), rescale=bool(sizes["apply_mla_qkv_lora_rescale"])
        and departure != "no_rescale",
        window=None if full else window, index_heads=sizes["index_n_heads"] if full else 0,
        index_topk=topk if full and departure != "no_index" else 0, use_relu=departure != "no_relu")


def trunk(lm, tokens, mask, sizes, int8=False, departure=None):
    """The state under the final norm, one row: tokens, mask [t] -> [t, d]."""
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i, kind in enumerate(sizes["layer_types"]):
        h = layer(h, lm[f"block_{i}"], mask, positions, is_dense=i < sizes["first_k_dense_replace"],
                  eps=float(sizes["rms_norm_eps"]), top_k=sizes["num_experts_per_tok"],
                  offset=int(sizes.get("expert_offset", 0)), scaling=float(sizes["routed_scaling_factor"]),
                  int8=int8, **attention_sizes(sizes, kind, departure))
    return h


def logprobs(lm, tokens, mask, sizes, int8=False, departure=None):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]), a row
    at a time. `int8` computes every dense and expert product in int8 (the
    router and the index stay in float32, as the configuration states): the
    control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        rows = [plain.head_logprobs(trunk(lm, tokens[r], mask[r], sizes, int8, departure), lm["ln_f"],
                                    lm["lm_head"], tokens[r], eps=float(sizes["rms_norm_eps"]), int8=int8)
                for r in range(tokens.shape[0])]
    return jnp.stack(rows)


def logits(lm, tokens, mask, sizes, departure=None):
    """[b, t, vocabulary] logits, for the tests: at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        rows = [plain.head_logits(trunk(lm, tokens[r], mask[r], sizes, departure=departure), lm["ln_f"],
                                  lm["lm_head"], eps=float(sizes["rms_norm_eps"])) for r in range(tokens.shape[0])]
    return jnp.stack(rows)
