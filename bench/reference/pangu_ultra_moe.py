"""Plain reference for the openPangu-Ultra-MoE family (`model_type`
`pangu_ultra_moe`, openPangu-Ultra-MoE-718B), written from the published
`config.json` keys. With N an RMSNorm (`rms_norm_eps`) and no bias anywhere:

    h_0 = embed_tokens[tokens]
    a = h + N_post_attn(Attn(N_in(h)));  h <- a + N_post_mlp(FFN(N_pre_mlp(a)))   (`sandwich_norm`)
    logits = lm_head(N_f(h_L))                                    (untied)

    Attn (latent attention, MLA; H = num_attention_heads):
        c_q = N_q(x W_qa)                              q_lora_rank
        [q_nope_h ; q_rope_h] = c_q W_qb               qk_nope_head_dim + qk_rope_head_dim a head
        [c_kv ; k_r] = x W_kva;  c = N_kv(c_kv)        kv_lora_rank + qk_rope_head_dim
        [k_nope_h ; v_h] = c W_kvb                     qk_nope_head_dim + v_head_dim a head
        q_rope_h, k_r rotated at the token's position (rotate-half, base
        rope_theta, no scaling); k_r is ONE vector for all heads
        s_h,t = (q_nope_h . k_nope_h,t + q_rope_h . k_r,t) / sqrt(qk_nope + qk_rope), causal
        o_h = sum_t softmax(s_h)_t v_h,t;  Attn = [o_1 .. o_H] W_o
    FFN, layer < first_k_dense_replace:  W2(silu(W1 x) * W3 x), intermediate_size
    FFN, the other layers:
        s = sigmoid(x W_r) over all experts, float32
        sel = top_{num_experts_per_tok}(s + expert_bias)
        w = s[sel];  w /= sum(w) + 1e-6 (`norm_topk_prob`);  w *= routed_scaling_factor
        y = sum_{e in sel} w_e E_e(x) + S(x), E_e and the shared expert S SwiGLU
        of moe_intermediate_size (`n_shared_experts` 1)
    Multi-token prediction (`num_nextn_predict_layers`, DeepSeek-V3's form), block k:
        h'_t = W_eh [N_e(Emb(x_t+k+1)) ; N_h(h_t)],  h the state under N_f (of the
        block before for k > 0); one block of the stack's last kind over h';
        logits for position t + k + 2 = lm_head(N_f(.))

This file computes the attention the way it is published, per-head keys
and values decompressed from the latent: the program's cached steps run the
ABSORBED form over the latents (`models/transformer.LatentAttention`), so
the comparison holds one form against the other.

Assumed, where the catalog's `config` does not settle it (each is in
`bench/configs/openpangu-ultra-moe-718b.json` under `assumed`): the router
scores by sigmoid and chooses without groups (DeepSeek-V3's gate without
`n_group`; the config has `norm_topk_prob` and `routed_scaling_factor` and
names neither a scoring function nor groups); the rotary pairs in the
half-split layout; the shared expert ungated; the multi-token block's form.

Departures, each shared with the program: (1) the stacks hold the experts
of ONE chip of an expert-parallel deployment side by side (`[fan_in,
experts held x fan_out]`), experts `expert_offset` (0) onward; the router
keeps its published width (read from its kernel) and the experts held are
read from the stacks, so the same code computes the uncut layer; the sum
runs over the selected experts held here, the shared expert is computed
whole, and that partial result goes on to the next layer. (2) positions
count real tokens from the left padding on. (3) `expert_bias` is a leaf
drawn from the seed like every other (a checkpoint of this family has no
selection bias: zeros). Memory: one layer is jitted at a time, one head's
[t, t] scores exist at a time, the dense feed-forward runs a slice of its
width at a time and the experts one at a time, each slice widened to
float32 where it is used: 9,216 positions fit beside a serving arena.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job (`serve_latent` is `serve` with another count of
# the cache's bytes: bench/jobs/serve_latent.py). `engine_logprob_rms`: the
# root mean square over the sampled tokens of 4 finished requests of |engine
# logprob - reference logprob| (natural log), the engine's decompressed prefill
# through the fused forward and then up to 1,024 absorbed paged decode steps,
# against this file's full forward over 9,216 positions. Set from readings taken
# on the chip at the cell's own sizes (`bench/tests/pangu_onchip.py`, 4 prompts
# of 1,223-8,192 to 1,024 tokens, seeds 11, 2147483659, 3000000019, 4000000007,
# 101, 202, 303, 2147483747, 3000000203, 4100000001, 977, 1234567; PERF.md
# section 2 has the table; my chip runs, PR 37):
#   sound <= 0.02233 (seed 2147483747; 0.01514-0.02227 on the other eleven)
#   reference-in-int8 control >= 0.05997 (seed 4000000007; 0.0627-0.0636 on three more)
# The limit is their geometric middle, 0.0366, rounded down. Tried against it on
# the chip (seed 11): the sandwich norms left out 1.213, the rotary part of the
# score left out 0.375, 1/sqrt(128) for 1/sqrt(192) 0.194 fail it; a bfloat16
# softmax in the decode steps, 0.0207, it cannot refuse (tests at tiny size do).
# `kv_bytes_rel`: as `gpt_neox.py` has it, against 576 values a token a layer.
_SERVE = {"engine_logprob_rms": 0.036, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_latent": _SERVE}

FFN_SLICE = 2048  # columns of the dense feed-forward widened to float32 at once
HEAD_POSITIONS = 512  # positions unembedded at once


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary(x, positions, theta: float):
    """Rotate-half over the whole last dimension of x [..., t, d] at
    `positions` [t]. The frequencies are worked out on the host in float64
    and rounded once (a float32 `theta ** x` on the TPU is off by ~1e-6
    relative, 3e-3 rad at position 4,000: PERF.md section 6, PR 33)."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # [t, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def dense(x, w, int8):
    return ops.dense(x, {"kernel": w}, int8)


def latent_attention(x, p, mask, positions, *, heads, nope, rope, v_dim, theta, eps, int8):
    """One row: x [t, d], mask and positions [t]. Per-head keys and values,
    one head at a time (a scan that adds each head's part of the output
    projection, so neither [heads, t, t] scores nor [t, heads, v] outputs
    exist at once)."""
    t = x.shape[0]
    lora = p["kv_b_proj"]["kernel"].shape[0]
    c_q = rms_norm(dense(x, ops.f32(p["q_a_proj"]["kernel"]), int8), p["q_a_norm"], eps)
    kv_a = dense(x, ops.f32(p["kv_a_proj"]["kernel"]), int8)
    c = rms_norm(kv_a[:, :lora], p["kv_a_norm"], eps)
    k_r = rotary(kv_a[:, lora:], positions, theta)  # [t, rope]: one for all heads
    i = jnp.arange(t)
    allowed = (i[None, :] <= i[:, None]) & mask[None, :].astype(bool)
    by_head = lambda name, width: jnp.moveaxis(p[name]["kernel"].reshape(-1, heads, width), 1, 0)
    w_o = p["o_proj"]["kernel"].reshape(heads, v_dim, -1)

    def one_head(y, w):
        w_qb, w_kvb, w_oh = (ops.f32(a) for a in w)
        q = dense(c_q, w_qb, int8)  # [t, nope + rope]
        kv = dense(c, w_kvb, int8)  # [t, nope + v]
        q = jnp.concatenate([q[:, :nope], rotary(q[:, nope:], positions, theta)], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)
        scores = jnp.matmul(q, k.T, precision=ops.HIGHEST) / jnp.sqrt(float(nope + rope))
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        o = jnp.matmul(probs, kv[:, nope:], precision=ops.HIGHEST)
        return y + dense(o, w_oh, int8), None

    y, _ = jax.lax.scan(one_head, jnp.zeros_like(x), (by_head("q_b_proj", nope + rope),
                                                      by_head("kv_b_proj", nope + v_dim), w_o))
    return y


def glu(x, w_gate, w_up, w_down, int8):
    return dense(silu(dense(x, w_gate, int8)) * dense(x, w_up, int8), w_down, int8)


def dense_ffn(x, p, int8):
    """SwiGLU, `FFN_SLICE` columns of its width at a time."""
    width = p["down_proj"]["kernel"].shape[0]
    n = min(FFN_SLICE, width)
    assert width % n == 0, (width, n)

    def one(j, y):
        cols = lambda name: ops.f32(jax.lax.dynamic_slice_in_dim(p[name]["kernel"], j * n, n, axis=1))
        rows = ops.f32(jax.lax.dynamic_slice_in_dim(p["down_proj"]["kernel"], j * n, n, axis=0))
        return y + glu(x, cols("gate_proj"), cols("up_proj"), rows, int8)

    return jax.lax.fori_loop(0, width // n, one, jnp.zeros_like(x))


def expert_ffn(x, p, *, top_k, offset, scaling, int8):
    """The experts held, a plain loop with a mask (every expert computes
    every token, a token keeps what its selected experts gave), and the
    shared expert beside them. The router's width is its kernel's, the
    experts held are the stacks'."""
    scores = jax.nn.sigmoid(jnp.matmul(x, ops.f32(p["router"]["kernel"]), precision=ops.HIGHEST))
    _, sel = jax.lax.top_k(scores + ops.f32(p["expert_bias"]["bias"]), top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6) * scaling
    d = x.shape[-1]
    held = p["expert_down"]["kernel"].shape[1] // d
    width = p["expert_down"]["kernel"].shape[0]

    def one(g, y):
        block = lambda name, n: ops.f32(jax.lax.dynamic_slice_in_dim(p[name]["kernel"], g * n, n, axis=1))
        mine = jnp.where(sel == offset + g, w, 0.0).sum(-1)  # [t]
        return y + mine[..., None] * glu(x, block("expert_gate", width), block("expert_up", width),
                                         block("expert_down", d), int8)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    if "shared_gate" in p:
        y = y + glu(x, *(ops.f32(p[n]["kernel"]) for n in ("shared_gate", "shared_up", "shared_down")), int8)
    return y


@functools.partial(jax.jit, static_argnames=("is_dense", "sandwich", "heads", "nope", "rope", "v_dim", "theta",
                                             "eps", "top_k", "offset", "scaling", "int8"))
def layer(h, p, mask, positions, *, is_dense, sandwich, heads, nope, rope, v_dim, theta, eps, top_k, offset,
          scaling, int8=False):
    """One block over one row: h [t, d]."""
    post = (lambda name, y: rms_norm(y, p[name], eps)) if sandwich else (lambda name, y: y)
    a = latent_attention(rms_norm(h, p["ln_attn"], eps), p["attn"], mask, positions, heads=heads, nope=nope,
                         rope=rope, v_dim=v_dim, theta=theta, eps=eps, int8=int8)
    h = h + post("ln_post_attn", a)
    x = rms_norm(h, p["ln_mlp"], eps)
    if is_dense:
        y = dense_ffn(x, p["mlp"], int8)
    else:
        y = expert_ffn(x, p["mlp"], top_k=top_k, offset=offset, scaling=scaling, int8=int8)
    return h + post("ln_post_mlp", y)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logits(h, ln_f, lm_head, *, eps, int8=False):
    return dense(rms_norm(h, ln_f, eps), ops.f32(lm_head["kernel"]), int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logprobs(h, ln_f, lm_head, tokens, *, eps, int8=False):
    """log softmax(lm_head(N_f(h[i]))) at tokens[i + 1], `HEAD_POSITIONS`
    positions at a time. h [t, d], tokens [t] -> [t - 1]."""
    t = h.shape[0]
    pad = -(t - 1) % HEAD_POSITIONS
    x = jnp.pad(rms_norm(h, ln_f, eps)[:-1], ((0, pad), (0, 0)))
    nxt = jnp.pad(tokens[1:].astype(jnp.int32), ((0, pad),))
    w = ops.f32(lm_head["kernel"])

    def chunk(args):
        xc, tc = args
        lp = jax.nn.log_softmax(dense(xc, w, int8), axis=-1)
        return jnp.take_along_axis(lp, tc[:, None], axis=-1)[:, 0]

    out = jax.lax.map(chunk, (x.reshape(-1, HEAD_POSITIONS, x.shape[-1]), nxt.reshape(-1, HEAD_POSITIONS)))
    return out.reshape(-1)[: t - 1]


def _static(sizes, int8):
    return dict(sandwich=bool(sizes.get("sandwich_norm", False)), heads=sizes["num_attention_heads"],
                nope=sizes["qk_nope_head_dim"], rope=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
                theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
                top_k=sizes["num_experts_per_tok"], offset=int(sizes.get("expert_offset", 0)),
                scaling=float(sizes.get("routed_scaling_factor", 1.0)), int8=int8)


def trunk(lm, tokens, mask, sizes, int8=False):
    """The state under the final norm, one row: tokens, mask [t] -> [t, d]."""
    static = _static(sizes, int8)
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i in range(sizes["num_hidden_layers"]):
        h = layer(h, lm[f"block_{i}"], mask, positions, is_dense=i < sizes["first_k_dense_replace"], **static)
    return h


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]), a row
    at a time. `int8` computes every dense and expert product in int8 (the
    router stays in float32, as the configuration states): the control,
    never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        rows = [head_logprobs(trunk(lm, tokens[r], mask[r], sizes, int8), lm["ln_f"], lm["lm_head"], tokens[r],
                              eps=float(sizes["rms_norm_eps"]), int8=int8) for r in range(tokens.shape[0])]
    return jnp.stack(rows)


def logits(lm, tokens, mask, sizes, mtp=False):
    """[b, t, vocabulary] logits, and with `mtp` the list of the multi-token
    blocks' ([b, t, vocabulary] each: block k's position i speaks for token
    i + k + 2; its last k + 1 positions read no next token). For the tests:
    the whole vocabulary at every position, so at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    static = _static(sizes, False)
    eps = static["eps"]
    main, extra = [], []
    with jax.default_matmul_precision("highest"):
        for r in range(tokens.shape[0]):
            h = trunk(lm, tokens[r], mask[r], sizes)
            main.append(head_logits(h, lm["ln_f"], lm["lm_head"], eps=eps))
            if not mtp:
                continue
            row, tok, m, positions = [], tokens[r], mask[r], ops.positions_from_mask(mask[r])
            for k in range(sizes["num_nextn_predict_layers"]):
                p = lm[f"mtp_{k}"]
                tok, m = jnp.pad(tok[1:], (0, 1)), m * jnp.pad(m[1:], (0, 1))
                emb = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tok])
                joined = jnp.concatenate([rms_norm(emb, p["enorm"], eps), rms_norm(h, p["hnorm"], eps)], -1)
                h = layer(dense(joined, ops.f32(p["eh_proj"]["kernel"]), False), p["block"], m, positions,
                          is_dense=sizes["num_hidden_layers"] <= sizes["first_k_dense_replace"], **static)
                row.append(head_logits(h, lm["ln_f"], lm["lm_head"], eps=eps))
            extra.append(row)
    if mtp:
        return jnp.stack(main), [jnp.stack(blocks) for blocks in zip(*extra)]
    return jnp.stack(main)
