"""Plain reference for the language model of Ling-3.0-flash-VL (inclusionAI),
written from the published `config.json` keys. With N an RMSNorm
(`rms_norm_eps`) and no bias on any product:

    h_0 = embed_tokens[tokens]
    a = h + Op(N_in(h));  h <- a + FFN(N_mlp(a))
    logits = lm_head(N_f(h_L))                                    (untied)

Layer i's Op is latent attention where (i + 1) % layer_group_size == 0 and
Kimi delta attention (KDA) elsewhere. H = num_attention_heads, d = head_dim.

    KDA, per token t (x the normed input):
        q~, k~, v~ = x W_q, x W_k, x W_v                          (hidden -> H d each)
        q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))  depthwise, causal, short_conv_kernel_size taps
        q^ = q / sqrt(|q|^2 + 1e-6) * d^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)   a head
        g = kda_lower_bound * sigmoid(exp(a_h) * (x W_f + b_dt))  a key channel, in [kda_lower_bound, 0]
        beta = sigmoid(x W_b)                                     a head
        S_t = (I - beta k^ k^^T) Diag(exp(g)) S_{t-1} + beta k^ v^T       S in R^{d x d} a head, float32
        o = S_t^T q^
        y = W_o [ N_head(o_h) * sigmoid(x W_g)_h ]                norm a head (group_norm_size 1), gate a head
    Latent attention (MLA), q_lora_rank null:
        [q_nope_h ; q_rope_h] = N_q((x W_q)_h)                    qk_nope_head_dim + qk_rope_head_dim a head
        [c_kv ; k_r] = x W_kva;  c = N_kv(c_kv);  k_r <- N_k(k_r) kv_lora_rank + qk_rope_head_dim
        [k_nope_h ; v_h] = c W_kvb
        q_rope_h, k_r rotated at the token's position (rotate-half, base rope_theta); k_r is ONE vector for all heads
        s_h,t = (q_nope_h . k_nope_h,t + q_rope_h . k_r,t) / sqrt(qk_nope + qk_rope), causal
        o_h = sum_t softmax(s_h)_t v_h,t;  y = W_o [ o_h * sigmoid(x W_g)_h ]
    FFN, layer < first_k_dense_replace:  W2(silu(W1 x) * W3 x), intermediate_size
    FFN, the other layers:
        s = sigmoid(x W_r) over all experts, float32;  c = s + expert_bias
        a group (n_group equal contiguous groups) scores the sum of its two largest c;
        the topk_group best groups stay; sel = the num_experts_per_tok largest c inside them
        w = s[sel];  w /= sum(w) + 1e-6 (`norm_topk_prob`);  w *= routed_scaling_factor
        y = sum_{e in sel} w_e E_e(x) + S(x), E_e and the shared expert S SwiGLU of moe_intermediate_size

The recurrence here is a scan over tokens, the definition; the program's
forward and prefill run it in chunks and its decode step is a kernel
(`trlx_tpu/ops/linear_attention.py`). The latent attention is per head,
decompressed; the program's cached steps run it absorbed over the latents.

Assumed, where the catalog's `config` does not settle it (each is in
`bench/configs/ling-3.0-flash-vl.json` under `assumed`, and each can be
departed from by a name in `sizes["departures"]`, which the tests and
`bench/tests/ling_onchip.py` use to show that the comparison sees it):
  `unbounded_gate`   the gate's lower-bounded form (`kda_safe_gate`, the public
                     KDA code's `safe_gate`); the departure is -exp(a) softplus(.)
  `no_kda_gate`      the output gate a head on KDA layers too
                     (`gated_attention_proj_granularity_type` head_wise; Kimi
                     Linear's own is low-rank and elementwise); the departure
                     leaves it out
  `no_qk_l2norm`     L2 as KDA's q/k norm; the departure leaves it out
  `no_mla_qk_norm`   `use_qk_norm` on a latent layer as an RMSNorm over each
                     head's whole query and over the shared rotary key before
                     rotation; the departure leaves both out
  `group_score_max`  DeepSeek-V3's `noaux_tc` group score (the sum of the two
                     largest); the departure is the largest alone
Further departures for the readings on the chip: `no_lower_bound` (= the
first), `beta_one`, `no_conv`, `no_group_limit`.

Departures shared with the program: (1) the stacks hold the experts of ONE
chip of an expert-parallel deployment side by side, experts
`expert_offset` (0) onward; the router keeps its published width (read from
its kernel), the experts held are read from the stacks, the sum runs over
the selected experts held here, the shared expert is computed whole.
(2) positions count real tokens; a masked position is the identity on S
(its input is zeroed, its beta and g are 0). (3) `expert_bias` is a leaf
like every other to this file; the cell's job sets it from the seed by the
balancing rule the published bias comes from, on THIS file's float32 forward
and on nothing the program computes (`bench/jobs/serve_hybrid.py:
balance_expert_bias`, which runs `mixed`, `router_input`, `choose_experts`
and `fed` below). (4) ids come from
the vocabulary slice held, which holds no image token: text traffic only.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job (`serve_hybrid` is `serve` with another count of
# the cache's bytes: bench/jobs/serve_hybrid.py). `engine_logprob_rms`: the root
# mean square over the sampled tokens of 4 finished requests of |engine logprob
# - reference logprob| (natural log): the engine's chunked prefill and then up
# to 2,048 decode steps (`kda_decode` over the slot state, absorbed MLA over the
# paged latents) against this file's full forward over 3,072 positions. Set
# from readings on the chip at the cell's own sizes and at the weights the job
# serves (`bench/tests/ling_onchip.py` and the cell's own runs; PERF.md section 2
# has the table, my chip runs, PR 41): the geometric middle of the largest sound
# reading when it was set, 0.1019, and the reference-in-int8 control's smallest,
# 0.2323 (five runs later the largest sound reading is 0.1031: the middle 0.1548).
# `kv_bytes_rel`: as `gpt_neox.py` has it, against the latent arena and the
# slot state counted from the published keys.
_SERVE = {"engine_logprob_rms": 0.154, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_hybrid": _SERVE}

FFN_SLICE = 2048  # columns of the dense feed-forward widened to float32 at once
HEAD_POSITIONS = 512  # positions unembedded at once


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary(x, positions, theta: float):
    """Rotate-half over the whole last dimension of x [..., t, d] at
    `positions` [t]; the frequencies in float64 on the host, rounded once."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # [t, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def dense(x, w, int8):
    return ops.dense(x, {"kernel": w}, int8)


def short_conv(z, w):
    """Depthwise causal convolution of one row: z [t, c], w [taps, c]
    (tap j meets the input taps - 1 - j positions back), zeros before the row."""
    taps, t = w.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
    return sum(ops.f32(w[j]) * padded[j:j + t] for j in range(taps))


def delta_attention(x, p, mask, *, heads, lower_bound, eps, departs, int8):
    """One row: x [t, hidden], mask [t]. The recurrence a token at a time."""
    t = x.shape[0]
    real = mask.astype(jnp.float32)
    x = x * real[:, None]
    proj = lambda name: dense(x, ops.f32(p[name]["kernel"]), int8)
    by_head = lambda y: y.reshape(t, heads, -1)
    if "no_conv" in departs:
        mixed = lambda name: silu(proj(f"{name}_proj"))
    else:
        mixed = lambda name: silu(short_conv(proj(f"{name}_proj"), p[f"{name}_conv"]["kernel"]))
    q, k, v = by_head(mixed("q")), by_head(mixed("k")), by_head(mixed("v"))
    d = q.shape[-1]
    if "no_qk_l2norm" not in departs:
        unit = lambda y: y / jnp.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
        q, k = unit(q), unit(k)
    q = q * d ** -0.5
    f = by_head(proj("f_proj") + ops.f32(p["dt_bias"]["bias"]))
    rate = jnp.exp(ops.f32(p["a_log"]["bias"]))[:, None]  # a head
    if "unbounded_gate" in departs or "no_lower_bound" in departs:
        g = -rate * jnp.logaddexp(f, 0.0)
    else:
        g = lower_bound * jax.nn.sigmoid(rate * f)
    beta = jnp.ones((t, heads)) if "beta_one" in departs else jax.nn.sigmoid(proj("b_proj"))
    g, beta = g * real[:, None, None], beta * real[:, None]

    def token(S, inputs):  # S [heads, d, d]
        q_t, k_t, v_t, g_t, b_t = inputs
        S = S * jnp.exp(g_t)[:, :, None]
        seen = jnp.einsum("hkv,hk->hv", S, k_t, precision=ops.HIGHEST)
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=ops.HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, v.shape[-1]), jnp.float32), (q, k, v, g, beta))
    o = rms_norm(o, p["o_norm"], eps)  # [t, heads, d]: a norm a head
    if "no_kda_gate" not in departs:
        o = o * jax.nn.sigmoid(proj("gate_proj"))[:, :, None]
    return dense(o.reshape(t, -1), ops.f32(p["o_proj"]["kernel"]), int8)


def latent_attention(x, p, mask, positions, *, heads, nope, rope, v_dim, theta, eps, departs, int8):
    """One row: x [t, hidden]. Per-head keys and values out of the latent,
    one head at a time (a scan that adds each head's part of the output
    projection)."""
    t = x.shape[0]
    lora = p["kv_b_proj"]["kernel"].shape[0]
    normed = "no_mla_qk_norm" not in departs
    kv_a = dense(x, ops.f32(p["kv_a_proj"]["kernel"]), int8)
    c = rms_norm(kv_a[:, :lora], p["kv_a_norm"], eps)
    k_r = kv_a[:, lora:]
    k_r = rotary(rms_norm(k_r, p["k_norm"], eps) if normed else k_r, positions, theta)  # [t, rope]
    gate = jax.nn.sigmoid(dense(x, ops.f32(p["gate_proj"]["kernel"]), int8))  # [t, heads]
    i = jnp.arange(t)
    allowed = (i[None, :] <= i[:, None]) & mask[None, :].astype(bool)
    by_head = lambda name, width: jnp.moveaxis(p[name]["kernel"].reshape(-1, heads, width), 1, 0)
    w_o = p["o_proj"]["kernel"].reshape(heads, v_dim, -1)

    def one_head(y, w):
        w_q, w_kvb, w_oh, gate_h = w
        q = dense(x, ops.f32(w_q), int8)  # [t, nope + rope]
        if normed:
            q = rms_norm(q, p["q_norm"], eps)
        kv = dense(c, ops.f32(w_kvb), int8)  # [t, nope + v]
        q = jnp.concatenate([q[:, :nope], rotary(q[:, nope:], positions, theta)], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)
        scores = jnp.matmul(q, k.T, precision=ops.HIGHEST) / jnp.sqrt(float(nope + rope))
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        o = jnp.matmul(probs, kv[:, nope:], precision=ops.HIGHEST) * gate_h[:, None]
        return y + dense(o, ops.f32(w_oh), int8), None

    y, _ = jax.lax.scan(one_head, jnp.zeros_like(x), (by_head("q_proj", nope + rope),
                                                      by_head("kv_b_proj", nope + v_dim), w_o, gate.T))
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


def choose_experts(scores, bias, *, top_k, n_group, topk_group, departs):
    """The group rule, written out: [t, experts] scores -> [t, top_k] chosen."""
    biased = scores + bias
    if n_group > 1 and "no_group_limit" not in departs:
        t, experts = biased.shape
        by_group = biased.reshape(t, n_group, experts // n_group)
        ranked = -jnp.sort(-by_group, axis=-1)
        group_score = ranked[..., 0] if "group_score_max" in departs else ranked[..., 0] + ranked[..., 1]
        # a group stays if fewer than topk_group groups score above it (ties by the lower index, as top_k)
        order = jnp.argsort(-group_score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        biased = jnp.where((rank < topk_group)[:, :, None], by_group, -jnp.inf).reshape(t, experts)
    return jax.lax.top_k(biased, top_k)[1]


def expert_ffn(x, p, *, top_k, n_group, topk_group, offset, scaling, departs, int8):
    """The experts held, a plain loop with a mask (every expert computes
    every token, a token keeps what its selected experts gave), and the
    shared expert beside them."""
    scores = jax.nn.sigmoid(jnp.matmul(x, ops.f32(p["router"]["kernel"]), precision=ops.HIGHEST))
    sel = choose_experts(scores, ops.f32(p["expert_bias"]["bias"]), top_k=top_k, n_group=n_group,
                         topk_group=topk_group, departs=departs)
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


_MIXED = ("is_latent", "heads", "nope", "rope", "v_dim", "theta", "eps", "lower_bound", "departs", "int8")
_FED = ("is_dense", "eps", "top_k", "n_group", "topk_group", "offset", "scaling", "departs", "int8")


@functools.partial(jax.jit, static_argnames=_MIXED)
def _mixed(h, p, mask, positions, *, is_latent, heads, nope, rope, v_dim, theta, eps, lower_bound, departs=(),
           int8=False):
    x = rms_norm(h, p["ln_attn"], eps)
    if is_latent:
        return h + latent_attention(x, p["attn"], mask, positions, heads=heads, nope=nope, rope=rope, v_dim=v_dim,
                                    theta=theta, eps=eps, departs=departs, int8=int8)
    return h + delta_attention(x, p["attn"], mask, heads=heads, lower_bound=lower_bound, eps=eps, departs=departs,
                               int8=int8)


@functools.partial(jax.jit, static_argnames=_FED)
def _fed(a, p, *, is_dense, eps, top_k, n_group, topk_group, offset, scaling, departs=(), int8=False):
    x = rms_norm(a, p["ln_mlp"], eps)
    if is_dense:
        return a + dense_ffn(x, p["mlp"], int8)
    return a + expert_ffn(x, p["mlp"], top_k=top_k, n_group=n_group, topk_group=topk_group, offset=offset,
                          scaling=scaling, departs=departs, int8=int8)


def mixed(h, p, mask, positions, **static):
    """The first half of a block over one row, h [t, hidden]: h + Op(N_in(h)).
    `static`: what `_static` gives and the layer's `is_latent`, `is_dense`;
    each half is a program of its own and takes what it reads."""
    return _mixed(h, p, mask, positions, **{k: static[k] for k in _MIXED if k in static})


@functools.partial(jax.jit, static_argnames=("eps",))
def router_input(a, ln_mlp, *, eps):
    """What a block's feed-forward, and so its router, is handed: N_mlp(a)."""
    return rms_norm(a, ln_mlp, eps)


def fed(a, p, **static):
    """The second half: a + FFN(N_mlp(a))."""
    return _fed(a, p, **{k: static[k] for k in _FED if k in static})


def layer(h, p, mask, positions, **static):
    """One block over one row: h [t, hidden]. Two programs, so that the job
    that sets the selection bias can stand between them."""
    return fed(mixed(h, p, mask, positions, **static), p, **static)


def layers_of(sizes):
    """(is_latent, is_dense) of each layer held, in order."""
    return [((i + 1) % sizes["layer_group_size"] == 0, i < sizes["first_k_dense_replace"])
            for i in range(sizes["num_hidden_layers"])]


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logits(h, ln_f, lm_head, *, eps, int8=False):
    return dense(rms_norm(h, ln_f, eps), ops.f32(lm_head["kernel"]), int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head_logprobs(h, ln_f, lm_head, tokens, *, eps, int8=False):
    """log softmax(lm_head(N_f(h[i]))) at tokens[i + 1], `HEAD_POSITIONS`
    positions at a time. h [t, hidden], tokens [t] -> [t - 1]."""
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
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(sizes.get(key, ())[: sizes["num_hidden_layers"]]):
            raise NotImplementedError(f"a non-zero swiglu limit ({key}) is not written: its form is not in the config")
    return dict(heads=sizes["num_attention_heads"], nope=sizes["qk_nope_head_dim"], rope=sizes["qk_rope_head_dim"],
                v_dim=sizes["v_head_dim"], theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
                lower_bound=float(sizes["kda_lower_bound"]), top_k=sizes["num_experts_per_tok"],
                n_group=int(sizes.get("n_group", 0)), topk_group=int(sizes.get("topk_group", 0)),
                offset=int(sizes.get("expert_offset", 0)), scaling=float(sizes.get("routed_scaling_factor", 1.0)),
                departs=tuple(sizes.get("departures", ())), int8=int8)


def trunk(lm, tokens, mask, sizes, int8=False):
    """The state under the final norm, one row: tokens, mask [t] -> [t, hidden]."""
    static = _static(sizes, int8)
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i, (is_latent, is_dense) in enumerate(layers_of(sizes)):
        h = layer(h, lm[f"block_{i}"], mask, positions, is_latent=is_latent, is_dense=is_dense, **static)
    return h


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]), a row
    at a time. `int8` computes every dense and expert product in int8 (the
    router and the recurrence stay in float32, as the configuration states):
    the control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        rows = [head_logprobs(trunk(lm, tokens[r], mask[r], sizes, int8), lm["ln_f"], lm["lm_head"], tokens[r],
                              eps=float(sizes["rms_norm_eps"]), int8=int8) for r in range(tokens.shape[0])]
    return jnp.stack(rows)


def logits(lm, tokens, mask, sizes):
    """[b, t, vocabulary] logits. For the tests: the whole vocabulary at
    every position, so at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    eps = float(sizes["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        return jnp.stack([head_logits(trunk(lm, tokens[r], mask[r], sizes), lm["ln_f"], lm["lm_head"], eps=eps)
                          for r in range(tokens.shape[0])])
