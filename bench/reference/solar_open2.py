"""Plain reference for Solar-Open2-250B (upstage, `solar_open2`), written from
the published `config.json` keys. With N an RMSNorm (`rms_norm_eps`) and no
bias on any product but where stated:

    h_0 = embed_tokens[tokens]
    a = h + Op(N_in(h));  h <- a + MoE(N_mlp(a))                  (experts in every layer: first_k_dense_replace 0)
    logits = lm_head(N_f(h_L))                                    (untied)

Layer i's Op is softmax attention where i is in `gqa_layers` and Kimi delta
attention (KDA) elsewhere.

    GQA (num_attention_heads query heads over num_key_value_heads K/V heads of head_dim):
        q, k, v = x W_q, x W_k, x W_v                  no rotation, no position of any kind (`use_rope` false)
        a_h = softmax_causal(q_h . k_{h // group} / sqrt(head_dim)) v_{h // group}
        y = W_o [ a * sigmoid(x W_g) ]                 `use_gqa_gate`: elementwise, W_g hidden -> heads x head_dim
    KDA (`linear_attn_config`: H = num_heads heads of d = head_dim for keys and values alike):
        q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))  depthwise causal, short_conv_kernel_size taps
        q^ = q / sqrt(|q|^2 + 1e-6) * d^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)  a head
        g = -exp(a_h) * softplus(x W_fa W_fb + b_dt)   a key channel, in (-inf, 0]; W_fa hidden -> d, W_fb d -> H d
        beta = 2 sigmoid(x W_b)                        a head, in (0, 2) (`kda_allow_neg_eigval`; sigmoid where false)
        S_t = (I - beta k^ k^^T) Diag(exp(g)) S_{t-1} + beta k^ v^T        S in R^{d x d} a head, float32
        o = S_t^T q^
        y = W_o [ N_head(o_h) * sigmoid(x W_ga W_gb + b_g) ]      norm a head, gate elementwise through d
    MoE:
        s = sigmoid(x W_r) over all n_routed_experts, float32;  sel = the num_experts_per_tok largest of s + expert_bias
        w = s[sel] / (sum(s[sel]) + 1e-20) (`norm_topk_prob`) * routed_scaling_factor
        y = sum_{e in sel} w_e E_e(x) + S(x), E_e and the one shared expert S SwiGLU of moe_intermediate_size, S ungated

The recurrence here is a scan over tokens, the definition; the program's
forward and prefill run it in chunks and its decode step is a kernel
(`trlx_tpu/ops/linear_attention.py`). The attention is one query head at a
time against its K/V head, the whole row's scores at once.

Assumed, where the catalog's `config` does not settle it (each is in
`bench/configs/solar-open2-250b.json` under `assumed`, and each can be
departed from by a name in `sizes["departures"]`, which the tests and
`bench/tests/solar_onchip.py` use to show that the comparison sees it):
  `gqa_gate_per_head`  the GQA gate elementwise (the gated-attention form,
                       after the product with V); the departure gates a head
                       by ONE value (the first column of its block of W_g)
  `gqa_qk_norm`        no q/k norm on a GQA layer (the config names none); the
                       departure puts an RMSNorm (no scale) on both
  `bounded_gate`       KDA's decay as Kimi Linear's public code has it, -exp(a)
                       softplus(.); the departure is its `safe_gate`, -5
                       sigmoid(exp(a) .), which the config does not name
  `no_qk_l2norm`, `no_conv`, `no_kda_gate`, `no_gate_bias`
                       KDA's L2 norms, SiLU convolutions, output gate and its
                       bias b_g as that code; each departure leaves one out
  `no_selection_bias`  DeepSeek-V3's router (whose key names the config uses)
                       with its selection-bias leaf; the departure selects on
                       the scores alone
The published flags are read from `sizes` and can be flipped there:
`use_rope` (rotate-half over the whole head, base `rope_theta`),
`use_gqa_gate`, `kda_allow_neg_eigval`. `kda_use_full_proj` true (one full
matrix in place of each low-rank pair) computes nothing a pair cannot: it
changes the leaves, and is refused by name here.

Departures shared with the program: (1) the stacks hold the experts of ONE
chip of an expert-parallel deployment side by side, experts
`expert_offset` (0) onward; the router keeps its published width (read from
its kernel), the experts held are read from the stacks, the sum runs over
the selected experts held here, the shared expert is computed whole.
(2) positions count real tokens; a masked position is the identity on S
(its input is zeroed, its beta and g are 0) and a key no query sees. (3) the
program normalises the chosen scores with + 1e-6 where this file has the
published + 1e-20: eight sigmoid scores sum to more than 1, so the two differ
by less than float32 resolves. (4) ids come from the vocabulary slice held.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job (`serve_kv_hybrid` is `serve` with another count
# of the pool's bytes: bench/jobs/serve_kv_hybrid.py). `engine_logprob_rms`: the
# root mean square over the sampled tokens of 4 finished requests of |engine
# logprob - reference logprob| (natural log): the engine's chunked prefill and
# then 1,024 decode steps (`kda_decode` over the slot state, `paged_decode` over
# the one GQA layer's arena) against this file's full forward over 9,216
# positions. Readings on the chip (PR 43, one v5e chip, the leaves the job
# serves: the seed's with the selection bias balanced; `bench/tests/
# solar_onchip.py`, 4 prompts of 1,223-8,192 to 1,024 tokens, 5 seeds, and the
# cell's own 7 runs): sound 0.0336-0.0417 (largest: the cell's run of seed
# 2000000011; the tool 0.0382-0.0412); the control, this reference in int8,
# 0.1157-0.1190. The limit is their geometric middle, 1.67 times the sound
# largest and 1.67 times under the control's smallest. With every `dt_bias`
# shifted by -4 on both sides (a state hundreds of tokens old still counts):
# 0.0476-0.0531 | 0.1417-0.1460, the same limit between them. What it refuses,
# each read on the chip against the sound program (seed 3000000203; shifted in
# brackets): beta = sigmoid 0.254 (0.463), the bounded gate 0.551 (0.679), rotary
# positions on the GQA layer 1.319 (1.381), the GQA gate left out 1.017 (1.140)
# or a head 0.910 (1.030), the convolutions left out 1.289 (1.320), KDA's output
# gate left out 0.785 (0.875), the q/k L2 norm left out: not a number (with
# write strengths up to 2 on keys of any length the recurrence diverges). What
# it cannot: a bfloat16 recurrent state (0.0393 against 0.0386 sound, 0.0523
# against 0.0494 shifted), which `kv_bytes_rel` refuses: the pool's bytes read
# 0.0010 from the stated count (its mask and tables), a bfloat16 state 0.165,
# keys and values kept for all four layers 1.98.
_SERVE = {"engine_logprob_rms": 0.0695, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_kv_hybrid": _SERVE}

HEAD_POSITIONS = 512  # positions unembedded at once


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary(x, positions, theta: float):
    """Rotate-half over the whole last dimension of x [t, d] at `positions`
    [t]: only where `use_rope` is flipped, the published model has none."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
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


def gqa_attention(x, p, mask, positions, *, heads, kv_heads, theta, use_rope, use_gate, eps, departs, int8):
    """One row: x [t, hidden], mask [t]. One query head at a time against its
    K/V head (a scan that adds each head's part of the output projection)."""
    t = x.shape[0]
    dim = p["k_proj"]["kernel"].shape[1] // kv_heads
    normed = (lambda y: y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)) if "gqa_qk_norm" in departs \
        else (lambda y: y)
    turned = (lambda y: rotary(y, positions, theta)) if use_rope else (lambda y: y)
    by_kv_head = lambda name: jnp.moveaxis(dense(x, ops.f32(p[name]["kernel"]), int8).reshape(t, kv_heads, dim), 1, 0)
    k, v = jax.vmap(lambda y: turned(normed(y)))(by_kv_head("k_proj")), by_kv_head("v_proj")  # [kv_heads, t, dim]
    i = jnp.arange(t)
    allowed = (i[None, :] <= i[:, None]) & mask[None, :].astype(bool)
    by_head = lambda name: jnp.moveaxis(p[name]["kernel"].reshape(-1, heads, dim), 1, 0)  # [heads, hidden, dim]
    w_o = p["o_proj"]["kernel"].reshape(heads, dim, -1)
    w_g = by_head("gate_proj") if use_gate else jnp.zeros((heads, 1, 1))

    def one_head(y, w):
        w_q, w_gh, w_oh, head = w
        q = turned(normed(dense(x, ops.f32(w_q), int8)))
        mine = head // (heads // kv_heads)
        scores = jnp.matmul(q, k[mine].T, precision=ops.HIGHEST) / jnp.sqrt(float(dim))
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        o = jnp.matmul(probs, v[mine], precision=ops.HIGHEST)
        if use_gate:
            gate = jax.nn.sigmoid(dense(x, ops.f32(w_gh), int8))  # [t, dim]
            o = o * (gate[:, :1] if "gqa_gate_per_head" in departs else gate)
        return y + dense(o, ops.f32(w_oh), int8), None

    y, _ = jax.lax.scan(one_head, jnp.zeros_like(x), (by_head("q_proj"), w_g, w_o, jnp.arange(heads)))
    return y


def delta_attention(x, p, mask, *, heads, neg_eigval, eps, departs, int8):
    """One row: x [t, hidden], mask [t]. The recurrence a token at a time."""
    t = x.shape[0]
    real = mask.astype(jnp.float32)
    x = x * real[:, None]
    proj = lambda name: dense(x, ops.f32(p[name]["kernel"]), int8)
    through = lambda name: dense(proj(f"{name}_a_proj"), ops.f32(p[f"{name}_b_proj"]["kernel"]), int8)
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
    f = by_head(through("f") + ops.f32(p["dt_bias"]["bias"]))
    rate = jnp.exp(ops.f32(p["a_log"]["bias"]))[:, None]  # a head
    g = -5.0 * jax.nn.sigmoid(rate * f) if "bounded_gate" in departs else -rate * jnp.logaddexp(f, 0.0)
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(proj("b_proj"))
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
        gate = through("g") + (0.0 if "no_gate_bias" in departs else ops.f32(p["g_bias"]["bias"]))
        o = o * jax.nn.sigmoid(by_head(gate))
    return dense(o.reshape(t, -1), ops.f32(p["o_proj"]["kernel"]), int8)


def glu(x, w_gate, w_up, w_down, int8):
    return dense(silu(dense(x, w_gate, int8)) * dense(x, w_up, int8), w_down, int8)


def choose_experts(scores, bias, *, top_k, departs=()):
    """[t, experts] scores -> [t, top_k] chosen: the largest biased scores, no groups."""
    return jax.lax.top_k(scores if "no_selection_bias" in departs else scores + bias, top_k)[1]


def expert_ffn(x, p, *, top_k, offset, scaling, departs, int8):
    """The experts held, a plain loop with a mask (every expert computes
    every token, a token keeps what its selected experts gave), and the
    shared expert beside them."""
    scores = jax.nn.sigmoid(jnp.matmul(x, ops.f32(p["router"]["kernel"]), precision=ops.HIGHEST))
    sel = choose_experts(scores, ops.f32(p["expert_bias"]["bias"]), top_k=top_k, departs=departs)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
    d = x.shape[-1]
    held = p["expert_down"]["kernel"].shape[1] // d
    width = p["expert_down"]["kernel"].shape[0]

    def one(g, y):
        block = lambda name, n: ops.f32(jax.lax.dynamic_slice_in_dim(p[name]["kernel"], g * n, n, axis=1))
        mine = jnp.where(sel == offset + g, w, 0.0).sum(-1)  # [t]
        return y + mine[..., None] * glu(x, block("expert_gate", width), block("expert_up", width),
                                         block("expert_down", d), int8)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    return y + glu(x, *(ops.f32(p[n]["kernel"]) for n in ("shared_gate", "shared_up", "shared_down")), int8)


_MIXED = ("is_gqa", "heads", "kv_heads", "kda_heads", "theta", "use_rope", "use_gate", "neg_eigval", "eps",
          "departs", "int8")
_FED = ("eps", "top_k", "offset", "scaling", "departs", "int8")


@functools.partial(jax.jit, static_argnames=_MIXED)
def _mixed(h, p, mask, positions, *, is_gqa, heads, kv_heads, kda_heads, theta, use_rope, use_gate, neg_eigval, eps,
           departs=(), int8=False):
    x = rms_norm(h, p["ln_attn"], eps)
    if is_gqa:
        return h + gqa_attention(x, p["attn"], mask, positions, heads=heads, kv_heads=kv_heads, theta=theta,
                                 use_rope=use_rope, use_gate=use_gate, eps=eps, departs=departs, int8=int8)
    return h + delta_attention(x, p["attn"], mask, heads=kda_heads, neg_eigval=neg_eigval, eps=eps, departs=departs,
                               int8=int8)


@functools.partial(jax.jit, static_argnames=_FED)
def _fed(a, p, *, eps, top_k, offset, scaling, departs=(), int8=False):
    return a + expert_ffn(rms_norm(a, p["ln_mlp"], eps), p["mlp"], top_k=top_k, offset=offset, scaling=scaling,
                          departs=departs, int8=int8)


def mixed(h, p, mask, positions, **static):
    """The first half of a block over one row, h [t, hidden]: h + Op(N_in(h)).
    `static`: what `_static` gives and the layer's `is_gqa`; each half is a
    program of its own and takes what it reads."""
    return _mixed(h, p, mask, positions, **{k: static[k] for k in _MIXED if k in static})


@functools.partial(jax.jit, static_argnames=("eps",))
def router_input(a, ln_mlp, *, eps):
    """What a block's experts, and so its router, are handed: N_mlp(a)."""
    return rms_norm(a, ln_mlp, eps)


def fed(a, p, **static):
    """The second half: a + MoE(N_mlp(a))."""
    return _fed(a, p, **{k: static[k] for k in _FED if k in static})


def layers_of(sizes):
    """Whether each layer held is a GQA one, in order."""
    return [i in sizes["gqa_layers"] for i in range(sizes["num_hidden_layers"])]


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
    if sizes.get("kda_use_full_proj", False):
        raise NotImplementedError("kda_use_full_proj true (full matrices in place of the low-rank pairs) is not "
                                  "written: it changes the leaves, not what they compute")
    if sizes.get("first_k_dense_replace", 0) or sizes.get("n_shared_experts", 1) != 1 \
            or not sizes.get("norm_topk_prob", True):
        raise NotImplementedError("leading dense layers, another number of shared experts than 1 and unnormalised "
                                  "weights are not written: the published config has none")
    return dict(heads=sizes["num_attention_heads"], kv_heads=sizes["num_key_value_heads"],
                kda_heads=sizes["linear_attn_config"]["num_heads"], theta=float(sizes.get("rope_theta", 10000.0)),
                use_rope=bool(sizes.get("use_rope", False)), use_gate=bool(sizes.get("use_gqa_gate", False)),
                neg_eigval=bool(sizes.get("kda_allow_neg_eigval", False)), eps=float(sizes["rms_norm_eps"]),
                top_k=sizes["num_experts_per_tok"], offset=int(sizes.get("expert_offset", 0)),
                scaling=float(sizes.get("routed_scaling_factor", 1.0)),
                departs=tuple(sizes.get("departures", ())), int8=int8)


def trunk(lm, tokens, mask, sizes, int8=False):
    """The state under the final norm, one row: tokens, mask [t] -> [t, hidden]."""
    static = _static(sizes, int8)
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens])
    for i, is_gqa in enumerate(layers_of(sizes)):
        block = lm[f"block_{i}"]
        h = fed(mixed(h, block, mask, positions, is_gqa=is_gqa, **static), block, **static)
    return h


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]), a row
    at a time. `int8` computes every dense and expert product in int8 (the
    router, the softmax and the recurrence stay in float32, as the
    configuration states): the control, never the reference."""
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
