"""Plain reference for Falcon-H1 (tiiuae, `falcon_h1`), written from the
published `config.json` keys and, where they leave a choice open, from the
family's public modelling code (`transformers`, `models/falcon_h1`). With x
the residual stream, N an RMSNorm (`rms_norm_eps`), no bias on any dense
product, and the config's own names for the twelve forward multipliers:

    e      = embed_tokens[token] * embedding_multiplier
    u      = N_in(x)                                                       input_layernorm

    attention branch (num_attention_heads query heads over num_key_value_heads K/V heads of head_dim):
    q      = (u * attention_in_multiplier) W_q      k = ((u * attention_in_multiplier) W_k) * key_multiplier
    v      = (u * attention_in_multiplier) W_v      rotate-half RoPE over the whole head, base rope_theta, on q and k
    attn   = (W_o softmax_causal(q k^T / sqrt(head_dim)) v) * attention_out_multiplier

    SSM branch (Mamba-2: H = mamba_n_heads heads of P = mamba_d_head, d_ssm = H P, N = mamba_d_state, g = mamba_n_groups):
    p      = ((u * ssm_in_multiplier) W_in) * m     W_in: hidden -> d_ssm (z) + d_ssm (x) + g N (B) + g N (C) + H (dt);
                                                    m = ssm_multipliers[0..4] spread over the z, x, B, C, dt columns
    [x;B;C]= SiLU(conv([x;B;C]) + b_conv)           depthwise causal, mamba_d_conv taps, over the d_ssm + 2 g N channels
    dt     = softplus(dt + dt_bias)   A = -exp(A_log)                      a head; no clamp
    h_t    = exp(dt_t A) h_{t-1} + B_t (dt_t x_t)^T                        h in R^{N x P} a head; head i reads group i // (H / g)
    y_t    = h_t^T C_t + D x_t                                             D a head
    y      = N_grouped(y * SiLU(z))                                        gate first, then a norm over each of g groups of
                                                                           d_ssm / g channels, one scale d_ssm wide
    ssm    = (y W_out) * ssm_out_multiplier

    x'     = x + attn + ssm
    x''    = x' + (W_down (SiLU((f W_gate) * mlp_multipliers[0]) * (f W_up))) * mlp_multipliers[1],  f = N_ff(x')
    logits = (N_f(x_last) W_head) * lm_head_multiplier                     head untied

The recurrence here is a scan over tokens, the definition; the program's
forward and prefill run it in chunks and its decode step is a kernel
(`trlx_tpu/ops/ssd.py`). The attention is one query head at a time against
its K/V head, the whole row's scores at once. The unembedding runs
`HEAD_POSITIONS` positions and `HEAD_COLUMNS` of the vocabulary at a time: the
whole head in float32 (5.3 GB at the published widths) does not fit beside a
serving pool.

Assumed, where the catalog's `config` does not settle it (each is in
`bench/configs/falcon-h1-34b.json` under `assumed`; each that changes the
numbers can be departed from by a name in `sizes["departures"]`, which the
tests and `bench/tests/falcon_onchip.py` use to show that the comparison sees
it):
  `no_d`               y_t = h_t^T C_t + D x_t with D a head, as the family's
                       code; the departure drops the skip
  `norm_before_gate`   `mamba_norm_before_gate` false read as gate FIRST, then
                       the norm; the departure norms, then gates
  `ungrouped_norm`     the gated norm over each of `mamba_n_groups` groups of
                       channels, as that code's `FalconH1RMSNormGated`; the
                       departure is one norm over all d_ssm
  no clamp on dt       (that code's `time_step_limit` is (0, inf)) and the key
                       multiplier on k BEFORE it is rotated have no departure:
                       at the family's initialisation dt lies inside Mamba-2's
                       optional clamp [0.001, 0.1] anyway, and a rotation is
                       linear, so the multiplier's place is a matter of
                       rounding only
  `state_bf16`         not an assumption but the precision control: the state
                       h rounded to bfloat16 after every token
The published flags that change the equations (`mamba_rms_norm`,
`mamba_norm_before_gate`, `attn_layer_indices`, the bias flags) are refused
by name when they differ from the published model's.

Departures shared with the program: (1) positions count real tokens; a
masked position is the identity on h (its input is zeroed before W_in, its dt
is 0) and a key no query sees. (2) nothing but depth is cut: every layer is of
one kind, so no share of a deployment is taken and no test adds shares up.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.files import load_module

ops = load_module("reference/plain_ops.py")

# Limits of `correct`, by job (`serve_parallel_hybrid` is `serve` with another
# count of the pool's bytes and three leaves a layer set by the family's
# published initialisation: bench/jobs/serve_parallel_hybrid.py).
# `engine_logprob_rms`: the root mean square over the sampled tokens of 4
# finished requests of |engine logprob - reference logprob| (natural log): the
# engine's chunked prefill and then 512 decode steps (`ssd_decode` over the slot
# state, `paged_decode` over the arena, in every layer) against this file's full
# forward over 1,536 positions. The numbers are small because the logits are:
# under the lm_head multiplier of 1/128 a seeded model's logits are a few
# hundredths, a logprob is -log(261,120) to three digits, and the sound
# program's error is the bfloat16 rounding of the logits themselves (median
# 2^-15 on every seed). Readings on the chip (PR 48, one v5e chip, the leaves
# the job serves; `bench/tests/falcon_onchip.py`, 4 prompts of 64-1,024 to 512
# tokens, 5 seeds, and the cell's own 10 runs): sound 4.51e-5 to 4.92e-5; the
# control, this reference in int8, 1.688e-4 to 1.782e-4. The limit is their
# geometric middle, 1.85 times the sound largest and 1.85 times under the
# control's smallest. The same program computing in float32 at `highest` over
# the same leaves: 6.7e-6. What the limit refuses, each read on the chip against
# the sound program (seeds 11, 2147483659): the skip D left out 4.4e-3, the norm
# before the gate 3.9e-3. What it cannot: a bfloat16 recurrent state (4.53e-5 to
# 4.65e-5 on five seeds, inside the sound range: at the family's initialisation
# and seeded B and C under multipliers of 0.18 and 0.5 the state's part of a
# mixer's output is small beside the skip's), which `kv_bytes_rel` refuses (the
# pool's bytes read 0.0002 from the stated count, its mask and tables; a
# bfloat16 state 0.284); and an ungrouped norm (5.13e-5 to 5.29e-5: two groups
# of 2,048 seeded channels have the same mean square to a percent), which the
# CPU tests at groups of 16 channels hold (tests/test_falcon_h1.py).
_SERVE = {"engine_logprob_rms": 9.1e-5, "kv_bytes_rel": 0.02}
LIMITS = {"serve": _SERVE, "serve_parallel_hybrid": _SERVE}

HEAD_POSITIONS = 512  # positions unembedded at once
HEAD_COLUMNS = 32640  # columns of the vocabulary widened to float32 at once (an eighth of 261,120)


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * ops.f32(p["scale"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary(x, positions, theta: float):
    """Rotate-half over the whole last dimension of x [t, d] at `positions` [t]."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def dense(x, w, int8):
    return ops.dense(x, {"kernel": w}, int8)


def short_conv(z, w, b):
    """Depthwise causal convolution of one row with a bias: z [t, c], w [taps, c]
    (tap j meets the input taps - 1 - j positions back), zeros before the row."""
    taps, t = w.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
    return sum(ops.f32(w[j]) * padded[j:j + t] for j in range(taps)) + ops.f32(b)


def attention_branch(u, p, mask, positions, *, heads, kv_heads, theta, mult, int8):
    """What the attention adds to the residual, one row: u [t, hidden] the
    block's normed input, mask [t]. One query head at a time against its K/V
    head (a scan that adds each head's part of the output projection)."""
    t, mult = u.shape[0], dict(mult)
    x = u * mult["attention_in"]
    dim = p["k_proj"]["kernel"].shape[1] // kv_heads
    by_kv_head = lambda name: jnp.moveaxis(dense(x, ops.f32(p[name]["kernel"]), int8).reshape(t, kv_heads, dim), 1, 0)
    k = jax.vmap(lambda y: rotary(y, positions, theta))(by_kv_head("k_proj") * mult["key"])  # [kv_heads, t, dim]
    v = by_kv_head("v_proj")
    i = jnp.arange(t)
    allowed = (i[None, :] <= i[:, None]) & mask[None, :].astype(bool)
    w_q = jnp.moveaxis(p["q_proj"]["kernel"].reshape(-1, heads, dim), 1, 0)  # [heads, hidden, dim]
    w_o = p["o_proj"]["kernel"].reshape(heads, dim, -1)

    def one_head(y, w):
        w_qh, w_oh, head = w
        q = rotary(dense(x, ops.f32(w_qh), int8), positions, theta)
        mine = head // (heads // kv_heads)
        scores = jnp.matmul(q, k[mine].T, precision=ops.HIGHEST) / jnp.sqrt(float(dim))
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return y + dense(jnp.matmul(probs, v[mine], precision=ops.HIGHEST), ops.f32(w_oh), int8), None

    y, _ = jax.lax.scan(one_head, jnp.zeros_like(u), (w_q, w_o, jnp.arange(heads)))
    return y * mult["attention_out"]


def ssm_branch(u, p, mask, *, ssm_heads, d_head, d_state, groups, eps, mult, departs, int8):
    """What the Mamba-2 mixer adds to the residual, one row: u [t, hidden],
    mask [t]. The recurrence a token at a time."""
    t, mult = u.shape[0], dict(mult)
    real = mask.astype(jnp.float32)
    d_ssm, gn = ssm_heads * d_head, groups * d_state
    proj = dense(u * real[:, None] * mult["ssm_in"], ops.f32(p["in_proj"]["kernel"]), int8)
    if mult["ssm"]:
        proj = proj * jnp.asarray(np.repeat(np.asarray(mult["ssm"], np.float32), (d_ssm, d_ssm, gn, gn, ssm_heads)))
    z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn], proj[:, 2 * d_ssm + 2 * gn:]
    xbc = silu(short_conv(xbc, p["conv1d"]["kernel"], p["conv1d"]["bias"]))
    x = xbc[:, :d_ssm].reshape(t, ssm_heads, d_head)
    by_head = lambda y: jnp.repeat(y.reshape(t, groups, d_state), ssm_heads // groups, axis=1)  # [t, H, N]
    B, C = by_head(xbc[:, d_ssm:d_ssm + gn]), by_head(xbc[:, d_ssm + gn:])
    dt = jnp.logaddexp(dt + ops.f32(p["dt_bias"]["bias"]), 0.0) * real[:, None]
    A = -jnp.exp(ops.f32(p["a_log"]["bias"]))

    def token(h, inputs):  # h [H, N, P]
        x_t, dt_t, B_t, C_t = inputs
        h = h * jnp.exp(dt_t * A)[:, None, None] + B_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        if "state_bf16" in departs:
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.einsum("hnp,hn->hp", h, C_t, precision=ops.HIGHEST)

    _, y = jax.lax.scan(token, jnp.zeros((ssm_heads, d_state, d_head), jnp.float32), (x, dt, B, C))
    if "no_d" not in departs:
        y = y + ops.f32(p["d"]["scale"])[:, None] * x
    y, gate = y.reshape(t, d_ssm), silu(z)
    width = d_ssm if "ungrouped_norm" in departs else d_ssm // groups
    normed = lambda a: (lambda g: g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps))(
        a.reshape(t, -1, width)).reshape(t, d_ssm)
    y = normed(y) * gate if "norm_before_gate" in departs else normed(y * gate)
    return dense(y * ops.f32(p["norm"]["scale"]), ops.f32(p["out_proj"]["kernel"]), int8) * mult["ssm_out"]


def feed_forward(f, p, *, mult, int8):
    on_gate, on_out = dict(mult)["mlp"] or (1.0, 1.0)
    gated = silu(dense(f, ops.f32(p["gate_proj"]["kernel"]), int8) * on_gate) * dense(
        f, ops.f32(p["up_proj"]["kernel"]), int8)
    return dense(gated, ops.f32(p["down_proj"]["kernel"]), int8) * on_out


_ATTN = ("heads", "kv_heads", "theta", "mult", "int8")
_SSM = ("ssm_heads", "d_head", "d_state", "groups", "eps", "mult", "departs", "int8")


@functools.partial(jax.jit, static_argnames=("eps", *_ATTN))
def attention_part(h, p, mask, positions, *, eps, **static):
    """The attention branch's contribution to the residual of one block."""
    return attention_branch(rms_norm(h, p["ln_attn"], eps), p["attn"], mask, positions, **static)


@functools.partial(jax.jit, static_argnames=_SSM)
def ssm_part(h, p, mask, *, eps, **static):
    """The SSM branch's contribution to the residual of one block."""
    return ssm_branch(rms_norm(h, p["ln_attn"], eps), p["ssm"], mask, eps=eps, **static)


@functools.partial(jax.jit, static_argnames=("eps", "mult", "int8"))
def _fed(a, p, *, eps, mult, int8=False):
    return a + feed_forward(rms_norm(a, p["ln_mlp"], eps), p["mlp"], mult=mult, int8=int8)


def mixed(h, p, mask, positions, **static):
    """The first half of a block over one row, h [t, hidden]: h + attn + ssm.
    `static`: what `_static` gives; each part is a program of its own and
    takes what it reads."""
    return (h + attention_part(h, p, mask, positions, eps=static["eps"], **{k: static[k] for k in _ATTN})
            + ssm_part(h, p, mask, **{k: static[k] for k in _SSM}))


def fed(a, p, **static):
    """The second half: a + MLP(N_ff(a))."""
    return _fed(a, p, eps=static["eps"], mult=static["mult"], int8=static["int8"])


def _head_slices(vocab: int) -> int:
    count = -(-vocab // HEAD_COLUMNS)
    return count if vocab % count == 0 else 1


@functools.partial(jax.jit, static_argnames=("eps", "scale", "int8"))
def head_logits(h, ln_f, lm_head, *, eps, scale, int8=False):
    return dense(rms_norm(h, ln_f, eps), ops.f32(lm_head["kernel"]), int8) * scale


@functools.partial(jax.jit, static_argnames=("eps", "scale", "int8"))
def head_logprobs(h, ln_f, lm_head, tokens, *, eps, scale, int8=False):
    """log softmax(lm_head(N_f(h[i])) * scale) at tokens[i + 1], `HEAD_POSITIONS`
    positions and one slice of the vocabulary at a time (a running maximum and
    sum over the slices). h [t, hidden], tokens [t] -> [t - 1]."""
    t, vocab = h.shape[0], lm_head["kernel"].shape[1]
    slices = _head_slices(vocab)
    width = vocab // slices
    pad = -(t - 1) % HEAD_POSITIONS
    x = jnp.pad(rms_norm(h, ln_f, eps)[:-1], ((0, pad), (0, 0)))
    nxt = jnp.pad(tokens[1:].astype(jnp.int32), ((0, pad),))

    def chunk(args):
        xc, tc = args

        def one_slice(carry, j):
            top, total, mine = carry
            w = ops.f32(jax.lax.dynamic_slice_in_dim(lm_head["kernel"], j * width, width, axis=1))
            logits = dense(xc, w, int8) * scale  # [positions, width]
            new_top = jnp.maximum(top, logits.max(-1))
            total = total * jnp.exp(top - new_top) + jnp.exp(logits - new_top[:, None]).sum(-1)
            local = tc - j * width
            here = (local >= 0) & (local < width)
            picked = jnp.take_along_axis(logits, jnp.clip(local, 0, width - 1)[:, None], axis=-1)[:, 0]
            return (new_top, total, jnp.where(here, picked, mine)), None

        n = xc.shape[0]
        start = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros((n,)))
        (top, total, mine), _ = jax.lax.scan(one_slice, start, jnp.arange(slices))
        return mine - top - jnp.log(total)

    out = jax.lax.map(chunk, (x.reshape(-1, HEAD_POSITIONS, x.shape[-1]), nxt.reshape(-1, HEAD_POSITIONS)))
    return out.reshape(-1)[: t - 1]


def _static(sizes, int8):
    for key, want in (("mamba_rms_norm", True), ("mamba_norm_before_gate", False), ("attn_layer_indices", None),
                      ("attention_bias", False), ("mamba_proj_bias", False), ("mlp_bias", False),
                      ("projectors_bias", False), ("mamba_conv_bias", True)):
        if sizes.get(key, want) != want:
            raise NotImplementedError(f"falcon_h1 with {key}={sizes[key]!r} is not written: the published model "
                                      "has none of it")
    # (name, value) pairs: hashable, so a jitted function's static argument; read through `dict(mult)`
    mult = (("embedding", float(sizes.get("embedding_multiplier", 1.0))),
            ("lm_head", float(sizes.get("lm_head_multiplier", 1.0))),
            ("attention_in", float(sizes.get("attention_in_multiplier", 1.0))),
            ("attention_out", float(sizes.get("attention_out_multiplier", 1.0))),
            ("key", float(sizes.get("key_multiplier", 1.0))),
            ("ssm_in", float(sizes.get("ssm_in_multiplier", 1.0))),
            ("ssm_out", float(sizes.get("ssm_out_multiplier", 1.0))),
            ("ssm", tuple(float(m) for m in sizes.get("ssm_multipliers") or ())),
            ("mlp", tuple(float(m) for m in sizes.get("mlp_multipliers") or ())))
    return dict(heads=sizes["num_attention_heads"], kv_heads=sizes["num_key_value_heads"],
                theta=float(sizes["rope_theta"]), ssm_heads=sizes["mamba_n_heads"], d_head=sizes["mamba_d_head"],
                d_state=sizes["mamba_d_state"], groups=sizes["mamba_n_groups"], eps=float(sizes["rms_norm_eps"]),
                mult=mult, departs=tuple(sizes.get("departures", ())), int8=int8)


def trunk(lm, tokens, mask, sizes, int8=False):
    """The state under the final norm, one row: tokens, mask [t] -> [t, hidden]."""
    static = _static(sizes, int8)
    positions = ops.positions_from_mask(mask)
    h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens]) * dict(static["mult"])["embedding"]
    for i in range(sizes["num_hidden_layers"]):
        block = lm[f"block_{i}"]
        h = fed(mixed(h, block, mask, positions, **static), block, **static)
    return h


def logprobs(lm, tokens, mask, sizes, int8=False):
    """[b, t - 1] float32: log p(tokens[:, i + 1] | tokens[:, :i + 1]), a row
    at a time. `int8` computes every dense product in int8 (the softmax, the
    recurrence, dt and the decays stay in float32, as the configuration
    states): the control, never the reference."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    eps, scale = float(sizes["rms_norm_eps"]), float(sizes.get("lm_head_multiplier", 1.0))
    with jax.default_matmul_precision("highest"):
        rows = [head_logprobs(trunk(lm, tokens[r], mask[r], sizes, int8), lm["ln_f"], lm["lm_head"], tokens[r],
                              eps=eps, scale=scale, int8=int8) for r in range(tokens.shape[0])]
    return jnp.stack(rows)


def logits(lm, tokens, mask, sizes):
    """[b, t, vocabulary] logits. For the tests: the whole vocabulary at
    every position, so at small sizes only."""
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    eps, scale = float(sizes["rms_norm_eps"]), float(sizes.get("lm_head_multiplier", 1.0))
    with jax.default_matmul_precision("highest"):
        return jnp.stack([head_logits(trunk(lm, tokens[r], mask[r], sizes), lm["ln_f"], lm["lm_head"], eps=eps,
                                      scale=scale) for r in range(tokens.shape[0])])


def branches(lm, tokens, mask, sizes, layer: int = 0):
    """(attention branch, SSM branch): what each adds to the residual in block
    `layer`, one row, each [t, hidden], before they are summed."""
    static = _static(sizes, False)
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)
    positions = ops.positions_from_mask(mask)
    with jax.default_matmul_precision("highest"):
        h = ops.f32(jnp.asarray(lm["embed_tokens"]["embedding"])[tokens]) * dict(static["mult"])["embedding"]
        for i in range(layer):
            h = fed(mixed(h, lm[f"block_{i}"], mask, positions, **static), lm[f"block_{i}"], **static)
        block = lm[f"block_{layer}"]
        return (attention_part(h, block, mask, positions, eps=static["eps"], **{k: static[k] for k in _ATTN}),
                ssm_part(h, block, mask, **{k: static[k] for k in _SSM}))
