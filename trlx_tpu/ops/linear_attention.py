"""Kimi delta attention (KDA): the gated delta rule with a decay a key channel.

One head keeps a matrix S in R^{d_k x d_v}, float32. A token brings a query
and a key (unit length; the query also scaled by d_k^-1/2), a value, a
log-decay g in (-inf, 0]^{d_k} and a write strength beta in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A position with beta = 0 and g = 0 is the identity on S: that is what a
padded position is given, on either side of the real tokens.

Three forms, equal on the same inputs (tests/test_kda_ops.py):

`kda_recurrent`   the definition, a `lax.scan` over positions.
`kda_chunked`     plain `jax.numpy` over chunks of `chunk` positions,
                  differentiable: what a forward without a cache and a
                  prompt's prefill run. With G_i the log-decays cumulated
                  from the chunk's start and w_i = v_i - (D_i S_{i-1})^T k_i,

                      (I + A) W = V - (K * exp(G)) S_0,   A_ij = beta_j (k_i * exp(G_i - G_j)) . k_j, j < i
                      O = (Q * exp(G)) S_0 + B W,         B_ij = beta_j (q_i * exp(G_i - G_j)) . k_j, j <= i
                      S_C = exp(G_C) * S_0 + (K * beta * exp(G_C - G))^T W

                  Every exponent that is used is a difference of cumulated
                  log-decays, at most 0, taken in float32; A and B are formed
                  as products about a reference in the middle of each row's
                  sub-chunk of `SUB` positions, so that every factor stays
                  within exp(+-SUB / 2 * |g|_max), e^+-40 at the published
                  lower bound of -5: a `k / cumprod` over a whole chunk would
                  reach e^320, and a reference at the sub-chunk's start e^-80,
                  where a small component of a unit vector leaves float32's
                  normal range.
                  (I + A)^-1 is exact: forward substitution, nothing dropped.
`kda_decode`      one position for every row of a slot pool as ONE Pallas
                  kernel: a row's state is read once and written once, in
                  place (`input_output_aliases`); a row whose `live` bit is 0
                  keeps its state to the bit. Off the TPU, or for head
                  counts the kernel's tiling does not take, `kda_step` is the
                  same step in `jax.numpy`.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

SUB = 16  # positions about one reference, 8 on either side: 8 * 5 = 40, far inside float32's +-87
_HEADS_PER_CALL = 32  # 4 vectors x 32 heads fill the 128 rows one in-kernel transpose takes
_VMEM_LIMIT = 64 * 1024 * 1024
_HIGHEST = lax.Precision.HIGHEST


def kda_step(state, q, k, v, g, beta):
    """One position: state [..., d_k, d_v] float32; q, k, g [..., d_k]; v
    [..., d_v]; beta [...]. Returns (o [..., d_v] float32, new state)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., :, None]
    seen = jnp.einsum("...kv,...k->...v", decayed, k, precision=_HIGHEST)
    new = decayed + (beta[..., None] * k)[..., :, None] * (v - seen)[..., None, :]
    return jnp.einsum("...kv,...k->...v", new, q, precision=_HIGHEST), new


def kda_recurrent(q, k, v, g, beta, state=None):
    """q, k, g [b, t, h, d_k]; v [b, t, h, d_v]; beta [b, t, h]; state
    [b, h, d_k, d_v] or None (zeros). Returns (o [b, t, h, d_v] float32,
    final state float32)."""
    b, t, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def one(s, x):
        o, s = kda_step(s, *x)
        return s, o

    by_time = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = lax.scan(one, state.astype(jnp.float32), by_time)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular a [..., c, c], by forward
    substitution a row at a time: row i of the inverse is e_i - a[i, :i] @
    (the rows above). Exact; c steps of a [c] x [c, c] product."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)

    def row(inv, i):
        new = eye[i] - jnp.einsum("...j,...jk->...k", a[..., i, :], inv, precision=_HIGHEST)
        return inv.at[..., i, :].set(new), None

    inv, _ = lax.scan(row, jnp.zeros_like(a), jnp.arange(c))
    return inv


def kda_chunked(q, k, v, g, beta, state=None, chunk: int = 64):
    """`kda_recurrent`'s numbers, a chunk at a time. The same arguments;
    t is padded up to whole chunks with identity positions."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, -(-t // SUB) * SUB)
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {SUB}")
    pad = -t % chunk
    q, k, v, g, beta = (jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                        for x in (q, k, v, g, beta))
    n, c, ns = (t + pad) // chunk, chunk, chunk // SUB
    # [b, h, n, c, .]: a head's chunks side by side
    split = lambda x: jnp.moveaxis(x.reshape(b, n, c, h, -1), 3, 1)
    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])  # [b, h, n, c, 1]
    G = jnp.cumsum(g, axis=-2)  # includes the position's own decay
    # the reference of a position: the log-decay cumulated up to the middle of its sub-chunk
    middle = G[..., SUB // 2 - 1::SUB, :]  # [.., ns, dk]
    toward = jnp.exp(G - jnp.repeat(middle, SUB, axis=-2))  # from the reference on (or back) to the position
    q_in, k_in = q * toward, k * toward
    # keys carried to each reference: exp(ref_I - G_j), within exp(+-SUB / 2 * |g|) for j in
    # sub-chunk I, below 1 for earlier j; later j are never used, and are held at a cap
    cap = lax.stop_gradient(1.0 - SUB // 2 * jnp.min(g))  # above every exponent that is used: no tie
    back = jnp.exp(jnp.minimum(middle[..., :, None, :] - G[..., None, :, :], cap))
    k_out = (k * beta)[..., None, :, :] * back  # [.., ns, c, dk]
    rows = lambda x: x.reshape(*x.shape[:-2], ns, SUB, dk)
    pair = lambda x: jnp.einsum("...sid,...sjd->...sij", rows(x), k_out, precision=_HIGHEST).reshape(
        *x.shape[:-2], c, c)
    i = jnp.arange(c)
    A = jnp.where(i[:, None] > i[None, :], pair(k_in), 0.0)
    B = jnp.where(i[:, None] >= i[None, :], pair(q_in), 0.0)
    T = _unit_lower_inverse(A)
    decay_in = jnp.exp(G)  # from the chunk's start
    k_end = k * beta * jnp.exp(G[..., -1:, :] - G)  # on to the chunk's end
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def one(s, x):
        q_c, k_c, v_c, T_c, B_c, qd, kd, ke, last = x
        w = jnp.einsum("...ij,...jv->...iv", T_c,
                       v_c - jnp.einsum("...ik,...kv->...iv", kd, s, precision=_HIGHEST), precision=_HIGHEST)
        o = (jnp.einsum("...ik,...kv->...iv", qd, s, precision=_HIGHEST)
             + jnp.einsum("...ij,...jv->...iv", B_c, w, precision=_HIGHEST))
        s = last[..., :, None] * s + jnp.einsum("...ik,...iv->...kv", ke, w, precision=_HIGHEST)
        return s, o

    by_chunk = tuple(jnp.moveaxis(x, 2, 0) for x in (
        q, k, v, T, B, q * decay_in, k * decay_in, k_end, decay_in[..., -1, :]))
    state, o = lax.scan(one, state.astype(jnp.float32), by_chunk)  # o [n, b, h, c, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


# ---------------------------------------------------------------------------
# The decode step as one kernel
# ---------------------------------------------------------------------------


def decode_kernel_takes(heads: int, dk: int, dv: int) -> bool:
    """Whether the compiled kernel's tiling fits: whole groups of 32 heads
    (their four vectors fill one 128-row transpose), 128-multiples a head."""
    return heads % _HEADS_PER_CALL == 0 and dk % 128 == 0 and dv % 128 == 0


def _decode_body(live_ref, state_ref, cols_ref, v_ref, out_state_ref, o_ref, *, heads: int):
    from jax.experimental import pallas as pl

    row = pl.program_id(0)

    @pl.when(live_ref[row] == 0)
    def _():
        out_state_ref[...] = state_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[row] != 0)
    def _():
        # rows (vector, head) x lanes d_k -> a vector's head h is column vector * heads + h
        cols = cols_ref[0].T  # [d_k, 4 * heads]
        for h in range(heads):
            col = lambda vec: cols[:, vec * heads + h:vec * heads + h + 1]  # [d_k, 1]
            decayed = state_ref[0, h] * col(2)
            seen = jnp.sum(decayed * col(1), axis=0, keepdims=True)  # [1, d_v]
            new = decayed + col(3) * (v_ref[0, h:h + 1, :] - seen)
            out_state_ref[0, h] = new
            o_ref[0, h:h + 1, :] = jnp.sum(new * col(0), axis=0, keepdims=True)


def kda_decode(state, q, k, v, g, beta, live, *, interpret: bool = False):
    """One position a row. state [rows, h, d_k, d_v] float32 (donate it: the
    result's state is written over it); q, k, g [rows, h, d_k]; v [rows, h,
    d_v]; beta [rows, h]; live [rows] (0: the row has no token, its state is
    left as it is and its output is 0). Returns (o [rows, h, d_v] float32,
    state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h, dk, dv = state.shape
    hb = h if interpret and h < _HEADS_PER_CALL else _HEADS_PER_CALL
    if h % hb or (not interpret and not decode_kernel_takes(h, dk, dv)):
        raise ValueError(f"kda_decode takes groups of {_HEADS_PER_CALL} heads of 128-multiples, got {state.shape}")
    f32 = lambda x: x.astype(jnp.float32)
    # the four vectors that act along d_k, (vector, head) on the rows so that ONE
    # transpose in the kernel turns them all into columns: q, k, exp(g), beta * k
    groups = lambda x: f32(x).reshape(rows, h // hb, 1, hb, dk)
    cols = jnp.concatenate([groups(q), groups(k), groups(jnp.exp(f32(g))),
                            groups(f32(beta)[..., None] * f32(k))], axis=2).reshape(rows, 4 * h, dk)
    spec = lambda *block: pl.BlockSpec(block, lambda r, j, live: (r, j) + (0,) * (len(block) - 2))
    new_state, o = pl.pallas_call(
        functools.partial(_decode_body, heads=hb),
        out_shape=(jax.ShapeDtypeStruct(state.shape, jnp.float32), jax.ShapeDtypeStruct((rows, h, dv), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[spec(1, hb, dk, dv), spec(1, 4 * hb, dk), spec(1, hb, dv)],
            out_specs=(spec(1, hb, dk, dv), spec(1, hb, dv)),
            grid=(rows, h // hb),
        ),
        # operand 0 is the prefetched `live`; the state is operand 1 and result 0
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_decode",
    )(live.astype(jnp.int32), f32(state), cols, f32(v))
    return o, new_state


def kda_decode_step(state, q, k, v, g, beta, live, mode: Optional[str]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The decode step of a slot pool by `mode`: "pallas" (the compiled
    kernel; it raises where its tiling does not fit, `decode_kernel_takes`,
    which the engine asks first and counts as a fallback) | "interpret" |
    None (`kda_step`, a masked row's state kept)."""
    if mode in ("pallas", "interpret"):
        return kda_decode(state, q, k, v, g, beta, live, interpret=mode == "interpret")
    o, new = kda_step(state, q, k, v, g, beta)
    keep = (live > 0)[:, None, None, None]
    return jnp.where(keep[..., 0], o, 0.0), jnp.where(keep, new, state)
