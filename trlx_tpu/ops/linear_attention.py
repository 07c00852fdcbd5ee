"""Kimi delta attention (KDA): the gated delta rule with a decay a key channel.

One head keeps a matrix S in R^{d_k x d_v}, float32. A token brings a query
and a key (unit length; the query also scaled by d_k^-1/2), a value, a
log-decay g in (-inf, 0]^{d_k} and a write strength beta in (0, 2) (above 1
the transition I - beta k k^T has a negative eigenvalue along k; the forms
below take beta as it is handed to them):

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

                  Exact for ANY g <= 0: every exponent that is taken is a
                  difference of cumulated log-decays that is at most 0, in
                  float32, so no factor exceeds 1 however fast a channel
                  forgets (a product about a reference in the middle of a
                  row's sub-chunk, the form this file had, needs exp(+8 |g|),
                  an `inf` at g = -12 a step). Inside a sub-chunk of `SUB`
                  positions A and B take exp(G_i - G_j) pair by pair; across
                  sub-chunks, J before I, they are products about the
                  cumulated decay at I's first position: exp(G_i - first_I) *
                  exp(first_I - G_j), each at most 1, whose product underflows
                  only where the pair's own decay does.
                  (I + A)^-1 is exact: forward substitution inside the
                  sub-chunks, the blocks below from those, nothing dropped.
                  A prompt longer than `SPAN` goes a span at a time, the
                  state carried: the pair terms, the inverse and the float32
                  copies are a span's, not the prompt's.
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

CHUNK = 64  # positions a step of the chunked form's scan over the state takes
SUB = 16  # positions whose decays are taken pair by pair; a chunk is whole sub-chunks
SPAN = 1024  # positions whose pair terms are formed at once
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


def _unit_lower_inverse_blocks(a):
    """(I + a)^-1 for strictly lower triangular a [..., ns, SUB, ns, SUB],
    block (I, J) at [..., I, :, J, :]: the diagonal blocks by forward
    substitution (`SUB` steps for all of them at once), the blocks below by
    T_IJ = -T_II sum_{J <= K < I} a_IK T_KJ. Exact; returns [..., c, c]."""
    ns = a.shape[-4]
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=_HIGHEST)
    diag = _unit_lower_inverse(jnp.stack([a[..., s, :, s, :] for s in range(ns)], axis=-3))  # [..., ns, SUB, SUB]
    t = [[None] * ns for _ in range(ns)]
    for i in range(ns):
        t[i][i] = diag[..., i, :, :]
        for j in range(i):
            t[i][j] = -mm(t[i][i], sum(mm(a[..., i, :, k, :], t[k][j]) for k in range(j, i)))
    zero = jnp.zeros_like(t[0][0])
    return jnp.concatenate([jnp.concatenate([t[i][j] if j <= i else zero for j in range(ns)], axis=-1)
                            for i in range(ns)], axis=-2)


def _span_chunked(state, q, k, v, g, beta, chunk: int):
    """`kda_chunked` over one span: q, k, g [b, L, h, d_k], v [b, L, h, d_v],
    beta [b, L, h], L whole chunks; state [b, h, d_k, d_v] float32. Every
    temporary here is the span's, whatever the prompt's length."""
    b, L, h, dk = q.shape
    dv = v.shape[-1]
    n, c, ns = L // chunk, chunk, chunk // SUB
    # [b, h, n, ns, SUB, .]: a head's chunks side by side, a chunk's sub-chunks too
    split = lambda x: jnp.moveaxis(x.astype(jnp.float32).reshape(b, n, ns, SUB, h, -1), 4, 1)
    q, k, v, g, beta = split(q), split(k), split(v), split(g), split(beta[..., None])
    whole = lambda x: x.reshape(b, h, n, c, x.shape[-1])
    G = jnp.cumsum(whole(g), axis=-2).reshape(g.shape)  # from the chunk's start, the position's own decay included
    first = G[..., :1, :] - g[..., :1, :]  # [.., ns, 1, dk]: cumulated up to each sub-chunk's first position
    local = G - first  # from the sub-chunk's start on to the position: <= 0
    kb = k * beta
    i = jnp.arange(SUB)
    # inside a sub-chunk, pair by pair: exp(G_i - G_j) itself, j <= i
    decay = jnp.where((i[:, None] >= i[None, :])[..., None],
                      jnp.exp(jnp.minimum(local[..., :, None, :] - local[..., None, :, :], 0.0)), 0.0)
    inside = lambda x: (x[..., :, None, :] * kb[..., None, :, :] * decay).sum(-1)  # [.., ns, SUB, SUB]
    # across sub-chunks, J < I: exp(G_i - first_I) * exp(first_I - G_j), each factor at most 1
    toward = jnp.exp(local)
    back = jnp.exp(jnp.minimum(first - whole(G)[..., None, :, :], 0.0))  # [.., ns, c, dk]
    k_out = whole(kb)[..., None, :, :] * back
    across = lambda x: jnp.einsum("...sid,...sjd->...sij", x * toward, k_out, precision=_HIGHEST).reshape(
        b, h, n, ns, SUB, ns, SUB)
    s = jnp.arange(ns)
    earlier, same = s[:, None, None, None] > s[None, None, :, None], s[:, None, None, None] == s[None, None, :, None]
    pair = lambda x, keep: (jnp.where(earlier, across(x), 0.0)
                            + jnp.where(same, jnp.where(keep, inside(x), 0.0)[..., :, :, None, :], 0.0))
    T = _unit_lower_inverse_blocks(pair(k, i[:, None] > i[None, :]))  # [b, h, n, c, c]
    B = pair(q, i[:, None] >= i[None, :]).reshape(b, h, n, c, c)
    q, k, v, G, kb = whole(q), whole(k), whole(v), whole(G), whole(kb)
    decay_in = jnp.exp(G)  # from the chunk's start
    k_end = kb * jnp.exp(G[..., -1:, :] - G)  # on to the chunk's end

    def one(s, x):
        v_c, T_c, B_c, qd, kd, ke, last = x
        w = jnp.einsum("...ij,...jv->...iv", T_c,
                       v_c - jnp.einsum("...ik,...kv->...iv", kd, s, precision=_HIGHEST), precision=_HIGHEST)
        o = (jnp.einsum("...ik,...kv->...iv", qd, s, precision=_HIGHEST)
             + jnp.einsum("...ij,...jv->...iv", B_c, w, precision=_HIGHEST))
        s = last[..., :, None] * s + jnp.einsum("...ik,...iv->...kv", ke, w, precision=_HIGHEST)
        return s, o

    by_chunk = tuple(jnp.moveaxis(x, 2, 0) for x in (
        v, T, B, q * decay_in, k * decay_in, k_end, decay_in[..., -1, :]))
    state, o = lax.scan(one, state, by_chunk)  # o [n, b, h, c, dv]
    return state, jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, L, h, dv)


def kda_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK, span: int = SPAN):
    """`kda_recurrent`'s numbers, a chunk at a time, for any g <= 0. The
    same arguments; t is padded up to whole chunks with identity positions.
    Prompts longer than `span` go a span at a time (a scan that carries the
    state), so that what is formed beside the inputs and the outputs does not
    grow with the prompt."""
    b, t, h, dk = q.shape
    chunk = min(chunk, -(-t // SUB) * SUB)
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {SUB}")
    chunks = -(-t // chunk)
    spans = -(-chunks // max(span // chunk, 1))
    L = -(-chunks // spans) * chunk  # spans of equal length, the fewest chunks of padding
    pad = spans * L - t
    q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state = state.astype(jnp.float32)
    with jax.named_scope("kda_chunked"):
        if spans == 1:
            state, o = _span_chunked(state, q, k, v, g, beta, chunk)
            return o[:, :t], state
        by_span = tuple(jnp.moveaxis(x.reshape(b, spans, L, *x.shape[2:]), 1, 0) for x in (q, k, v, g, beta))
        # a gradient keeps a span's state and recomputes the span: its residuals are the inputs'
        body = jax.checkpoint(lambda s, x: _span_chunked(s, *x, chunk))
        state, o = lax.scan(body, state, by_span)  # o [spans, b, L, h, dv]
    return jnp.moveaxis(o, 0, 1).reshape(b, spans * L, h, -1)[:, :t], state


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
