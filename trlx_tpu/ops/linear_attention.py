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

Four forms, equal on the same inputs (tests/test_kda_ops.py):

`kda_recurrent`   the definition, a `lax.scan` over positions.
`kda_chunked`     plain `jax.numpy` over chunks of `chunk` positions, differentiable: what a forward without
                  a cache runs (training, the CPU, a mesh). With G_i the log-decays cumulated from the chunk's
                  start and w_i = v_i - (D_i S_{i-1})^T k_i,

                      (I + A) W = V - (K * exp(G)) S_0,   A_ij = beta_j (k_i * exp(G_i - G_j)) . k_j, j < i
                      O = (Q * exp(G)) S_0 + B W,         B_ij = beta_j (q_i * exp(G_i - G_j)) . k_j, j <= i
                      S_C = exp(G_C) * S_0 + (K * beta * exp(G_C - G))^T W

                  Exact for ANY g <= 0: every exponent that is taken is a difference of cumulated log-decays
                  that is at most 0, in float32, so no factor exceeds 1 however fast a channel forgets (a
                  product about a reference in the middle of a row's sub-chunk, the form this file had, needs
                  exp(+8 |g|), an `inf` at g = -12 a step). Inside a sub-chunk of `SUB` positions A and B take
                  exp(G_i - G_j) pair by pair; across sub-chunks, J before I, they are products about the
                  cumulated decay at I's first position: exp(G_i - first_I) * exp(first_I - G_j), each at most
                  1, whose product underflows only where the pair's own decay does. (I + A)^-1 is exact:
                  forward substitution inside the sub-chunks, the blocks below from those, nothing dropped.
                  A prompt longer than `SPAN` goes a span at a time, the state carried: the pair terms, the
                  inverse and the float32 copies are a span's, not the prompt's.
`kda_chunk_fwd`   a span of a prompt as ONE Pallas kernel, forward only: what a cached prefill runs where
                  kernels run (`chunk_kernel_mode`: one TPU device or the interpreter, and `kda_decode`'s
                  tiling; `kda_chunked(forward_only=True)` asks). The same three equations a (head, chunk) in
                  VMEM, float32 products, the same rule for every exponent: inside a sub-chunk the pairs
                  (i, i - d) of every i at once, exp(g_{i-d+1} + .. + g_i), one sub-diagonal of A and B a
                  shift d; across sub-chunks one product about the middle of the two, then four, sub-chunks
                  that hold the pair; the diagonal blocks' inverse by forward substitution, then the blocks'
                  rule. The head's state stays in VMEM over a span; the scan over spans is `kda_chunked`'s.
`kda_decode`      one position for every row of a slot pool as ONE Pallas kernel: a row's state is read once
                  and written once, in place (`input_output_aliases`); a row whose `live` bit is 0 keeps its
                  state to the bit. Off the TPU, or for head counts the kernel's tiling does not take,
                  `kda_step` is the same step in `jax.numpy`.
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


def kda_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK, span: int = SPAN, forward_only: bool = False):
    """`kda_recurrent`'s numbers, a chunk at a time, for any g <= 0. The same arguments; t is padded up to
    whole chunks with identity positions. Prompts longer than `span` go a span at a time (a scan that carries
    the state): what is formed beside the inputs and the outputs does not grow with the prompt. `forward_only`
    (a cached prefill: nothing differentiates it) lets `chunk_kernel_mode` hand the spans to `kda_chunk_fwd`."""
    b, t, h, dk = q.shape
    if forward_only and (mode := chunk_kernel_mode(h, dk, v.shape[-1])):
        return _chunked_by_kernel(q, k, v, g, beta, state, span, mode)
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


# ---------------------------------------------------------------------------
# A span of a prompt as one kernel
# ---------------------------------------------------------------------------


def chunk_kernel_mode(heads: int, dk: int, dv: int) -> Optional[str]:
    """How a cached prefill runs the recurrence: "pallas" | "interpret"
    (`kda_chunk_fwd`, where `ops.attention.kernel_mode()` says so and the
    tiling fits: `decode_kernel_takes`, ONE predicate for both kernels, so the
    engine's `kda_decode_tiling` counts both, and a grid step's blocks, every
    head's, inside the kernel's VMEM limit: 42 of 64 MiB at 64 heads of 128;
    the interpreter takes any shape) or None (the XLA form)."""
    from trlx_tpu.ops.attention import kernel_mode

    mode = kernel_mode()
    held = 4 * heads * (2 * CHUNK * (3 * dk + 2 * dv) + 5 * dk * dv)  # inputs and outputs twice, the states five times
    takes = mode == "interpret" or (mode == "pallas" and decode_kernel_takes(heads, dk, dv) and held <= _VMEM_LIMIT)
    return mode if takes else None


def _chunk_body(span_ref, state_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, o_ref, out_state_ref, s_ref):
    """One (row, chunk), a head at a time: `_span_chunked`'s numbers for the
    chunk from the heads' states in `s_ref`, which live across the span's
    chunks. A block's rows are (position, head), as the arrays lie in HBM: a
    head's [c, d] tile is every `heads`-th row. Every exponent is a sum of
    log-decays, at most 0: inside a sub-chunk pair by pair (one sub-diagonal
    of A and B a shift), across sub-chunks about the middle of the block of
    two, then four, sub-chunks that holds the pair."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    step = pl.program_id(1)
    (_, c, heads), dk = beta_ref.shape, q_ref.shape[2]
    f32 = jnp.float32

    @pl.when(step == 0)
    def _():
        s_ref[...] = state_ref[0]

    def dot(a, b, dims=((1,), (0,))):
        return lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST, preferred_element_type=f32)

    nt, tn = ((1,), (1,)), ((0,), (0,))
    ri, ci = lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    eye, same_sub = ri == ci, ri // SUB == ci // SUB
    eye_f, lower_f = eye.astype(f32), (ri >= ci).astype(f32)
    eye_k = lax.broadcasted_iota(jnp.int32, (dk, dk), 0) == lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    of_head = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    column = lambda x, pick: jnp.sum(jnp.where(pick, x, 0.0), axis=1, keepdims=True)  # picks one of a row's lanes

    def head(h, carry):
        tile = pl.ds(h, c, stride=heads)
        q, k, g, v, s = q_ref[0, tile], k_ref[0, tile], g_ref[0, tile], v_ref[0, tile], s_ref[h]
        G = dot(lower_f, g)  # [c, dk]: cumulated from the chunk's start, the position's own decay included
        kb = k * column(beta_ref[0], of_head == h)
        # inside a sub-chunk: the pair (i, i - d) for every i at once, exp(g_{i-d+1} + .. + g_i)
        A = jnp.zeros((c, c), f32)
        B = jnp.where(eye, jnp.sum(q * kb, axis=1, keepdims=True), 0.0)
        between, g_back, kb_back = jnp.zeros_like(g), g, kb
        for d in range(1, SUB):
            between = between + g_back
            g_back, kb_back = pltpu.roll(g_back, 1, 0), pltpu.roll(kb_back, 1, 0)  # row i holds position i - d
            pair = kb_back * jnp.exp(between)
            on = (ri - ci == d) & (row % SUB >= d)
            A = A + jnp.where(on, jnp.sum(k * pair, axis=1, keepdims=True), 0.0)
            B = B + jnp.where(on, jnp.sum(q * pair, axis=1, keepdims=True), 0.0)
        # across sub-chunks: exp(G_i - G_mid) * exp(G_mid - G_j) about the middle of the block that holds both
        half = c // 2
        while half >= SUB:
            size = 2 * half
            mid = jnp.concatenate([jnp.broadcast_to(G[at + half - 1:at + half], (size, dk))
                                   for at in range(0, c, size)], axis=0)
            factor = jnp.exp(jnp.minimum(jnp.where(row % size >= half, G - mid, mid - G), 0.0))
            both = dot(jnp.concatenate([k * factor, q * factor], axis=0), kb * factor, nt)  # [2 c, c]
            on = (ri // size == ci // size) & (ri % size >= half) & (ci % size < half)
            A, B = A + jnp.where(on, both[:c], 0.0), B + jnp.where(on, both[c:], 0.0)
            half //= 2
        # (I + A)^-1: the diagonal blocks by forward substitution, row j of all of them at a step
        # (what is below a block's row j takes it times its column j), then the blocks' rule:
        # (I + D + N)^-1 = (I + M)^-1 X with X = (I + D)^-1 and M = X N, whose fourth power is 0
        D = jnp.where(same_sub, A, 0.0)
        X = eye_f
        for j in range(SUB - 1):
            rows = sum(X[at + j:at + j + 1] for at in range(0, c, SUB))  # [1, c]: each block's row j, side by side
            X = X - jnp.where(same_sub, column(D, ci % SUB == j) * rows, 0.0)
        M = dot(X, A - D)
        less = eye_f - M
        T = dot(less + dot(less, dot(M, M)), X)  # (I - M)(I + M^2) X
        decay_in = jnp.exp(G)
        seen = dot(jnp.concatenate([k * decay_in, q * decay_in], axis=0), s)  # [2 c, dv]
        w = dot(T, v - seen[:c])
        o_ref[0, tile] = seen[c:] + dot(B, w)
        k_end = kb * jnp.exp(G[c - 1:c] - G)
        s_ref[h] = column(decay_in[c - 1:c], eye_k) * s + dot(k_end, w, tn)
        return carry

    lax.fori_loop(0, heads, head, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        out_state_ref[0] = s_ref[...]


def kda_chunk_fwd(state, q, k, v, g, beta, span, *, chunks: int, interpret: bool = False):
    """One span of a prompt, forward only, as ONE Pallas kernel. state
    [b, h, d_k, d_v] float32; the PROMPT's q, k, g [b, T, h, d_k], v [b, T, h,
    d_v] and beta [b, T, h], float32, T whole chunks of `CHUNK`; `span` (a
    traced integer) takes the prompt's chunks [span * chunks, (span + 1) *
    chunks). A grid step is a (row, chunk) with every head's tile, read where
    it lies ([T * h, d]: a head's positions are `h` rows apart), the chunks
    innermost: the heads' states stay in VMEM from the span's first chunk to
    its last, and nothing of the span but its inputs, its outputs and the state
    touches HBM. Returns (o [b, chunks * CHUNK, h, d_v] float32, the state
    after the span)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, T, h, dk = q.shape
    dv, c = v.shape[-1], CHUNK
    if not interpret and not decode_kernel_takes(h, dk, dv):
        raise ValueError(f"kda_chunk_fwd takes groups of {_HEADS_PER_CALL} heads of 128-multiples, got {q.shape}")
    whole = pl.BlockSpec((1, h, dk, dv), lambda r, i, span: (r, 0, 0, 0))
    along = lambda *block: pl.BlockSpec((1,) + block, lambda r, i, span: (r, span[0] * chunks + i, 0))
    rows = lambda x: x.reshape(b, T * h, -1)  # (position, head) a row: the bytes as they lie
    o, new_state = pl.pallas_call(
        _chunk_body,  # itself, not a partial of it: one trace of the body serves every layer and every width
        out_shape=(jax.ShapeDtypeStruct((b, chunks * c * h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[whole, along(c * h, dk), along(c * h, dk), along(c * h, dk), along(c * h, dv), along(c, h)],
            out_specs=(pl.BlockSpec((1, c * h, dv), lambda r, i, span: (r, i, 0)), whole),
            scratch_shapes=[pltpu.VMEM((h, dk, dv), jnp.float32)],
            grid=(b, chunks),
        ),
        # operand 0 is the prefetched `span`; the state is operand 1 and result 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(jnp.asarray(span, jnp.int32).reshape(1), state, rows(q), rows(k), rows(g), rows(v), beta)
    return o.reshape(b, chunks * c, h, dv), new_state


def _chunked_by_kernel(q, k, v, g, beta, state, span: int, mode: str):
    """`kda_chunked` with `kda_chunk_fwd` for `_span_chunked`: the same
    padding to whole chunks and to spans of equal length, the same scan over
    spans that carries the state. A span's call reads its chunks out of the
    prompt's arrays: nothing is cut or re-laid a span. Jitted, so that a
    program's layers share one trace and one lowering of it."""
    from trlx_tpu.ops.attention import note_kernel_path

    note_kernel_path("kda_chunk_fwd", mode, q.shape)
    return _spans_by_kernel(q, k, v, g, beta, state, span=span, interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("span", "interpret"))
def _spans_by_kernel(q, k, v, g, beta, state, *, span: int, interpret: bool):
    b, t, h, dk = q.shape
    chunks = -(-t // CHUNK)
    spans = -(-chunks // max(span // CHUNK, 1))
    n = -(-chunks // spans)  # a span's chunks
    T = spans * n * CHUNK
    q, k, v, g, beta = (jnp.pad(x.astype(jnp.float32), ((0, 0), (0, T - t)) + ((0, 0),) * (x.ndim - 2))
                        for x in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32) if state is None else state.astype(jnp.float32)
    one = lambda s, i: kda_chunk_fwd(s, q, k, v, g, beta, i, chunks=n, interpret=interpret)[::-1]
    with jax.named_scope("kda_chunked"):
        if spans == 1:
            state, o = one(state, 0)
            return o[:, :t], state
        state, o = lax.scan(one, state, jnp.arange(spans))  # o [spans, b, L, h, dv]
    return jnp.moveaxis(o, 0, 1).reshape(b, T, h, -1)[:, :t], state
