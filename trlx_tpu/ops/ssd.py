"""The Mamba-2 recurrence (SSD, state-space duality): a scalar decay a head.

One head keeps a matrix h in R^{N x P}, float32 (N = `d_state`, P = the
head's width). A token brings an input x in R^P, a step dt >= 0, a write
direction B and a read direction C in R^N; the head has one rate A < 0:

    h_t = exp(dt_t A) h_{t-1} + B_t (dt_t x_t)^T
    y_t = h_t^T C_t

It is the delta rule of `ops/linear_attention.py` without the delta term and
with one decay for all of a head's channels (k = B_t, v = dt_t x_t, q = C_t,
g = dt_t A on every channel). B and C come in `groups`: head i reads group
i // (heads / groups). The skip `D x_t` and the gate are the layer's
(`models/transformer.py:Mamba2Mixer`), not the recurrence's. A position with
dt = 0 is the identity on h (decay 1, write 0): that is what a padded
position is given, on either side of the real tokens.

Three forms, equal on the same inputs (tests/test_ssd_ops.py):

`ssd_recurrent`   the definition, a `lax.scan` over positions.
`ssd_chunked`     plain `jax.numpy`, a scan over chunks of `chunk` positions
                  that carries the state, differentiable: what a forward
                  without a cache and a prompt's prefill run. With G_i the
                  log-decays dt A cumulated from the chunk's start,

                      Y = (C h_0) * exp(G) + [(C B^T) * L] (dt x),   L_ij = exp(G_i - G_j), j <= i
                      h_c = exp(G_c) h_0 + (B * exp(G_c - G))^T (dt x)

                  Exact for ANY dt A <= 0: every exponent taken is a
                  difference of cumulated log-decays that is at most 0, so no
                  factor exceeds 1 however fast a head forgets. C B^T is
                  formed a group, not a head. Every temporary is a chunk's,
                  whatever the prompt's length.
`ssd_decode`      one position for every row of a slot pool as ONE Pallas
                  kernel: a row's state is read once and written once, in
                  place (`input_output_aliases`); a row whose `live` bit is 0
                  keeps its state to the bit. Off the TPU, or for head
                  counts the kernel's tiling does not take, `ssd_step` is the
                  same step in `jax.numpy`.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# the decode kernel tiles as `kda_decode` does (4 vectors x 32 heads fill the 128 rows of one
# in-kernel transpose; 128-multiples a head): one rule, `decode_kernel_takes(heads, d_state, d_head)`
from trlx_tpu.ops.linear_attention import _HEADS_PER_CALL, _VMEM_LIMIT, decode_kernel_takes  # noqa: F401

CHUNK = 128  # positions a step of the chunked form's scan takes (`mamba_chunk_size`)
_HIGHEST = lax.Precision.HIGHEST


def _by_head(v, heads: int):
    """B or C [..., groups, N] -> [..., heads, N]: head i reads group i // (heads / groups)."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_step(state, x, dt, A, B, C):
    """One position: state [..., h, N, P] float32; x [..., h, P]; dt [..., h];
    A [h]; B, C [..., groups, N]. Returns (y [..., h, P] float32, new state)."""
    x, dt, A, B, C = (v.astype(jnp.float32) for v in (x, dt, A, B, C))
    heads = x.shape[-2]
    new = state * jnp.exp(dt * A)[..., None, None] \
        + _by_head(B, heads)[..., :, None] * (dt[..., None] * x)[..., None, :]
    return jnp.einsum("...np,...n->...p", new, _by_head(C, heads), precision=_HIGHEST), new


def ssd_recurrent(x, dt, A, B, C, state=None):
    """x [b, t, h, P]; dt [b, t, h]; A [h]; B, C [b, t, groups, N]; state
    [b, h, N, P] or None (zeros). Returns (y [b, t, h, P] float32, final
    state float32)."""
    b, t, h, P = x.shape
    if state is None:
        state = jnp.zeros((b, h, B.shape[-1], P), jnp.float32)

    def one(s, inputs):
        x_t, dt_t, B_t, C_t = inputs
        y, s = ssd_step(s, x_t, dt_t, A, B_t, C_t)
        return s, y

    by_time = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C))
    state, y = lax.scan(one, state.astype(jnp.float32), by_time)
    return jnp.moveaxis(y, 0, 1), state


def _chunk(state, inputs, A):
    """One chunk of `ssd_chunked`: state [b, g, r, N, P] (heads as groups x
    heads a group); x [b, c, g, r, P]; dt [b, c, g, r]; B, C [b, c, g, N]."""
    x, dt, B, C = inputs
    c = x.shape[1]
    G = jnp.cumsum(dt * A, axis=1)  # [b, c, g, r], <= 0, the position's own decay included
    i = jnp.arange(c)
    pair = jnp.where((i[:, None] >= i[None, :])[None, :, :, None, None],
                     jnp.exp(jnp.minimum(G[:, :, None] - G[:, None, :], 0.0)), 0.0)  # [b, i, j, g, r]
    cb = jnp.einsum("bign,bjgn->bijg", C, B, precision=_HIGHEST)
    xdt = x * dt[..., None]
    y = (jnp.einsum("bijgr,bjgrp->bigrp", cb[..., None] * pair, xdt, precision=_HIGHEST)
         + jnp.einsum("bign,bgrnp->bigrp", C, state, precision=_HIGHEST) * jnp.exp(G)[..., None])
    on = jnp.exp(G[:, -1:] - G)  # from a position on to the chunk's end
    state = (jnp.exp(G[:, -1])[..., None, None] * state
             + jnp.einsum("bjgn,bjgrp->bgrnp", B, xdt * on[..., None], precision=_HIGHEST))
    return state, y


def ssd_chunked(x, dt, A, B, C, state=None, chunk: int = CHUNK):
    """`ssd_recurrent`'s numbers, a chunk at a time, for any dt A <= 0. The
    same arguments; t is padded up to whole chunks with identity positions
    (dt = 0). A gradient keeps a chunk's state and recomputes the chunk."""
    b, t, h, P = x.shape
    g, N = B.shape[-2:]
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t
    f32 = lambda v: jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
    # [n, b, chunk, ...], a head as (group, head of the group)
    by_chunk = lambda v, *tail: jnp.moveaxis(f32(v).reshape(b, n, chunk, *tail), 1, 0)
    inputs = (by_chunk(x, g, h // g, P), by_chunk(dt, g, h // g), by_chunk(B, g, N), by_chunk(C, g, N))
    if state is None:
        state = jnp.zeros((b, h, N, P), jnp.float32)
    state = state.astype(jnp.float32).reshape(b, g, h // g, N, P)
    A = A.astype(jnp.float32).reshape(g, h // g)
    with jax.named_scope("ssd_chunked"):
        state, y = lax.scan(jax.checkpoint(lambda s, v: _chunk(s, v, A)), state, inputs)  # y [n, b, chunk, g, r, P]
    return jnp.moveaxis(y, 0, 1).reshape(b, n * chunk, h, P)[:, :t], state.reshape(b, h, N, P)


# ---------------------------------------------------------------------------
# The decode step as one kernel
# ---------------------------------------------------------------------------


def _decode_body(live_ref, state_ref, cols_ref, v_ref, out_state_ref, y_ref, *, heads: int):
    from jax.experimental import pallas as pl

    row = pl.program_id(0)

    @pl.when(live_ref[row] == 0)
    def _():
        out_state_ref[...] = state_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live_ref[row] != 0)
    def _():
        # rows (vector, head) x lanes N -> a vector's head h is column vector * heads + h
        cols = cols_ref[0].T  # [N, 4 * heads]
        for h in range(heads):
            col = lambda vec: cols[:, vec * heads + h:vec * heads + h + 1]  # [N, 1]
            new = state_ref[0, h] * col(2) + col(1) * v_ref[0, h:h + 1, :]
            out_state_ref[0, h] = new
            y_ref[0, h:h + 1, :] = jnp.sum(new * col(0), axis=0, keepdims=True)


def ssd_decode(state, x, dt, A, B, C, live, *, interpret: bool = False):
    """One position a row. state [rows, h, N, P] float32 (donate it: the
    result's state is written over it); x [rows, h, P]; dt [rows, h]; A [h];
    B, C [rows, groups, N]; live [rows] (0: the row has no token, its state is
    left as it is and its output is 0). Returns (y [rows, h, P] float32,
    state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h, N, P = state.shape
    hb = h if interpret and h < _HEADS_PER_CALL else _HEADS_PER_CALL
    if h % hb or (not interpret and not decode_kernel_takes(h, N, P)):
        raise ValueError(f"ssd_decode takes groups of {_HEADS_PER_CALL} heads of 128-multiples, got {state.shape}")
    f32 = lambda v: v.astype(jnp.float32)
    # the vectors that act along N, (vector, head) on the rows so that ONE transpose in
    # the kernel turns them all into columns: C, B, the head's decay on every channel, 0
    groups = lambda v: v.reshape(rows, h // hb, 1, hb, N)
    decay = jnp.broadcast_to(jnp.exp(f32(dt) * f32(A))[..., None], (rows, h, N))
    cols = jnp.concatenate([groups(_by_head(f32(C), h)), groups(_by_head(f32(B), h)), groups(decay),
                            jnp.zeros((rows, h // hb, 1, hb, N), jnp.float32)], axis=2).reshape(rows, 4 * h, N)
    spec = lambda *block: pl.BlockSpec(block, lambda r, j, live: (r, j) + (0,) * (len(block) - 2))
    new_state, y = pl.pallas_call(
        functools.partial(_decode_body, heads=hb),
        out_shape=(jax.ShapeDtypeStruct(state.shape, jnp.float32), jax.ShapeDtypeStruct((rows, h, P), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[spec(1, hb, N, P), spec(1, 4 * hb, N), spec(1, hb, P)],
            out_specs=(spec(1, hb, N, P), spec(1, hb, P)),
            grid=(rows, h // hb),
        ),
        # operand 0 is the prefetched `live`; the state is operand 1 and result 0
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_decode",
    )(live.astype(jnp.int32), f32(state), cols, f32(dt)[..., None] * f32(x))
    return y, new_state


def ssd_decode_step(state, x, dt, A, B, C, live, mode: Optional[str]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The decode step of a slot pool by `mode`: "pallas" (the compiled
    kernel; it raises where its tiling does not fit, `decode_kernel_takes`,
    which the engine asks first and counts as a fallback) | "interpret" |
    None (`ssd_step`, a masked row's state kept)."""
    if mode in ("pallas", "interpret"):
        return ssd_decode(state, x, dt, A, B, C, live, interpret=mode == "interpret")
    y, new = ssd_step(state, x, dt, A, B, C)
    keep = (live > 0)[:, None, None, None]
    return jnp.where(keep[..., 0], y, 0.0), jnp.where(keep, new, state)
