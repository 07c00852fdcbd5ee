"""Sparse experts: routing (sigmoid scores with a selection bias, or a
softmax over the chosen logits) and grouped dispatch over the experts held
here.

A layer of E experts of which this process holds G (a contiguous range
from `offset`: one chip's share under expert parallelism, or all of them)
scores every token against all E, keeps each token's top-k, and computes
what its own experts add:

    1. route      `route_sigmoid`: s = sigmoid(x W_r) in float32; sel =
                  top_k(s + b); the combine weights are s[sel] renormalized
                  over the k chosen (the selection bias b steers the choice
                  and nothing else). `route_softmax`: r = x W_r in float32;
                  sel = top_k(r); the weights are softmax(r[sel]). The input
                  need not be the one the experts read (`routed_experts`
                  takes the routing as it takes the tokens: SmallThinker
                  routes on a block's input and dispatches behind attention)
    2. dispatch   the T x k assignments sorted by expert, those of experts
                  held elsewhere (and of masked tokens) behind the rest; the
                  rows of x gathered in that order. The row count is static,
                  T x k: whatever the router does, no token is dropped
    3. compute    three grouped matrix products over the G stacks
                  (`grouped_matmul`: row group g against rhs[g]); tiles past
                  the real rows do no work
    4. combine    rows back in token order, weighted, summed over k

Nothing stands in for the experts held elsewhere: their share of the sum is
simply absent, in the forward and in the gradient alike.

`grouped_matmul` is a Pallas kernel on one TPU device (`moe_gmm` forward,
`moe_gmm_dlhs` and `moe_tgmm` backward: the names their events carry in the
device trace) and `jax.lax.ragged_dot` everywhere else, by the rule
`ops.attention.kernel_mode` applies to the other kernels. The kernels walk
the row tiles with the group metadata of jax's megablox
(`make_group_metadata`): tile i belongs to group `group_ids[i]` and covers
row tile `m_tile_ids[i]`; a row tile that two groups share is visited
once by each, and each visit stores only its own rows.
"""

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

STATS = ("local_assignment_share", "tokens_per_expert_max_over_mean", "dropped_tokens")
# beside them where `count_met`: a layer that holds every expert says how many of them a call met
MET_STATS = ("experts_met", "experts_held")
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def _router_logits(x, router_kernel):
    """x W_r in float32 at `highest` precision: a near-tie between two experts
    decides which matrices a token meets, so the scores may not carry
    bfloat16's error."""
    return jnp.matmul(x.astype(jnp.float32), router_kernel.astype(jnp.float32), precision=lax.Precision.HIGHEST)


def route_sigmoid(x, router_kernel, select_bias, top_k: int, n_group: int = 0, topk_group: int = 0):
    """(top_i [T, k] int32, top_w [T, k] float32), scores in float32.
    With `n_group` groups (DeepSeek-V3's `noaux_tc`): the experts lie in
    equal contiguous groups, a group scores the sum of its two largest biased
    scores, and only the experts of the `topk_group` best groups can be chosen."""
    scores = jax.nn.sigmoid(_router_logits(x, router_kernel))
    biased = scores + lax.stop_gradient(select_bias.astype(jnp.float32))
    if n_group:
        tokens, experts = biased.shape
        by_group = biased.reshape(tokens, n_group, experts // n_group)
        group_score = lax.top_k(by_group, 2)[0].sum(-1)  # [T, n_group]
        _, kept = lax.top_k(group_score, topk_group)
        allowed = (kept[:, :, None] == jnp.arange(n_group)[None, None, :]).any(1)  # [T, n_group]
        biased = jnp.where(allowed[:, :, None], by_group, -jnp.inf).reshape(tokens, experts)
    _, top_i = lax.top_k(biased, top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
    return top_i.astype(jnp.int32), top_w


def route_softmax(x, router_kernel, top_k: int):
    """(top_i [T, k] int32, top_w [T, k] float32): the k largest logits of x
    W_r and a softmax over those k: the full softmax renormalised over the
    chosen (the other experts' terms cancel). No bias, no groups, no scale."""
    top_l, top_i = lax.top_k(_router_logits(x, router_kernel), top_k)
    return top_i.astype(jnp.int32), jax.nn.softmax(top_l, axis=-1)


def route(x, router_kernel, select_bias, top_k: int, kind: str = "sigmoid", n_group: int = 0, topk_group: int = 0):
    """(top_i, top_w) under the router `kind` (`TransformerConfig.moe_router`)."""
    if kind == "topk_softmax":
        return route_softmax(x, router_kernel, top_k)
    return route_sigmoid(x, router_kernel, select_bias, top_k, n_group, topk_group)


# ---------------------------------------------------------------------------
# Grouped matrix products
# ---------------------------------------------------------------------------


def _tile(n: int, cap: int) -> Optional[int]:
    """The largest multiple of 128 that divides n and is at most cap."""
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return None


def _tiling(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    tk, tn = _tile(k, 1024), _tile(n, 1024)
    if tk is None or tn is None:
        return None
    return (512 if m >= 2048 else 128), tk, tn


def _metadata(group_sizes, m, tm, visit_empty_groups):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    (offsets, group_ids, m_tile_ids), num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=visit_empty_groups)
    return offsets, group_ids, m_tile_ids, num_tiles


def _gmm_kernel(lhs, rhs, group_sizes, *, transpose_rhs: bool, tiling, name: str, interpret: bool):
    """out[r] = lhs[r] @ W[group of r]: lhs [m, c], out [m, o]. The G
    matrices lie side by side in `rhs`: [c, G * o], W[g] = rhs[:, g * o:
    (g + 1) * o]; with `transpose_rhs` rhs is [o, G * c] and W[g] the
    transpose of its g-th column block. Rows past the groups' end are
    never written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = lhs.shape
    groups = group_sizes.shape[0]
    o = rhs.shape[0] if transpose_rhs else rhs.shape[1] // groups
    tm, tc, to = tiling
    tiles_c, tiles_o = c // tc, o // to
    offsets, group_ids, m_tile_ids, num_tiles = _metadata(group_sizes, m, tm, False)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(offsets_ref, gids_ref, mids_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        tile, c_i = pl.program_id(1), pl.program_id(2)

        @pl.when(c_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                        preferred_element_type=jnp.float32)

        @pl.when(c_i == tiles_c - 1)
        def _():
            gid = gids_ref[tile]
            rows = mids_ref[tile] * tm + lax.broadcasted_iota(jnp.int32, (tm, to), 0)
            mine = (rows >= offsets_ref[gid]) & (rows < offsets_ref[gid + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...].astype(jnp.float32)
                                     ).astype(out_ref.dtype)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((to, tc), lambda o_i, t, c_i, off, g, mi: (o_i, g[t] * tiles_c + c_i))
    else:
        rhs_spec = pl.BlockSpec((tc, to), lambda o_i, t, c_i, off, g, mi: (c_i, g[t] * tiles_o + o_i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, o), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tc), lambda o_i, t, c_i, off, g, mi: (mi[t], c_i)), rhs_spec],
            out_specs=pl.BlockSpec((tm, to), lambda o_i, t, c_i, off, g, mi: (mi[t], o_i)),
            grid=(tiles_o, num_tiles, tiles_c),
            scratch_shapes=[pltpu.VMEM((tm, to), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(offsets, group_ids, m_tile_ids, lhs, rhs)


def _tgmm_kernel(lhs, grad, group_sizes, *, tiling, name: str, interpret: bool):
    """out[:, g * n:(g + 1) * n] = lhs[rows of g]^T @ grad[rows of g]
    ([m, k], [m, n] -> [k, G * n], the layout of `_gmm_kernel`'s rhs); an
    empty group gets zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = grad.shape[1]
    tm, tk, tn = tiling
    groups, tiles_n = group_sizes.shape[0], n // tn
    offsets, group_ids, m_tile_ids, num_tiles = _metadata(group_sizes, m, tm, True)
    count = jnp.reshape(num_tiles, (1,)).astype(jnp.int32)

    def kernel(offsets_ref, gids_ref, mids_ref, count_ref, lhs_ref, grad_ref, out_ref, acc_ref):
        tile = pl.program_id(2)
        gid = gids_ref[tile]
        first = (tile == 0) | (gid != gids_ref[jnp.maximum(tile - 1, 0)])
        last = (tile == count_ref[0] - 1) | (gid != gids_ref[jnp.minimum(tile + 1, count_ref[0] - 1)])

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        start, end = offsets_ref[gid], offsets_ref[gid + 1]

        @pl.when(end > start)
        def _():
            rows = mids_ref[tile] * tm + lax.broadcasted_iota(jnp.int32, (tm, tk), 0)
            mine = (rows >= start) & (rows < end)
            x = jnp.where(mine, lhs_ref[...], jnp.zeros_like(lhs_ref))
            acc_ref[...] += lax.dot_general(x, grad_ref[...], (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((k, groups * n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, t, off, g, mi, cnt: (mi[t], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, t, off, g, mi, cnt: (mi[t], n_i)),
            ],
            out_specs=pl.BlockSpec((tk, tn), lambda n_i, k_i, t, off, g, mi, cnt: (k_i, g[t] * tiles_n + n_i)),
            grid=(tiles_n, k // tk, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(offsets, group_ids, m_tile_ids, count, lhs, grad)


def _pad_rows(x, tm):
    pad = -x.shape[0] % tm
    return x if pad == 0 else jnp.pad(x, ((0, pad), (0, 0)))


def _real_rows(x, group_sizes):
    """x with the rows past the groups' end set to zero: the kernels never
    write them, and what they hold may not reach a sum."""
    rows = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < group_sizes.sum(), x, jnp.zeros_like(x))


def _out_width(rhs, group_sizes) -> int:
    return rhs.shape[1] // group_sizes.shape[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_matmul(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    tiling = _tiling(m, k, _out_width(rhs, group_sizes))
    out = _gmm_kernel(_pad_rows(lhs, tiling[0]), rhs, group_sizes, transpose_rhs=False,
                      tiling=tiling, name="moe_gmm", interpret=interpret)
    return _real_rows(out[:m], group_sizes)


def _kernel_matmul_fwd(lhs, rhs, group_sizes, interpret):
    return _kernel_matmul(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _kernel_matmul_bwd(interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    m, k = lhs.shape
    n = _out_width(rhs, group_sizes)
    grad = grad.astype(lhs.dtype)
    tm = _tiling(m, k, n)[0]
    lhs_p, grad_p = _pad_rows(lhs, tm), _pad_rows(grad, tm)
    dlhs = _gmm_kernel(grad_p, rhs, group_sizes, transpose_rhs=True, tiling=_tiling(m, n, k),
                       name="moe_gmm_dlhs", interpret=interpret)
    drhs = _tgmm_kernel(lhs_p, grad_p, group_sizes, tiling=_tiling(m, k, n), name="moe_tgmm",
                        interpret=interpret)
    return _real_rows(dlhs[:m], group_sizes), drhs.astype(rhs.dtype), None


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


def grouped_matmul(lhs, rhs, group_sizes, mode: Optional[str] = None):
    """[m, k] x G matrices [k, n] -> [m, n]: rows [sum(group_sizes[:g]),
    sum(group_sizes[:g + 1])) of lhs against the g-th column block of `rhs`
    [k, G * n] (the matrices side by side, as the expert stacks are
    stored); rows past the last group give zeros. `mode` is
    `ops.attention.kernel_mode()` unless given: the Pallas kernels on one
    TPU device (or interpreted, on request), `jax.lax.ragged_dot` otherwise
    and for shapes the kernels do not tile."""
    if mode is None:
        from trlx_tpu.ops.attention import kernel_mode

        mode = kernel_mode()
    group_sizes = group_sizes.astype(jnp.int32)
    (m, k), n = lhs.shape, _out_width(rhs, group_sizes)
    if mode in ("pallas", "interpret") and _tiling(m, k, n) and _tiling(m, n, k):
        return _kernel_matmul(lhs, rhs, group_sizes, mode == "interpret")
    stacked = rhs.reshape(k, group_sizes.shape[0], n).transpose(1, 0, 2)
    # masked on the way in as on the way out: on the TPU the gradient that
    # ragged_dot hands its lhs holds whatever was in memory on the rows past
    # the groups' end (input gradient rms 3.1 against the reference's 0.22
    # at 65,536 x 2048, PR 29), and the mask's own transpose clears them
    return _real_rows(lax.ragged_dot(_real_rows(lhs, group_sizes), stacked, group_sizes), group_sizes)


# ---------------------------------------------------------------------------
# Dispatch and combine
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_sorted(x, order, inverse, k):
    """x[order // k]: each token's row once for each of its k assignments,
    in dispatch order. Its transpose is a gather too (by the inverse
    permutation), never a scatter."""
    return x[order // k]


def _gather_sorted_fwd(x, order, inverse, k):
    return x[order // k], (inverse, x.shape[0])


def _gather_sorted_bwd(k, residuals, grad):
    inverse, tokens = residuals
    return grad[inverse].reshape(tokens, k, -1).sum(1), None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _ungather(rows, order, inverse):
    """rows[inverse]: dispatch order back to (token, assignment) order."""
    return rows[inverse]


def _ungather_fwd(rows, order, inverse):
    return rows[inverse], (order,)


def _ungather_bwd(residuals, grad):
    return grad[residuals[0]], None, None


_ungather.defvjp(_ungather_fwd, _ungather_bwd)


def dispatch(top_i, n_experts_held: int, offset: int, token_mask=None):
    """Sort the T x k assignments by expert, those that are not this
    process's (or come from a masked token) last.

    Returns (order, inverse, held, group_sizes): `order[r]` is the flat
    (token * k + j) assignment at dispatch row r, `inverse` its inverse
    permutation, `held` [T, k] marks the assignments computed here,
    `group_sizes` [G] the rows of each expert held."""
    local = top_i - offset
    held = (local >= 0) & (local < n_experts_held)
    if token_mask is not None:
        held = held & (token_mask[:, None] > 0)
    key = jnp.where(held, local, n_experts_held).reshape(-1)
    # two sorts and a compare-and-sum: a TPU scatter walks its rows one by one
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = (key[:, None] == jnp.arange(n_experts_held, dtype=key.dtype)[None]).sum(0, dtype=jnp.int32)
    return order, inverse, held, group_sizes


def routed_experts(x, top_i, top_w, w_gate, w_up, w_down, *, offset: int = 0, act: Callable = jax.nn.silu,
                   token_mask=None, mode: Optional[str] = None, count_met: bool = False):
    """What the experts held add for tokens already routed: x [T, d]; top_i,
    top_w [T, k] (a router's, `route_sigmoid` or `route_softmax`, over
    whatever input the model routes on); w_gate, w_up [d, G * f] and w_down
    [f, G * d], the matrices of experts [offset, offset + G) side by side, in
    the compute type. Returns (y [T, d], stats): y is the part of sum_e w_e
    W2_e(act(W1_e x) * W3_e x) that the experts held give; `stats` are scalars
    named in STATS and, with `count_met`, MET_STATS."""
    tokens, k, held_n = x.shape[0], top_i.shape[1], w_down.shape[1] // x.shape[1]
    order, inverse, held, group_sizes = dispatch(top_i, held_n, offset, token_mask)

    rows = _gather_sorted(x, order, inverse, k)  # [T * k, d]
    hidden = act(grouped_matmul(rows, w_gate, group_sizes, mode)) * grouped_matmul(rows, w_up, group_sizes, mode)
    out = grouped_matmul(hidden, w_down, group_sizes, mode)  # [T * k, d], zeros past the real rows
    out = _ungather(out, order, inverse).reshape(tokens, k, -1)
    weights = jnp.where(held, top_w, 0.0).astype(out.dtype)
    y = jnp.einsum("tk,tkd->td", weights, out)

    real = group_sizes.sum()
    valid = (jnp.float32(tokens) if token_mask is None else (token_mask > 0).sum().astype(jnp.float32))
    mean = real.astype(jnp.float32) / held_n
    stats = {
        "local_assignment_share": real.astype(jnp.float32) / jnp.maximum(valid * k, 1.0),
        "tokens_per_expert_max_over_mean": group_sizes.max().astype(jnp.float32) / jnp.maximum(mean, 1.0),
        # the static row count is T x k, every assignment there is: none can be left out
        "dropped_tokens": jnp.maximum(held.sum() - order.shape[0], 0).astype(jnp.float32),
    }
    if count_met:  # the held experts with at least one row: what a call reads of the stacks
        stats["experts_met"] = (group_sizes > 0).sum().astype(jnp.float32)
        stats["experts_held"] = jnp.float32(held_n)
    return y, stats
