"""True 1F1B pipeline schedule: hand-scheduled value-and-grad with in-pipe
per-microbatch loss.

The GPipe-by-autodiff engine (trlx_tpu/parallel/pipeline.py) returns the
FULL batch's logits to the caller, which computes the loss outside the
pipeline program. That is simple and its backward falls out of autodiff,
but it banks two O(global-batch) artifacts per step: the [B, t, d]
final-stage activation bank (the scan's ys) and — far larger — the
[B, t, V] logits the loss consumes (13 GB at B=64, t=1024, V=50k in f32).
The reference's Apex 1F1B engine has neither: each microbatch's loss and
backward run as soon as its forward finishes, so at most O(S) microbatches
of activations are ever live and logits only ever exist per-microbatch
(reference modeling_nemo_ppo.py:713-731 — get_forward_backward_func with
forward_only=False interleaves fwd/bwd per microbatch).

This module is the TPU-native equivalent: ONE shard_map program whose tick
scan runs the eager-1F1B schedule

    forward  of microbatch f at stage i on tick  t_F(f, i) = f + i
    backward of microbatch b at stage i on tick  t_B(b, i) = b + 2S - 2 - i

so the last stage (i = S-1) runs a microbatch's loss + backward on the
SAME tick as its forward, and the backward wavefront climbs the pipeline
one stage per tick, exactly S-1 ticks behind the forward wavefront's
departure. Every stage does one forward and one backward per tick in
steady state (no parity holes — adjacent ranks are served by the same
tick via the down/up ppermute pair), and the in-flight window at stage i
is 2(S - i) - 1 microbatches, bounded by 2S - 1 *independent of M*.

Because the schedule is hand-written, so is the backward: each stage
stashes only its INPUT activation per in-flight microbatch — a ring
buffer of min(2S-1, M) slots keyed by microbatch index at v=1, or
2Sv-1 slots keyed by forward tick under interleaving (live span
<= 2Sv-2 ticks, so tick-keying never collides) — and the backward tick
recomputes the stage forward under `jax.vjp`: the same recompute cost
autodiff-with-remat pays, but with residual lifetime bounded by the
schedule instead of the scan.
Gradients accumulate in the scan carry; the final psum over the data
(and, under PP x SP, sequence) axes replaces the transpose-inserted
collectives of the autodiff path.

There is no NCCL/MPI or Apex machinery to port: the schedule is pure
`lax.scan` + two `ppermute`s per tick, and XLA overlaps the permutes with
the next tick's compute. fsdp/tensor mesh axes stay GSPMD-auto, so the
stage matmuls and their vjps shard exactly as in the GPipe engine.
"""

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.models.transformer import TransformerConfig, position_ids, train_bias
from trlx_tpu.parallel.pipeline import (
    PIPE_AXIS,
    _apply_layer_stack,
    partial_shard_map,
)

# Reduction axes for cross-device grad/stat sums. "sequence" is present
# (size 1 unless PP x SP) because activations shard over it: each sequence
# shard's vjp yields a PARTIAL param cotangent, reduced with the data-axis
# partials in the same psum. Stage (layer) grads reduce over LAYER_AXES
# only — they stay sharded over "pipe".
GRAD_AXES = ("data", "sequence", PIPE_AXIS)
LAYER_AXES = ("data", "sequence")


def _vary(x):
    """Mark a value as device-varying over the manual axes (jax VMA
    types). Correctness of the whole engine depends on this NOT being a
    no-op — see the CRITICAL note in make_1f1b_grad_fn: an invariant
    input to jax.vjp gets its cotangent implicitly psummed over the
    manual axes, which would corrupt gradients."""
    have = getattr(getattr(x, "aval", None), "vma", None) or frozenset()
    missing = tuple(ax for ax in GRAD_AXES if ax not in have)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def masked_sums(x, m):
    """Per-microbatch accumulators from which finalize_tensor_stats can
    rebuild get_tensor_stats (mean/min/max/std over masked entries)
    exactly: sums + sum-of-squares + masked min/max."""
    return dict(
        s=(x * m).sum(),
        s2=(x * x * m).sum(),
        min=jnp.where(m > 0, x, jnp.inf).min(),
        max=jnp.where(m > 0, x, -jnp.inf).max(),
    )


def gated_reducers(gate):
    """(gsum, gmin, gmax) over the [n_ticks] stat bank: gated to the
    real last-stage ticks and reduced over GRAD_AXES."""

    def gsum(leaf):
        return jax.lax.psum(jnp.where(gate, leaf, 0.0).sum(), GRAD_AXES)

    def gmin(leaf):
        return jax.lax.pmin(jnp.where(gate, leaf, jnp.inf).min(), GRAD_AXES)

    def gmax(leaf):
        return jax.lax.pmax(jnp.where(gate, leaf, -jnp.inf).max(), GRAD_AXES)

    return gsum, gmin, gmax


def finalize_tensor_stats(d, n, gsum, gmin, gmax, count=None):
    """get_tensor_stats from banked masked_sums; std uses the
    algebraically-equal sqrt(E[x^2] - mean^2) form. When the global masked
    `count` is supplied and zero, min/max clamp to 0 (matching the batch
    path utils/modeling.py get_tensor_stats) instead of the +/-inf the
    empty-gated reductions would produce."""
    mean = gsum(d["s"]) / n
    e2 = gsum(d["s2"]) / n
    mn, mx = gmin(d["min"]), gmax(d["max"])
    if count is not None:
        mn = jnp.where(count > 0, mn, 0.0)
        mx = jnp.where(count > 0, mx, 0.0)
    return dict(
        mean=mean,
        min=mn,
        max=mx,
        std=jnp.sqrt(jnp.maximum(e2 - mean * mean, 0.0)),
    )


def cond_or_zeros(pred, fn, args):
    """`lax.cond(pred, fn, zeros)` with the skip branch returning
    VMA-varying zeros of fn's output shapes — the ONE implementation of
    the tick body's slot-skip pattern (loss, embed, fwd, bwd slots), so
    the _vary handling cannot diverge between them. Only legal when `fn`
    contains no collectives (the predicate is device-varying)."""
    shapes = jax.eval_shape(fn, args)

    def skip(_):
        return jax.tree_util.tree_map(
            lambda s: _vary(jnp.zeros(s.shape, s.dtype)), shapes
        )

    return jax.lax.cond(pred, fn, skip, args)


def default_finalize(tick_stats, gate, ctx):
    """Sum-decomposed stats: every leaf is a per-microbatch SUM
    contribution; the final stat is the GRAD_AXES psum of the gated tick
    sums. Losses normalized inside loss_mb (divide by a ctx-borne global
    count) therefore come out exactly equal to the batch-level
    computation."""
    del ctx
    gsum, _, _ = gated_reducers(gate)
    return jax.tree_util.tree_map(gsum, tick_stats)


def make_1f1b_grad_fn(
    model,  # TransformerLM (definitions are pure; only embed/unembed used here)
    cfg: TransformerConfig,
    mesh: Mesh,
    n_microbatches: int,
    loss_mb: Callable,  # (rest, heads, h, tok_mb, mask_mb, mb_batch, ctx) -> (loss_contrib, tick_stats)
    ctx_fn: Optional[Callable] = None,  # (tokens, attn_mask, batch) -> ctx; runs INSIDE shard_map
    finalize_fn: Callable = default_finalize,  # (tick_stats[n_ticks], gate[n_ticks], ctx) -> stats
    freeze_split: int = 0,
    loss_collectives: bool = False,  # loss_mb contains collectives (e.g. the
    # ILQL SP path's sequence all_gather of V) — forces the predicated
    # always-compute loss slot, since a collective may not sit under the
    # lax.cond fast path (its predicate is pipe-varying)
    n_virtual: int = 1,  # interleaved virtual stages per device (the
    # Megatron virtual-PP chunking): device d holds chunks l*S + d for
    # l < n_virtual, a microbatch crosses S*v chunk-stages, and the
    # generalized tick algebra below reduces EXACTLY to the plain engine
    # at v=1 (one code path — the v=1 tests validate the reduction)
) -> Callable:
    """Build fn(stacked, rest, heads, tokens, attn_mask, batch) ->
    (loss, stats, (d_stacked, d_rest, d_heads)).

    - `stacked`: [n_stages, lps, ...] block pytree sharded over "pipe"
      (the permanent pipelined-trainer layout), or
      [n_stages, n_virtual, lps, ...] for the interleaved layout.

    INTERLEAVED 1F1B (n_virtual = v > 1): chunk-stage k = l*S + d lives
    on device d; microbatch m's forward crosses k = 0..Sv-1 at tick
    t_F = E(m) + k with E(m) = (m mod S) + (m div S)*S*v (the wave
    spacing of parallel/pipeline.py interleaved_blocks), and the backward
    of chunk-stage k runs at t_B = E(m) + 2Sv-2 - k. The last chunk-stage
    runs loss + backward on its own forward tick (t_F = t_B there), the
    fwd/bwd rings WRAP (chunk l on device S-1 feeds chunk l+1 on device
    0), the stash keys chunk inputs by their forward tick mod (2Sv-1)
    (live span <= 2Sv-2, so no collision), and chunk gradients accumulate
    into the [v, lps, ...] slice of the carry. Cost: ~v x the stashed
    chunk activations of plain 1F1B; payoff: the measured ~1/v bubble
    (schedule_analysis.onef1b_interleaved_lockstep).
    - `rest`: non-block LM params (embeddings/ln_f/lm_head), replicated
      over the manual axes (fsdp/tensor shard them under GSPMD-auto).
    - `heads`: pytree of extra head params the loss consumes (e.g.
      {"v_head": ...}); pass {} when the loss is LM-only.
    - `tokens`/`attn_mask`: [B, t] int arrays, batch dim sharded over
      "data". B/data_ways must divide into n_microbatches.
    - `batch`: pytree of [B, ...] arrays sliced per microbatch and handed
      to `loss_mb` (old logprobs, advantages, labels, ...).

    `loss_mb` returns this microbatch's CONTRIBUTION to the final scalar
    loss (normalize by a global count carried in `ctx` — computed once by
    `ctx_fn`, which may psum over ("data", "sequence")) plus a pytree of
    per-microbatch stat scalars; `finalize_fn` reduces the [n_ticks] bank
    of those into the final stats dict (`default_finalize` = gated global
    sums).

    The returned loss/stats are replicated; d_stacked keeps the stacked
    sharding; d_rest/d_heads are psummed over GRAD_AXES — embed grads
    arrive from stage 0, unembed/head grads from stage S-1, and tied
    embeddings correctly receive both contributions.
    """
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = mesh_shape[PIPE_AXIS]
    M = int(n_microbatches)
    v = int(n_virtual)
    Sv = S * v
    D = 2 * Sv - 2  # fwd->bwd tick distance of chunk-stage 0
    if v == 1:
        # microbatch-keyed stash (slot = m mod RS): live (f, b) pairs obey
        # f - b < 2S-1, so min(2S-1, M) slots suffice — the tight bound for
        # M < ramp configurations
        RS = min(2 * S - 1, M)
    else:
        # forward-tick-keyed stash (slot = t_F mod RS): a chunk input born
        # at t_F is consumed at t_F + D - 2k <= t_F + D, so D + 1 slots
        # never collide between live entries (chunk index alone is not a
        # key — device d holds v in-flight chunks per microbatch)
        RS = D + 1
    n_ticks = ((M - 1) % S) + ((M - 1) // S) * Sv + 2 * Sv - 1
    # With no GSPMD-auto axis active, the loss head (unembed+loss fwd+vjp,
    # the d x V matmuls) and the embed vjp can run under lax.cond so only
    # the one stage that keeps the result pays for it — removing the ~S x
    # loss-head overcompute of pure where-predication. With auto axes
    # (TP/FSDP inside the pipe program) the branches would contain
    # GSPMD-inserted collectives under a device-varying predicate, so
    # there we keep the predicated always-compute form.
    full_manual = (
        all(mesh_shape.get(ax, 1) == 1 for ax in ("fsdp", "tensor"))
        and not loss_collectives
    )
    # The r4 ramp skip for the stage fwd/bwd slots additionally requires a
    # collective-free stage body: under PP x SP the stage runs RING
    # attention (sequence-axis ppermutes), which may not sit under the
    # pipe-varying cond — there the always-compute slots stay. (The
    # loss/embed conds are unaffected: CE loss_mb and the embed lookup
    # carry no collectives.)
    slot_conds = full_manual and mesh_shape.get("sequence", 1) == 1

    def embed_apply(rest, tok, pos):
        return model.apply({"params": rest}, tok, pos, method=model.embed)

    def inner(stacked, rest, heads, tokens, attn_mask, positions, batch):
        idx = jax.lax.axis_index(PIPE_AXIS)
        # v == 1: [lps, ...] layer stack; v > 1: [v, lps, ...] chunk stack
        my_layers = jax.tree_util.tree_map(lambda x: x[0], stacked)
        lps = jax.tree_util.tree_leaves(my_layers)[0].shape[0 if v == 1 else 1]
        # CRITICAL: the vjps below must see device-VARYING params. Inside a
        # manual shard_map, jax.vjp w.r.t. an invariant (replicated) input
        # auto-inserts a psum over the manual axes so the cotangent can be
        # typed invariant — which would hand every device the SUM of all
        # stages' cotangents (including bubble-tick garbage the per-tick
        # gating could then never remove) and double-count the data axis
        # against the explicit psums at the end. pcast-to-varying keeps
        # each device's cotangent a LOCAL partial; the gated accumulation
        # + one final psum then reduces exactly once.
        my_layers = jax.tree_util.tree_map(_vary, my_layers)
        rest_v = jax.tree_util.tree_map(_vary, rest)
        heads_v = jax.tree_util.tree_map(_vary, heads)

        B, t = tokens.shape
        assert B % M == 0, f"local batch {B} not divisible into {M} microbatches"
        mb = B // M
        ctx = ctx_fn(tokens, attn_mask, batch) if ctx_fn is not None else None

        tok_mbs = tokens.reshape(M, mb, t)
        mask_mbs = attn_mask.reshape(M, mb, t)
        pos_mbs = positions.reshape(M, mb, t)
        batch_mbs = jax.tree_util.tree_map(
            lambda x: x.reshape(M, x.shape[0] // M, *x.shape[1:]), batch
        )

        def stage_fwd(layers, x, mask, pos, layer_offset):
            bias = train_bias(cfg, mask)
            return _apply_layer_stack(
                cfg, layers, x, bias, pos, mask,
                layer_offset=layer_offset, freeze_split=freeze_split,
            )

        def chunk_at(l):
            """This device's chunk l of the layer stack (static slice at
            v == 1, so the plain engine pays no gather)."""
            if v == 1:
                return my_layers
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, l, 0, keepdims=False),
                my_layers,
            )

        def loss_head(rest_, heads_, h_, tok, mask, mb_batch):
            return loss_mb(rest_, heads_, h_, tok, mask, mb_batch, ctx)

        # shapes/dtypes of the activation flowing down (embed output) and
        # its cotangent flowing up — dtype from an abstract eval so the
        # carry matches whatever compute dtype the model emits
        h_shape = jax.eval_shape(
            embed_apply, rest, tok_mbs[0], pos_mbs[0]
        )
        act = lambda: jnp.zeros(h_shape.shape, h_shape.dtype)

        # ring permutes WRAP (chunk-stage k on device S-1 feeds k+1 on
        # device 0); at v == 1 the wrapped edge's payload is never consumed
        # (device 0 always takes the embed input), matching the old
        # line-permute semantics
        fwd_perm = [(s, (s + 1) % S) for s in range(S)]
        bwd_perm = [(s, (s - 1) % S) for s in range(S)]

        zero_grads = jax.tree_util.tree_map(
            jnp.zeros_like, (my_layers, rest, heads)
        )

        def tick(carry, r):
            recv_h, recv_dx, stash, d_layers, d_rest, d_heads, loss_acc = carry

            # ------ forward slot: (microbatch m_f, chunk l_f) ------
            # chunk-stage k = l*S + idx runs microbatch m's forward at tick
            # E(m) + k with E(m) = (m mod S) + (m div S)*Sv; inverting for
            # this device: base/w/q as in pipeline.py interleaved_blocks
            # (q == k, and q = idx when v == 1 — the plain schedule)
            base = jnp.mod(r - idx, S)
            w = (r - base) // Sv
            m_f = base + w * S
            q = r - (jnp.mod(m_f, S) + (m_f // S) * Sv)
            valid_f = (m_f >= 0) & (m_f < M) & (q >= 0) & (q < Sv)
            l_f = 0 if v == 1 else jnp.clip(q // S, 0, v - 1)
            fi = jnp.clip(m_f, 0, M - 1)
            tok_f = jax.lax.dynamic_index_in_dim(tok_mbs, fi, 0, keepdims=False)
            mask_f = jax.lax.dynamic_index_in_dim(mask_mbs, fi, 0, keepdims=False)
            pos_f = jax.lax.dynamic_index_in_dim(pos_mbs, fi, 0, keepdims=False)
            x0 = embed_apply(rest, tok_f, pos_f)
            first_f = (idx == 0) if v == 1 else ((idx == 0) & (l_f == 0))
            x_in = jnp.where(first_f, x0, recv_h)
            chunk_f = chunk_at(l_f)
            off_f = (l_f * S + idx) * lps
            # Ramp ticks skip the stage forward entirely (lax.cond, like
            # the loss/embed slots): during fill/drain a stage then pays
            # only the slot it actually runs, so the engine's wall ramp is
            # ~(S-1) single-width ticks each side — Megatron-1F1B's ideal
            # bubble (S-1)/(M+S-1) — instead of 2(S-1) full double-slot
            # ticks. Full-manual, sequence-free meshes only; under auto
            # axes or PP x SP (ring attention's sequence ppermutes) the
            # branch would wrap collectives in a device-varying predicate.
            if slot_conds:
                y = cond_or_zeros(
                    valid_f,
                    lambda a: stage_fwd(chunk_f, a[0], a[1], a[2], off_f),
                    (x_in, mask_f, pos_f),
                )
            else:
                y = stage_fwd(chunk_f, x_in, mask_f, pos_f, off_f)
            # stash this chunk-stage's INPUT — keyed by microbatch at v=1,
            # by forward tick at v>1 (slot RS is the bubble trash can)
            key_f = m_f if v == 1 else r
            slot = jnp.where(valid_f, jnp.mod(key_f, RS), RS)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, x_in, slot, 0
            )

            # ------ loss + backward slot: (m_b, chunk l_b) ------
            # backward of chunk-stage k runs at E(m) + D - k; invert per
            # candidate chunk l (v is small and static — unrolled)
            if v == 1:
                b = r - D + idx
                valid_b = (b >= 0) & (b < M)
                m_b = b
                l_b = 0
                k_b = idx
            else:
                vals, ms, ls = [], [], []
                for l in range(v):
                    c_l = r - D + l * S + idx
                    beta = jnp.mod(c_l, Sv)
                    m_l = beta + (c_l // Sv) * S
                    val_l = (c_l >= 0) & (beta < S) & (m_l < M)
                    vals.append(val_l)
                    ms.append(jnp.where(val_l, m_l, 0))
                    ls.append(jnp.where(val_l, l, 0))
                valid_b = functools.reduce(jnp.logical_or, vals)
                m_b = sum(ms)
                l_b = sum(ls)
                k_b = l_b * S + idx
            bi = jnp.clip(m_b, 0, M - 1)
            tok_b = jax.lax.dynamic_index_in_dim(tok_mbs, bi, 0, keepdims=False)
            mask_b = jax.lax.dynamic_index_in_dim(mask_mbs, bi, 0, keepdims=False)
            pos_b = jax.lax.dynamic_index_in_dim(pos_mbs, bi, 0, keepdims=False)
            mb_batch_b = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, bi, 0, keepdims=False),
                batch_mbs,
            )

            # loss fires on the LAST chunk-stage (k = Sv-1), whose backward
            # tick IS its forward tick (t_F = t_B there), so `y` is that
            # microbatch's final hidden state; embed grads on chunk-stage 0
            last = (idx == S - 1) if v == 1 else ((idx == S - 1) & (l_b == v - 1))
            first = (idx == 0) if v == 1 else ((idx == 0) & (l_b == 0))

            def loss_slot(args):
                y_, tok_, mask_, mbb = args
                l, lh_vjp, tick_stats = jax.vjp(
                    functools.partial(
                        loss_head, tok=tok_, mask=mask_, mb_batch=mbb
                    ),
                    rest_v, heads_v, y_, has_aux=True,
                )
                dl_rest, dl_heads, dy_last = lh_vjp(
                    _vary(jnp.ones((), l.dtype))
                )
                return l, tick_stats, dl_rest, dl_heads, dy_last.astype(y_.dtype)

            loss_args = (y, tok_b, mask_b, mb_batch_b)
            if full_manual:
                l, tick_stats, dl_rest, dl_heads, dy_last = cond_or_zeros(
                    last & valid_b, loss_slot, loss_args
                )
            else:
                l, tick_stats, dl_rest, dl_heads, dy_last = loss_slot(loss_args)

            # read back the stashed chunk input: v=1 keyed by microbatch;
            # v>1 keyed by its forward tick t_F = E(m_b) + k_b = r - D + 2*k_b
            key_b = bi if v == 1 else jnp.mod(r - D + 2 * k_b, RS)
            x_b = jax.lax.dynamic_index_in_dim(
                stash, jnp.mod(key_b, RS), 0, keepdims=False
            )
            dy_from_loss = (idx == S - 1) if v == 1 else (k_b == Sv - 1)
            dy = jnp.where(dy_from_loss, dy_last, recv_dx)
            chunk_b = chunk_at(l_b)
            off_b = (l_b * S + idx) * lps
            if slot_conds:
                # same ramp skip for the backward slot (see fwd note)
                def bwd_slot(args):
                    x_, dy_, mask_, pos_ = args
                    _, s_vjp = jax.vjp(
                        lambda lp, xx: stage_fwd(lp, xx, mask_, pos_, off_b),
                        chunk_b, x_,
                    )
                    return s_vjp(dy_)

                d_lp, dx = cond_or_zeros(valid_b, bwd_slot, (x_b, dy, mask_b, pos_b))
            else:
                _, s_vjp = jax.vjp(
                    lambda lp, x_: stage_fwd(lp, x_, mask_b, pos_b, off_b),
                    chunk_b, x_b,
                )
                d_lp, dx = s_vjp(dy)

            # embed backward on chunk-stage 0: dx is the cotangent of this
            # stage's input == the embed output
            def embed_slot(args):
                tok_, pos_, dx_ = args
                _, e_vjp = jax.vjp(
                    lambda r_: embed_apply(r_, tok_, pos_), rest_v
                )
                return e_vjp(dx_)[0]

            embed_args = (tok_b, pos_b, dx)
            if full_manual:
                de_rest = cond_or_zeros(first & valid_b, embed_slot, embed_args)
            else:
                de_rest = embed_slot(embed_args)

            # jnp.where (not gate-multiply): bubble slots may hold inf/nan;
            # chunk grads land in the l_b-th slice of the [v, lps, ...] carry
            if v == 1:
                d_layers = jax.tree_util.tree_map(
                    lambda acc, g: acc + jnp.where(valid_b, g, 0.0), d_layers, d_lp
                )
            else:
                d_layers = jax.tree_util.tree_map(
                    lambda acc, g: jax.lax.dynamic_update_index_in_dim(
                        acc,
                        jax.lax.dynamic_index_in_dim(acc, l_b, 0, keepdims=False)
                        + jnp.where(valid_b, g, 0.0),
                        l_b, 0,
                    ),
                    d_layers, d_lp,
                )
            d_rest = jax.tree_util.tree_map(
                lambda acc, gl, ge: acc
                + jnp.where(valid_b & last, gl, 0.0)
                + jnp.where(valid_b & first, ge, 0.0),
                d_rest, dl_rest, de_rest,
            )
            d_heads = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(valid_b & last, g, 0.0),
                d_heads, dl_heads,
            )
            loss_acc = loss_acc + jnp.where(valid_b & last, l, 0.0)

            next_h = jax.lax.ppermute(y, PIPE_AXIS, fwd_perm)
            next_dx = jax.lax.ppermute(dx.astype(y.dtype), PIPE_AXIS, bwd_perm)
            gate = valid_b & last
            return (
                (next_h, next_dx, stash, d_layers, d_rest, d_heads, loss_acc),
                (tick_stats, gate),
            )

        init = jax.tree_util.tree_map(
            _vary,
            (
                act(), act(),
                jnp.zeros((RS + 1,) + h_shape.shape, h_shape.dtype),
                *zero_grads,
                jnp.zeros((), jnp.float32),
            ),
        )
        carry, (tick_stats, gate) = jax.lax.scan(
            tick, init, jnp.arange(n_ticks)
        )
        _, _, _, d_layers, d_rest, d_heads, loss_acc = carry

        loss = jax.lax.psum(loss_acc, GRAD_AXES)
        stats = finalize_fn(tick_stats, gate, ctx)
        # stage grads stay per-stage (pipe-sharded); data/sequence-
        # replicated params need the reduction autodiff's transpose
        # would insert
        d_stacked = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, LAYER_AXES)[None], d_layers
        )
        d_rest = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, GRAD_AXES), d_rest
        )
        d_heads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, GRAD_AXES), d_heads
        )
        return loss, stats, d_stacked, d_rest, d_heads

    # batch dim over "data", sequence dim over "sequence" (size 1 except
    # PP x SP, where the stage runs ring attention and the loss consumes
    # globally-preshifted per-position targets). Position ids come from
    # the GLOBAL mask before the shard_map — a shard-local cumsum would
    # restart at 0 per sequence shard.
    b_spec = P("data", "sequence")
    smap = partial_shard_map(
        inner,
        mesh,
        in_specs=(P(PIPE_AXIS), P(), P(), b_spec, b_spec, b_spec, b_spec),
        out_specs=(P(), P(), P(PIPE_AXIS), P(), P()),
        compute_dtype=cfg.dtype,
    )

    def fn(stacked, rest, heads, tokens, attn_mask, batch):
        loss, stats, d_stacked, d_rest, d_heads = smap(
            stacked, rest, heads, tokens, attn_mask,
            position_ids(attn_mask), batch,
        )
        return loss, stats, (d_stacked, d_rest, d_heads)

    return fn
