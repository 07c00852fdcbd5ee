"""Fused log-probability of labels over a large vocabulary.

`logprobs_of_labels` (label logit minus logsumexp) is the single hottest
non-matmul op in RLHF: PPO scoring runs it over [batch, seq, vocab~50k]
logits for policy AND reference (reference: log_softmax + gather,
trlx utils/modeling.py logprobs_of_labels, used at
accelerate_ppo_trainer.py:440-446), and the CE losses are the same
computation. The naive form materializes a full [N, V] float32
log_softmax intermediate — pure HBM traffic.

Two fused tiers (same dispatch pattern as ops/attention.py):

1. Pallas TPU kernel: grid over (row blocks, vocab blocks) with online
   logsumexp accumulators in VMEM — the label logit and the logsumexp are
   accumulated in one streaming pass over the vocab; nothing of size
   [N, V] is ever written.
2. XLA path: gather-then-logsumexp (`take_along_axis(logits) - lse`),
   which XLA fuses into reductions without a normalized-probs
   intermediate; used on CPU/multi-chip and as the recompute building
   block of the backward.

The backward is shared: d/dlogits = g * (onehot(labels) - softmax(logits)),
computed from the saved logsumexp (no second reduction pass).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.ops import attention

NEG_INF = -1e30


def _lse_xla(logits32: jnp.ndarray) -> jnp.ndarray:
    return jax.scipy.special.logsumexp(logits32, axis=-1)


def _logprobs_xla(logits: jnp.ndarray, labels: jnp.ndarray):
    """[N, V] x [N] -> ([N] logprobs, [N] lse), no [N, V] intermediate
    beyond the f32 cast XLA fuses into the reductions."""
    logits32 = logits.astype(jnp.float32)
    lse = _lse_xla(logits32)
    label_logit = jnp.take_along_axis(logits32, labels[:, None], axis=-1)[:, 0]
    return label_logit - lse, lse


def _fused_kernel(logits_ref, labels_ref, out_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, block_v, n_vblocks, vocab):
    import jax.experimental.pallas as pl

    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = logits_ref[...].astype(jnp.float32)  # [R, Vb]
    labels = labels_ref[...]  # [R, 128] (label duplicated across lanes)
    cols = kk * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # the grid is ceil(v / block_v): the last block may read past the vocab
    # edge (Pallas pads with garbage) — mask the tail out
    x = jnp.where(cols < vocab, x, NEG_INF)
    hit = cols == labels[:, :1]  # each label lands in exactly one vocab block
    acc_ref[...] += jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)

    m_prev = m_ref[...]  # [R, 128]
    m_cur = jnp.max(x, axis=1, keepdims=True)  # [R, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(x - m_new[:, :1]), axis=1, keepdims=True
    )
    m_ref[...] = m_new

    @pl.when(kk == n_vblocks - 1)
    def _done():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = lse.astype(lse_ref.dtype)
        out_ref[...] = (acc_ref[...] - lse).astype(out_ref.dtype)


def _logprobs_pallas(logits, labels, block_rows=256, block_v=2048, interpret=False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, v = logits.shape
    # Blocks need not divide the array (the grid is a ceiling; the kernel
    # masks the vocab tail, Pallas clips row-tail writes), but TPU lowering
    # requires block dims be multiples of (8, 128) or equal to the array's.
    br = block_rows if n >= block_rows else n
    bv = block_v if v >= block_v else v
    n_vblocks = -(-v // bv)

    labels_l = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n, 128))
    kernel = functools.partial(_fused_kernel, block_v=bv, n_vblocks=n_vblocks, vocab=v)
    out, lse = pl.pallas_call(
        kernel,
        grid=(-(-n // br), n_vblocks),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, kk: (i, kk)),
            pl.BlockSpec((br, 128), lambda i, kk: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 128), lambda i, kk: (i, 0)),
            pl.BlockSpec((br, 128), lambda i, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),  # running max
            pltpu.VMEM((br, 128), jnp.float32),  # running sumexp
            pltpu.VMEM((br, 128), jnp.float32),  # label-logit accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_ce_fwd",
    )(logits, labels_l)
    return out[:, 0], lse[:, 0]


def fused_logprobs_sharded(mesh, logits, labels, interpret=False):
    """The streaming-vocab kernel under a multi-chip mesh: rows shard over
    (data, fsdp) and the VOCAB shards over tensor (the TP lm_head output
    layout, so no all-gather of the [N, V] logits is forced). Each shard
    runs the kernel on its local vocab block with labels offset into the
    local range (out-of-shard labels hit nothing -> zero contribution);
    the per-shard partial results combine exactly:
        label_logit = psum(acc)           (one shard owns each label)
        lse         = logsumexp over shards (max-shifted psum of exps)
    Full-manual shard_map. Returns (logprobs [N], lse [N])."""
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.ops.attention import pallas_shard_map

    v = logits.shape[-1]
    v_local = v // dict(mesh.shape)["tensor"]

    def local_fn(logits_l, labels_g):
        start = jax.lax.axis_index("tensor") * v_local
        # Labels outside this shard's [start, start+v_local) range become
        # -1: the kernel's grid may pad the local vocab up to block_v, and
        # an off-shard label landing in that phantom tail would otherwise
        # match a NEG_INF-masked column and poison the psum.
        in_shard = (labels_g >= start) & (labels_g < start + v_local)
        labels_l = jnp.where(in_shard, labels_g - start, -1)
        out_l, lse_l = _logprobs_pallas(logits_l, labels_l, interpret=interpret)
        label_logit = jax.lax.psum(out_l + lse_l, "tensor")  # acc; 0 off-shard
        m = jax.lax.pmax(lse_l, "tensor")
        lse = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), "tensor"))
        return label_logit - lse, lse

    rows = P(("data", "fsdp"))
    return pallas_shard_map(
        local_fn,
        mesh,
        in_specs=(P(("data", "fsdp"), "tensor"), rows),
        out_specs=(rows, rows),
    )(logits, labels)


def _sharded_ce_ok(mesh, n: int, v: int) -> bool:
    sizes = dict(mesh.shape)
    dp = sizes["data"] * sizes["fsdp"]
    tp = sizes["tensor"]
    return n % dp == 0 and v % tp == 0 and (v // tp) >= 8


@jax.custom_vjp
def _fused_logprobs_2d(logits, labels):
    out, _ = _fused_fwd_dispatch(logits, labels)
    return out


def _fused_fwd_dispatch(logits, labels):
    mode = attention.kernel_mode()
    if mode == "pallas":
        attention.note_kernel_path("fused_ce", "pallas", logits.shape)
        return _logprobs_pallas(logits, labels)
    mesh = attention.active_pallas_mesh() if mode == "sharded" else None
    if mesh is not None and _sharded_ce_ok(mesh, *logits.shape):
        attention.note_kernel_path("fused_ce", "sharded", logits.shape)
        return fused_logprobs_sharded(mesh, logits, labels)
    attention.note_kernel_path("fused_ce", "xla", logits.shape)
    return _logprobs_xla(logits, labels)


def _fused_fwd(logits, labels):
    out, lse = _fused_fwd_dispatch(logits, labels)
    return out, (logits, labels, lse)


def _fused_bwd(res, g):
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == labels[:, None]
    ).astype(jnp.float32)
    dlogits = (g[:, None] * (onehot - p)).astype(logits.dtype)
    return dlogits, None


_fused_logprobs_2d.defvjp(_fused_fwd, _fused_bwd)


def fused_logprobs_of_labels(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Log-probabilities of `labels` under `logits` without materializing a
    [.., V] log_softmax. logits: [..., V] (any leading shape), labels:
    matching leading shape, int. Returns float32 of the leading shape.

    Out-of-range labels (e.g. an ignore_index like -100) are clamped into
    [0, V) so both dispatch paths agree; callers mask ignored positions
    out of their loss themselves (as causal_lm_ce_loss does)."""
    lead = logits.shape[:-1]
    v = logits.shape[-1]
    n = int(np.prod(lead)) if lead else 1
    labels = jnp.clip(labels.reshape(n).astype(jnp.int32), 0, v - 1)
    out = _fused_logprobs_2d(logits.reshape(n, v), labels)
    return out.reshape(lead)
