"""Fused attention for the TPU hot path.

The reference delegates fused/flash attention to NeMo TransformerEngine
hooks (SURVEY.md §2.6: nemo cfg `transformer_engine`,
nemo_ppo_trainer.py:348-349) — a CUDA dependency. Here it is a first-class
op with three tiers:

1. `flash_attention` — Pallas TPU kernels (blockwise online-softmax, grid
   over (batch*heads, q-blocks, kv-blocks), VMEM accumulators), forward
   AND backward: the forward saves (out, lse) and the FlashAttention-2
   backward recomputes p = exp(s - lse) blockwise in two kernels (dq;
   dk/dv), so peak memory never materializes the [t, t] score matrix in
   either direction. Off-TPU the same backward algorithm runs as plain
   XLA scans (`_flash_bwd_xla`) — primal-only math either way, which is
   what makes long-context training possible at all: autodiff through
   the blockwise scan saves every block's attention probabilities
   (O(t^2) residuals) and OOMs a 12-layer GPT-2 at seq 8192.
2. `blockwise_attention` — pure-XLA `lax.scan` over KV blocks with the
   same online-softmax math. Differentiable, runs anywhere (CPU tests),
   and is the building block ring attention reuses per ring hop
   (trlx_tpu/ops/ring_attention.py).
3. the naive einsum path in models/transformer.py for short sequences
   where fusion doesn't matter.

Layouts: q, k, v are [b, t, nh, hd] (model layout); `mask` is the [b, S]
key-validity mask. Causal structure is computed from block indices inside
the kernel instead of an O(t^2) bias tensor.
"""

import functools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

NEG_INF = -1e30


def _pick_block(n: int, target: int = 128) -> int:
    """Largest divisor of n that is <= target (TPU-friendly when n is a
    multiple of 128; degrades gracefully for tiny test shapes)."""
    b = min(n, target)
    while n % b != 0:
        b -= 1
    return b


# Auto block sizes (block_q/block_k = None). Big blocks matter: at
# gpt2-small shape (hd 64) the per-cell matmuls are tiny and the kernel
# is grid-overhead/VPU-bound — measured on v5e at seq 2048, 128x128
# blocks run ~5 TF/s, 1024-2048 blocks ~14 TF/s (2.7x faster than
# jax.experimental's builtin TPU flash at the same shape). The backward
# keeps 512 blocks: it holds four [bq, bk] f32 tiles (s/p/dp/ds) in VMEM.
FWD_BLOCK = 1024
BWD_BLOCK = 512
WINDOW_BLOCK = 512  # the banded forward's blocks: see `_flash_fwd_pallas`


def _auto_block(n: int, requested, target: int) -> int:
    return _pick_block(n, target if requested is None else requested)


# ---------------------------------------------------------------------------
# Tier 2: blockwise XLA attention (differentiable reference + ring building
# block). Online softmax: carry (acc, m, l) across KV blocks.
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, bias, acc, m, l, scale):
    """One online-softmax update. q: [b, tq, nh, hd]; k, v: [b, tk, nh, hd];
    bias: broadcastable to [b, nh, tq, tk] additive f32 (0 or NEG_INF);
    acc: [b, tq, nh, hd] f32; m, l: [b, nh, tq] f32. Returns updated
    (acc, m, l)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale + bias
    m_cur = jnp.max(s, axis=-1)  # [b, nh, tq]
    m_new = jnp.maximum(m, m_cur)
    # Fully-masked-so-far rows keep m == NEG_INF; exp(s - NEG_INF) would
    # explode to exp(0)=1 on masked entries, so clamp the shift.
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - shift[..., None])  # [b, nh, tq, tk]
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    correction = jnp.exp(m - m_new)
    correction = jnp.where(m <= NEG_INF / 2, 0.0, correction)
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def _finalize(acc, l):
    l_t = l.transpose(0, 2, 1)[..., None]  # [b, tq, nh, 1]
    # Safe denominator, not just a clamp: with `maximum(l, 1e-30)` the
    # backward of the (unselected) division branch multiplies upstream
    # grads by 1e30 for fully-masked query rows (e.g. left-padding), which
    # overflows to inf/NaN in the surrounding sums even though the forward
    # is a clean 0.
    l_safe = jnp.where(l_t > 0, l_t, 1.0)
    return jnp.where(l_t > 0, acc / l_safe, 0.0)


def init_carry(q32: jnp.ndarray):
    """Fresh online-softmax carry (acc, m, l), derived from q so it carries
    q's sharding/varying-axes type (required for scan carries under
    shard_map)."""
    zero_rows = jnp.transpose(q32[..., 0], (0, 2, 1)) * 0.0  # [b, nh, tq]
    return (q32 * 0.0, zero_rows + NEG_INF, zero_rows)


def blockwise_update(
    q32: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    carry,
    causal: bool = True,
    block_k: int = 128,
    q_offset=0,
    k_offset=0,
    window: Optional[int] = None,
):
    """Fold one KV chunk into an online-softmax carry, scanning the chunk in
    `block_k` blocks. `q_offset`/`k_offset` shift the causal comparison for
    ring/sharded use (global position = local index + offset; offsets may be
    traced scalars). Returns the updated carry — `_finalize` turns it into
    the attention output."""
    b, tq, nh, hd = q32.shape
    tk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv  # GQA: kv stays at nkv heads; repeat per block only
    scale = 1.0 / np.sqrt(hd)
    bk = _pick_block(tk, block_k if block_k is not None else 128)
    nblocks = tk // bk

    rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)  # [tq, 1]
    kb = k.reshape(b, nblocks, bk, nkv, hd)
    vb = v.reshape(b, nblocks, bk, nkv, hd)
    maskb = None if mask is None else mask.reshape(b, nblocks, bk)

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, mblk, idx = blk
        if group > 1:
            kblk = jnp.repeat(kblk, group, axis=2)  # [b, bk, nh, hd] temp
            vblk = jnp.repeat(vblk, group, axis=2)
        cols = k_offset + idx * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        allowed = jnp.ones((tq, bk), dtype=bool)
        if causal:
            allowed = cols <= rows  # [tq, bk]
        if window is not None:
            allowed = allowed & (rows - cols < window)
        bias = jnp.where(allowed, 0.0, NEG_INF)[None, None]  # [1, 1, tq, bk]
        if mblk is not None:
            bias = bias + jnp.where(mblk[:, None, None, :].astype(bool), 0.0, NEG_INF)
        acc, m, l = _attend_block(q32, kblk, vblk, bias, acc, m, l, scale)
        return (acc, m, l), None

    xs = (
        kb.transpose(1, 0, 2, 3, 4),
        vb.transpose(1, 0, 2, 3, 4),
        None if maskb is None else maskb.transpose(1, 0, 2),
        jnp.arange(nblocks),
    )
    carry, _ = jax.lax.scan(body, carry, xs)
    return carry


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = True,
    block_k: int = 128,
    q_offset=0,
    k_offset=0,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Memory-efficient attention: scan over KV blocks, never building the
    full [t, S] matrix as a saved residual. Differentiable (scan autodiff).
    `window`: query i sees keys j with 0 <= i - j < window."""
    q32 = q.astype(jnp.float32)
    carry = blockwise_update(
        q32, k, v, mask, init_carry(q32),
        causal=causal, block_k=block_k, q_offset=q_offset, k_offset=k_offset, window=window,
    )
    acc, _, l = carry
    return _finalize(acc, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Tier 1: Pallas TPU kernel (forward). Grid (b*nh, nq, nk); VMEM scratch
# carries (m, l, acc) across the kv-block dimension of the grid.
# ---------------------------------------------------------------------------



def _block_allowed(mask_ref, qb, kb, block_q: int, block_k: int, causal: bool,
                   window: Optional[int] = None):
    """Key-validity + causal structure for one (q-block, k-block) pair —
    the single mask-construction policy shared by all four Pallas kernels
    (fwd, fwd+lse, bwd dq, bwd dkv); the Pallas-vs-XLA parity tests
    require these to stay bit-identical."""
    valid = mask_ref[0] > 0  # [1, bk] int mask row
    allowed = jnp.broadcast_to(valid, (block_q, block_k))
    if causal:
        rows = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        allowed = allowed & (cols <= rows)
        if window is not None:
            allowed = allowed & (rows - cols < window)
    return allowed


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
                      *, scale, causal, block_q, block_k, window=None):
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: the whole kv block is in the future of the whole q block →
    # nothing to do. (Predicated out rather than skipped — grid is static.)
    run = jnp.asarray(True)
    if causal:
        run = (kb * block_k) <= (qb * block_q + block_q - 1)
    if window is not None:
        # the band: a kv block that ends `window` or more behind the q
        # block's first row holds no key any of its rows may see
        run = run & ((kb * block_k + block_k - 1) > (qb * block_q - window))

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        allowed = _block_allowed(mask_ref, qb, kb, block_q, block_k, causal, window)
        s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_scr[:, 0]  # [bq]
        l_prev = l_scr[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finalize_out():
        l = l_scr[:, 0]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


def _flash_fwd_pallas(q, k, v, mask, causal, block_q, block_k, interpret=False, window=None):
    """Value heads may be narrower than query/key heads (a latent layer's
    decompressed prefill: 192 against 128): the scores are scaled by the
    query/key width and the output is as wide as v. Such a call is named
    `flash_fwd_latent` in the device trace.
    `window` (causal only): query i sees keys j with 0 <= i - j < window.
    The kv blocks outside a q block's band are neither computed (the
    kernel's `run`) nor fetched: their index is held on the band's nearest
    block, and a repeated block index moves no data. Such a call is named
    `flash_fwd_window` in the device trace (`flash_fwd_latent_window` with
    narrower value heads); its blocks are `WINDOW_BLOCK` wide at most, so
    that a band narrower than a forward block is not rounded up to one."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, nh, hd = q.shape
    tk, nkv, hv = k.shape[1], k.shape[2], v.shape[3]
    group = nh // nkv
    target = FWD_BLOCK if window is None else min(FWD_BLOCK, max(WINDOW_BLOCK, window))
    bq = _auto_block(tq, block_q, target)
    bk = _auto_block(tk, block_k, target)
    nq, nk = tq // bq, tk // bk
    scale = 1.0 / np.sqrt(hd)

    # [b*nh, t, hd] q layout; k/v stay at [b*nkv, t, hd] — the index maps
    # below route each q-head grid slot to its kv head (GQA) and each
    # batch-head slot to its batch's mask row, with zero duplication in HBM.
    qh = q.transpose(0, 2, 1, 3).reshape(b * nh, tq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hv)
    if mask is None:
        mask = jnp.ones((b, tk), jnp.int32)
    maskh = mask.astype(jnp.int32)[:, None, :]  # [b, 1, tk]
    name = "flash_fwd" if window is None else "flash_fwd_window"
    if hv != hd:  # a banded latent layer's decompressed prefill is both
        name = "flash_fwd_latent" if window is None else "flash_fwd_latent_window"

    def kv_index(i, j, kk):
        if window is not None:
            first = jnp.maximum(j * bq - window + 1, 0) // bk  # the band of q block j
            kk = jnp.clip(kk, first, (j * bq + bq - 1) // bk)
        return ((i // nh) * nkv + (i % nh) // group, kk, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hv), kv_index),
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // nh, 0, kk)),
        ],
        out_specs=pl.BlockSpec((1, bq, hv), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * nh, tq, hv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # m (broadcast over lanes)
            pltpu.VMEM((bq, 128), jnp.float32),  # l
            pltpu.VMEM((bq, hv), jnp.float32),   # acc
        ],
        interpret=interpret,
        name=name,
    )(qh, kh, vh, maskh)
    return out.reshape(b, nh, tq, hv).transpose(0, 2, 1, 3)


KERNELS_ENV = "TRLX_TPU_KERNELS"

# The mesh the current trainer's programs run over, registered by
# MeshRuntime.from_config (standard and pipe meshes alike). None means no
# trainer has built a mesh: computations then run on the default device.
_ACTIVE_MESH = None

#: kernel name -> path ("pallas" | "sharded" | "interpret" | "xla") -> the
#: operand shapes its dispatch emitted that path for into traced programs.
#: A path's first use is logged; `chip_smoke.py` reads the record to report
#: which path the programs it ran were actually built with.
KERNEL_PATHS: Dict[str, Dict[str, List[Tuple[int, ...]]]] = {}


def set_active_pallas_mesh(mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def note_kernel_path(kernel: str, path: str, shape) -> None:
    shapes = KERNEL_PATHS.setdefault(kernel, {}).setdefault(path, [])
    if not shapes:
        logger.info(f"kernel {kernel}: {path} path (first at shape {tuple(shape)})")
    if tuple(shape) not in shapes:
        shapes.append(tuple(shape))


def kernels_env() -> str:
    """The `TRLX_TPU_KERNELS` request, normalized: "" (unset: choose from
    the device), "off", "interpret" or "pallas"."""
    env = os.environ.get(KERNELS_ENV, "").strip().lower()
    if env in ("off", "xla", "0"):
        return "off"
    if env in ("pallas", "1", "force"):
        return "pallas"
    if env in ("", "interpret"):
        return env
    raise ValueError(
        f"{KERNELS_ENV}={env!r} is not one of off|xla|0, interpret, pallas|1|force"
    )


def require_tpu(devices, what: str) -> None:
    """A request for a compiled Mosaic kernel can only be met on a TPU."""
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"{what} asks for the compiled Pallas kernel, but the computation "
            f"runs on platform {platform!r}; ask for 'interpret' to run the "
            "kernel through the Pallas interpreter"
        )


def kernel_mode() -> str:
    """How the flash and fused-CE dispatch below lower their kernels,
    decided from the devices the computation runs on — the registered
    mesh's, or the default device when no trainer registered one:

    * ``"pallas"``    — one TPU device: the Mosaic kernel, called directly.
    * ``"sharded"``   — a standard (data, fsdp, tensor, sequence=1) mesh of
      several TPU devices: the same kernel under `pallas_shard_map` (a
      bare pallas_call inside a multi-device jit does not compile: "Mosaic
      kernels cannot be automatically partitioned").
    * ``"interpret"`` — the same kernels through the Pallas interpreter.
      Only ever the literal request `TRLX_TPU_KERNELS=interpret`.
    * ``"off"``       — the plain XLA paths: any non-TPU platform, pipe
      and sequence-sharded meshes (their programs are already manual over
      other axes; ring attention owns the sequence-sharded case), or
      `TRLX_TPU_KERNELS=off`.

    `TRLX_TPU_KERNELS=pallas` demands a compiled kernel and raises where
    the rule above would give ``"off"``."""
    env = kernels_env()
    if env in ("off", "interpret"):
        return env
    mesh = _ACTIVE_MESH
    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    if env == "pallas":
        require_tpu(devices, f"{KERNELS_ENV}=pallas")
    if devices[0].platform != "tpu":
        return "off"
    if len(devices) == 1:
        return "pallas"
    sizes = dict(mesh.shape)
    if set(sizes) == {"data", "fsdp", "tensor", "sequence"} and sizes["sequence"] == 1:
        return "sharded"
    if env == "pallas":
        raise RuntimeError(
            f"{KERNELS_ENV}=pallas: no shard_map wrapper for mesh {sizes}"
        )
    return "off"


def active_pallas_mesh():
    """The registered mesh, when kernels run shard_map-wrapped over it."""
    return _ACTIVE_MESH if kernel_mode() == "sharded" else None


# ---------------------------------------------------------------------------
# Flash backward. The residuals are (out, lse) — the standard
# FlashAttention-2 backward recomputes p = exp(s - lse) blockwise and
# accumulates dq / dk / dv with five matmuls per block pair. Both
# implementations below are primal-only math (no autodiff through a scan),
# so backward memory is O(t · block): the previous recompute-by-vjp path
# saved every KV block's attention probabilities as scan residuals, which
# is O(t^2) and ran a 12-layer GPT-2 out of HBM at seq 8192.
# ---------------------------------------------------------------------------


def _flash_fwd_kernel_lse(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr,
                          *, scale, causal, block_q, block_k):
    """The forward kernel, additionally writing the log-sum-exp per query
    row (the backward's residual). Dead rows (no valid key) get a huge
    LSE so the backward's exp(s - lse) underflows to exactly 0."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = jnp.asarray(True)
    if causal:
        run = (kb * block_k) <= (qb * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

        allowed = _block_allowed(mask_ref, qb, kb, block_q, block_k, causal)
        s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finalize_out():
        l = l_scr[:, 0]
        m = m_scr[:, 0]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(denom), DEAD_LSE)
        # [8, bq] sublane-broadcast layout: TPU blocks need their last two
        # dims (8, 128)-divisible, which a flat [1, bq] row is not
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


DEAD_LSE = 1e9  # lse sentinel for fully-masked query rows: exp(s - 1e9) == 0


def _flash_fwd_pallas_lse(q, k, v, mask, causal, block_q, block_k, interpret=False):
    """Forward + LSE residual. Returns (out [b,tq,nh,hd], lse [b,nh,tq])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, nh, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    bq = _auto_block(tq, block_q, FWD_BLOCK)
    bk = _auto_block(tk, block_k, FWD_BLOCK)
    nq, nk = tq // bq, tk // bk
    scale = 1.0 / np.sqrt(hd)

    qh = q.transpose(0, 2, 1, 3).reshape(b * nh, tq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    if mask is None:
        mask = jnp.ones((b, tk), jnp.int32)
    maskh = mask.astype(jnp.int32)[:, None, :]

    def kv_index(i, j, kk):
        return ((i // nh) * nkv + (i % nh) // group, kk, 0)

    kernel = functools.partial(
        _flash_fwd_kernel_lse, scale=scale, causal=causal, block_q=bq, block_k=bk
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // nh, 0, kk)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, 8, bq), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, tq, hd), q.dtype),
            jax.ShapeDtypeStruct((b * nh, 8, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # m
            pltpu.VMEM((bq, 128), jnp.float32),  # l
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
        ],
        interpret=interpret,
        name="flash_fwd_lse",
    )(qh, kh, vh, maskh)
    return (
        out.reshape(b, nh, tq, hd).transpose(0, 2, 1, 3),
        lse[:, 0, :].reshape(b, nh, tq),
    )


def _bwd_block_terms(q, k, v, do, lse_row, delta_row, allowed, scale):
    """Shared FlashAttention-2 backward block math (f32 2-D tiles):
    returns (p, ds) for one (q-block, k-block) pair."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    p = jnp.where(allowed, jnp.exp(s - lse_row[:, None]), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_row[:, None]) * scale
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr,
                         *, scale, causal, block_q, block_k):
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = jnp.asarray(True)
    if causal:
        run = (kb * block_k) <= (qb * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        allowed = _block_allowed(mask_ref, qb, kb, block_q, block_k, causal)
        _, ds = _bwd_block_terms(
            q, k, v, do, lse_ref[0, 0], delta_ref[0, 0], allowed, scale
        )
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kb == nk - 1)
    def _done():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                          *, scale, causal, block_q, block_k):
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = jnp.asarray(True)
    if causal:
        run = (qb * block_q + block_q - 1) >= (kb * block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        allowed = _block_allowed(mask_ref, qb, kb, block_q, block_k, causal)
        p, ds = _bwd_block_terms(
            q, k, v, do, lse_ref[0, 0], delta_ref[0, 0], allowed, scale
        )
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qb == nq - 1)
    def _done():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, mask, out, lse, g, causal, block_q, block_k,
                      interpret=False):
    """Pallas flash backward: dq over (q-block, scan k-blocks), dk/dv over
    (k-block, scan q-blocks); GQA folds the q-head group outside."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, nh, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    bq = _auto_block(tq, block_q, BWD_BLOCK)
    bk = _auto_block(tk, block_k, BWD_BLOCK)
    nq, nk = tq // bq, tk // bk
    scale = 1.0 / np.sqrt(hd)

    qh = q.transpose(0, 2, 1, 3).reshape(b * nh, tq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * nkv, tk, hd)
    doh = g.transpose(0, 2, 1, 3).reshape(b * nh, tq, hd)
    if mask is None:
        mask = jnp.ones((b, tk), jnp.int32)
    maskh = mask.astype(jnp.int32)[:, None, :]
    # [b*nh, 8, tq] sublane-broadcast layout (TPU block constraints;
    # see _flash_fwd_kernel_lse)
    lseh = jnp.broadcast_to(lse.reshape(b * nh, 1, tq), (b * nh, 8, tq))
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * nh, 1, tq)
    delta = jnp.broadcast_to(delta, (b * nh, 8, tq))

    def kv_index(i, j, kk):
        return ((i // nh) * nkv + (i % nh) // group, kk, 0)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(b * nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),   # q
            pl.BlockSpec((1, bk, hd), kv_index),                     # k
            pl.BlockSpec((1, bk, hd), kv_index),                     # v
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // nh, 0, kk)),
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),   # do
            pl.BlockSpec((1, 8, bq), lambda i, j, kk: (i, 0, j)),    # lse
            pl.BlockSpec((1, 8, bq), lambda i, j, kk: (i, 0, j)),    # delta
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * nh, tq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qh, kh, vh, maskh, doh, lseh, delta)

    def kv_index_k(i, j, kk):
        return ((i // nh) * nkv + (i % nh) // group, j, 0)

    dkh, dvh = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(b * nh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, kk, 0)),  # q
            pl.BlockSpec((1, bk, hd), kv_index_k),                   # k
            pl.BlockSpec((1, bk, hd), kv_index_k),                   # v
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // nh, 0, j)),
            pl.BlockSpec((1, bq, hd), lambda i, j, kk: (i, kk, 0)),  # do
            pl.BlockSpec((1, 8, bq), lambda i, j, kk: (i, 0, kk)),   # lse
            pl.BlockSpec((1, 8, bq), lambda i, j, kk: (i, 0, kk)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, tk, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * nh, tk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qh, kh, vh, maskh, doh, lseh, delta)

    if group > 1:  # GQA: per-q-head dk/dv fold back onto the kv heads
        dkh = dkh.reshape(b, nkv, group, tk, hd).sum(2)
        dvh = dvh.reshape(b, nkv, group, tk, hd).sum(2)
        dk = dkh.transpose(0, 2, 1, 3).astype(k.dtype)
        dv = dvh.transpose(0, 2, 1, 3).astype(v.dtype)
    else:
        dk = dkh.reshape(b, nh, tk, hd).transpose(0, 2, 1, 3).astype(k.dtype)
        dv = dvh.reshape(b, nh, tk, hd).transpose(0, 2, 1, 3).astype(v.dtype)
    return (
        dq.reshape(b, nh, tq, hd).transpose(0, 2, 1, 3).astype(q.dtype),
        dk, dv,
    )


def blockwise_attention_lse(q, k, v, mask=None, causal=True, block_k=128):
    """blockwise_attention that also returns the LSE residual [b, nh, tq]
    (the XLA-path forward for the custom flash backward)."""
    q32 = q.astype(jnp.float32)
    carry = blockwise_update(
        q32, k, v, mask, init_carry(q32), causal=causal, block_k=block_k
    )
    acc, m, l = carry
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), DEAD_LSE)
    return _finalize(acc, l).astype(q.dtype), lse


def _flash_bwd_xla(q, k, v, mask, out, lse, g, causal, block_k):
    """Blockwise flash backward in plain XLA (CPU path + parity oracle for
    the Pallas kernels). Primal-only scans: nothing quadratic is saved."""
    b, tq, nh, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    scale = 1.0 / np.sqrt(hd)
    bk = _pick_block(tk, block_k if block_k is not None else 128)
    nblocks = tk // bk

    q32 = q.astype(jnp.float32)
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # [b, tq, nh]
    delta_h = delta.transpose(0, 2, 1)  # [b, nh, tq]
    if mask is None:
        mask = jnp.ones((b, tk), jnp.int32)

    kb_ = k.reshape(b, nblocks, bk, nkv, hd).transpose(1, 0, 2, 3, 4)
    vb_ = v.reshape(b, nblocks, bk, nkv, hd).transpose(1, 0, 2, 3, 4)
    mb_ = mask.reshape(b, nblocks, bk).transpose(1, 0, 2)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def p_ds(kblk, vblk, mblk, idx):
        kf = kblk.astype(jnp.float32)
        vf = vblk.astype(jnp.float32)
        if group > 1:
            kf = jnp.repeat(kf, group, axis=2)
            vf = jnp.repeat(vf, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kf,
                       preferred_element_type=jnp.float32) * scale
        cols = idx * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        allowed = jnp.broadcast_to(mblk[:, None, None, :] > 0, s.shape)
        if causal:
            allowed = allowed & (cols <= rows)[None, None]
        p = jnp.where(allowed, jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do, vf,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta_h[..., None]) * scale
        return kf, p, ds

    def dq_body(acc, blk):
        kblk, vblk, mblk, idx = blk
        kf, _, ds = p_ds(kblk, vblk, mblk, idx)
        return acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kf,
                                preferred_element_type=jnp.float32), None

    dq, _ = jax.lax.scan(
        dq_body, jnp.zeros_like(q32),
        (kb_, vb_, mb_, jnp.arange(nblocks)),
    )

    def dkv_body(carry, blk):
        kblk, vblk, mblk, idx = blk
        _, p, ds = p_ds(kblk, vblk, mblk, idx)
        dvb = jnp.einsum("bhqk,bqhd->bkhd", p, do,
                         preferred_element_type=jnp.float32)
        dkb = jnp.einsum("bhqk,bqhd->bkhd", ds, q32,
                         preferred_element_type=jnp.float32)
        if group > 1:  # fold q-head grads back onto kv heads
            dvb = dvb.reshape(b, bk, nkv, group, hd).sum(3)
            dkb = dkb.reshape(b, bk, nkv, group, hd).sum(3)
        return carry, (dkb, dvb)

    _, (dk_blocks, dv_blocks) = jax.lax.scan(
        dkv_body, 0, (kb_, vb_, mb_, jnp.arange(nblocks))
    )
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(b, tk, nkv, hd)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, tk, nkv, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def pallas_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map for shard-local Pallas kernels: disables the varying-axes
    check (pallas_call outputs carry no vma metadata). Shared by
    flash_attention_sharded and fused_ce.fused_logprobs_sharded."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def flash_attention_sharded(mesh, q, k, v, mask, causal=True, block_q=None,
                            block_k=None, interpret=False):
    """The Pallas forward under a multi-chip mesh: batch shards over
    (data, fsdp) and heads over tensor, each shard running the kernel on
    its local block. Full-manual shard_map (every axis named), so no
    partial-auto lowering is involved. Caller guarantees divisibility
    (`_sharded_flash_ok`).

    Validation: parity is pinned in interpret mode on the CPU mesh
    (tests/test_pallas_sharded.py), the wrapper AOT-compiles for a v5e 2x2
    on (data, fsdp, tensor) = (4,1,1), (2,1,2), (1,2,2)
    (tests/test_kernels_compile_tpu.py), and it ran on four v5e chips in
    PR 21's `chip_smoke.py` under data=4 and fsdp=2 x tensor=2 (score at
    [128, 104, 12, 64], train at [32, 104, 12, 64]; 32 finite losses in
    each layout). Its output on the chip has not been compared element by
    element with the one-chip kernel's."""
    from jax.sharding import PartitionSpec as P

    qkv_spec = P(("data", "fsdp"), None, "tensor", None)
    fn = pallas_shard_map(
        functools.partial(
            _flash_fwd_pallas, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        ),
        mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P(("data", "fsdp"), None)),
        out_specs=qkv_spec,
    )
    if mask is None:
        mask = jnp.ones((q.shape[0], k.shape[1]), jnp.int32)
    return fn(q, k, v, mask)


def _sharded_flash_ok(mesh, q, k) -> bool:
    sizes = dict(mesh.shape)
    dp = sizes["data"] * sizes["fsdp"]
    tp = sizes["tensor"]
    b, _, nh, _ = q.shape
    nkv = k.shape[2]
    return b % dp == 0 and nh % tp == 0 and nkv % tp == 0 and (nh // tp) % max(nkv // tp, 1) == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention(q, k, v, mask, causal, block_q, block_k):
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        note_kernel_path("flash_fwd", mode, q.shape)
        return _flash_fwd_pallas(q, k, v, mask, causal, block_q, block_k,
                                 interpret=(mode == "interpret"))
    if mode == "sharded" and _sharded_flash_ok(_ACTIVE_MESH, q, k):
        note_kernel_path("flash_fwd", "sharded", q.shape)
        return flash_attention_sharded(_ACTIVE_MESH, q, k, v, mask, causal,
                                       block_q, block_k)
    note_kernel_path("flash_fwd", "xla", q.shape)
    return blockwise_attention(q, k, v, mask, causal, block_k)


def _flash_fwd_rule(q, k, v, mask, causal, block_q, block_k):
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        note_kernel_path("flash_fwd", mode, q.shape)
        out, lse = _flash_fwd_pallas_lse(q, k, v, mask, causal, block_q, block_k,
                                         interpret=(mode == "interpret"))
        return out, (q, k, v, mask, out, lse)
    if mode == "sharded" and _sharded_flash_ok(_ACTIVE_MESH, q, k):
        # sharded fwd keeps the legacy recompute backward (lse would need
        # the shard_map plumbing); memory note in docs/parallelism.md
        note_kernel_path("flash_fwd", "sharded", q.shape)
        out = flash_attention_sharded(_ACTIVE_MESH, q, k, v, mask, causal,
                                      block_q, block_k)
        return out, (q, k, v, mask, None, None)
    note_kernel_path("flash_fwd", "xla", q.shape)
    out, lse = blockwise_attention_lse(q, k, v, mask, causal, block_k)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, res, g):
    q, k, v, mask, out, lse = res
    if lse is None:
        # legacy recompute path (sharded fwd): vjp through the blockwise
        # scan — O(t^2 / block_k) residual memory, fine at short context
        note_kernel_path("flash_bwd", "xla", q.shape)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(q_, k_, v_, mask, causal, block_k),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        return dq, dk, dv, None
    # FlashAttention-2 backward from the (out, lse) residuals: primal-only
    # blockwise math, O(t · block) memory (Pallas kernels on a single TPU
    # chip; the same algorithm as plain XLA scans elsewhere)
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        note_kernel_path("flash_bwd", mode, q.shape)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, mask, out, lse, g,
                                       causal, block_q, block_k,
                                       interpret=(mode == "interpret"))
    else:
        note_kernel_path("flash_bwd", "xla", q.shape)
        dq, dk, dv = _flash_bwd_xla(q, k, v, mask, out, lse, g, causal, block_k)
    return dq, dk, dv, None


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention. q,k,v: [b, t, nh, hd]; mask: [b, S] key validity
    (1 = real). Returns [b, t, nh, hd]. On TPU forward AND backward run
    as Pallas kernels; elsewhere the blockwise XLA paths are used.
    block_q/block_k default to the tuned auto sizes (FWD_BLOCK for the
    forward, BWD_BLOCK for the backward kernels). `window` bands a causal
    call (query i sees keys j with 0 <= i - j < window): the forward only,
    for a cached prefill; differentiating it is refused by name. So is a v
    narrower than q and k (a latent layer's decompressed prefill), whose
    output is as wide as v."""
    if window is not None:
        return _flash_window_forward(q, k, v, mask, window, block_q, block_k)
    if v.shape[-1] != q.shape[-1]:
        return _flash_latent_forward(q, k, v, mask, causal, block_q, block_k)
    return _flash_attention(q, k, v, mask, causal, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_latent_forward(q, k, v, mask, causal, block_q, block_k):
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        note_kernel_path("flash_fwd_latent", mode, q.shape)
        return _flash_fwd_pallas(q, k, v, mask, causal, block_q, block_k,
                                 interpret=(mode == "interpret"))
    # off the one-chip kernel: the blockwise scan carries q's width, so the
    # values ride padded to it and the padding is cut off the output
    note_kernel_path("flash_fwd_latent", "xla", q.shape)
    hv = v.shape[-1]
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - hv),))
    return blockwise_attention(q, k, v, mask, causal, block_k)[..., :hv]


def _flash_latent_fwd_rule(q, k, v, mask, causal, block_q, block_k):
    raise NotImplementedError(
        "the flash backward with value heads narrower than query/key heads is not written: a "
        "training forward over latent layers takes the dense bias (models/transformer.fused_attention_ok)")


_flash_latent_forward.defvjp(_flash_latent_fwd_rule, lambda *a: None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_window_forward(q, k, v, mask, window, block_q, block_k):
    mode = kernel_mode()
    if mode in ("pallas", "interpret"):
        note_kernel_path("flash_fwd_window", mode, q.shape)
        return _flash_fwd_pallas(q, k, v, mask, True, block_q, block_k,
                                 interpret=(mode == "interpret"), window=window)
    # a multi-device mesh has no shard_map wrapper for the banded kernel
    note_kernel_path("flash_fwd_window", "xla", q.shape)
    hv = v.shape[-1]  # narrower values ride padded, as `_flash_latent_forward`'s
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - hv),))
    return blockwise_attention(q, k, v, mask, True, block_k, window=window)[..., :hv]


def _flash_window_fwd_rule(q, k, v, mask, window, block_q, block_k):
    raise NotImplementedError(
        "the windowed flash backward is not written: a training forward longer than "
        "its sliding window takes the dense bias (models/transformer.fused_attention_ok)")


_flash_window_forward.defvjp(_flash_window_fwd_rule, lambda *a: None)
