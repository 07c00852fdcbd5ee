"""Pallas paged-attention decode kernel (PagedAttention, Kwon et al. 2023)
and the paged KV arena's layout, which this module alone knows.

The gather read path (`paged_kv_gather`) serves decode steps by copying
every block of a slot's block table back into a dense [b, n_tbl*block,
nkv, hd] view, dequantizing int8 arenas into a SECOND materialized copy
and `jnp.repeat`-ing kv heads up to n_heads for GQA before softmax·V.
The kernel collapses the read side into one Pallas pass per (slot,
table-entry) grid cell:

* the slot's block table is a **scalar-prefetch** operand, so each KV
  tile's BlockSpec index map dereferences `table[slot, j]` and the DMA
  engine fetches the physical arena block directly (all kv heads of the
  block in one contiguous transfer) — no gathered dense copy in HBM;
* int8 arenas are dequantized **in registers**: the per-token f32 scales
  multiply the [group, block] score / probability tiles, which is the
  `ops.quant.dequantize_kv` product re-associated — no dequantized copy;
* an online flash-style softmax (same (acc, m, l) carry and NEG_INF
  masking policy as `ops.attention._flash_fwd_kernel`) runs across the
  table walk, so the [group, S] score matrix never materializes;
* each kv head's whole q-head **group** multiplies against its fetched KV
  tile, so GQA divides KV bytes per step by the group factor.

Layout. The Pallas TPU lowering requires the last two dims of every block
to be divisible by (8, 128) or equal to the array's, and the TPU's
default device layout keeps an array row-major only when its minor dim
is >= 64 (narrower minor dims are transposed to save tile padding, and
XLA then copies the whole operand in front of the kernel on every call).
Hence:

* K / V arenas are [n_blocks, nkv, block, hd] — a block's tile for one kv
  head is its last two dims. With hd >= 64 (every preset but the `-tiny`
  test models) the kernel's operand is the arena as it lies; below that
  XLA inserts the layout copy, which costs time but not correctness.
  That holds for the PROGRAM only if the write in front of the kernel
  leaves the arena in that layout too: `paged_kv_write` scatters whole
  blocks over the arena's major dimension for that reason (a scatter
  into dims 0 and 2, `arena.at[phys, :, off]`, made XLA re-lay the whole
  arena in front of the write and back in front of the kernel or the
  gather, four arena copies a layer, in every decode step and every
  prefill: tests/test_kernels_compile_tpu.py holds write and read in one
  program).
* int8 scale planes are [n_blocks, 1, nkv*block] f32, head-major along
  the lane axis (column h*block + offset), so a block's scales are one
  lane-dense row and each head's slice is a static lane window.
* the key mask enters as [b, n_tbl, 1, block]: one block per lane row.

`q` is [b, nh, hd] (ONE query position per row — the decode shape);
`table` is [b, n_tbl] int32; `key_mask` is [b, n_tbl*block] key validity
over logical columns. Rows whose mask is all-zero (inactive slots) return
exact 0.0 — the engine overwrites their sampled token anyway.

Kernel selection lives with the caller (`InferenceEngine`, through
`ops.attention.kernel_mode`), which also counts per-dispatch fallbacks
for shapes this kernel does not express (multi-position spec-verify
queries, alibi/sliding-window biases, prefix tuning).
`paged_attention_reference` is the XLA shadow of the gather path, kept
here so tests can pin both semantics side by side.
"""

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.ops import quant
from trlx_tpu.ops.attention import NEG_INF


def init_paged_layer(
    num_blocks: int, block_size: int, n_kv_heads: int, head_dim: int, dtype
) -> Dict[str, jnp.ndarray]:
    """One layer's zeroed arena: k / v, plus f32 scale planes for int8."""
    shape = (num_blocks, n_kv_heads, block_size, head_dim)
    layer = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        scale_shape = (num_blocks, 1, n_kv_heads * block_size)
        layer["k_scale"] = jnp.zeros(scale_shape, jnp.float32)
        layer["v_scale"] = jnp.zeros(scale_shape, jnp.float32)
    return layer


def paged_kv_write(
    layer: Dict[str, jnp.ndarray],
    k: jnp.ndarray,      # [b, t, nkv, hd]
    v: jnp.ndarray,      # [b, t, nkv, hd]
    table: jnp.ndarray,  # [b, n_tbl] physical block ids; >= n_blocks drops the write
    start: jnp.ndarray,  # [b] logical column of each row's first position
    valid: Optional[jnp.ndarray] = None,  # [b, t]; 0 keeps the position out of the arena
) -> Dict[str, jnp.ndarray]:
    """Write one step's K/V into the arena (quantizing for int8 arenas):
    row r's position i lands at logical column `start[r] + i`, i.e. in
    block `table[r, column // block]` at offset `column % block`. Positions
    with `valid == 0` (right-pad, inactive slots), columns past the table
    and table entries >= n_blocks (padding rows of an insert, stale
    tables) never touch the arena.

    Whole blocks are patched: the blocks a row's run of t columns can
    straddle are gathered, the new positions selected in, and the blocks
    scattered back over the MAJOR dimension of the donated arena, which
    XLA's TPU scatter updates where it lies (module docstring, "Layout")
    at about a microsecond a block; a block with no valid position of this
    call is not touched. (A scatter of [hd] rows is as free of copies but
    serial at 70 ns a row: PERF.md section 6, PR 28.) This relies on the
    block pool handing a block that is being written to one row only
    (inference/paging.py: shared prefix blocks are full and read-only).
    A scale plane takes its t x nkv elements one by one through the flat
    view: patching its [1, nkv*block] rows makes XLA re-lay the plane."""
    n_blocks, nkv, blk, _ = layer["k"].shape
    b, t = k.shape[:2]
    n_tbl = table.shape[1]
    valid = jnp.ones((b, t), bool) if valid is None else valid.astype(bool)
    rows = jnp.arange(b)[:, None]

    n_touch = (t + blk - 2) // blk + 1  # blocks a run of t columns can straddle
    entry = (start // blk)[:, None] + jnp.arange(n_touch)  # [b, n_touch]
    phys = table[rows, jnp.clip(entry, 0, n_tbl - 1)]
    # column p of the touched blocks holds this call's position src[p]
    src = jnp.arange(n_touch * blk)[None, :] - (start % blk)[:, None]
    live = (src >= 0) & (src < t)
    src = jnp.clip(src, 0, t - 1)
    live = (live & valid[rows, src]).reshape(b, n_touch, blk)
    live &= ((entry < n_tbl) & (phys < n_blocks))[..., None]
    phys = jnp.where(live.any(-1), phys, n_blocks)

    def put(arena, values):
        new = values[rows, src].reshape(b, n_touch, blk, nkv, -1).swapaxes(2, 3)
        patched = jnp.where(live[:, :, None, :, None], new.astype(arena.dtype), arena[phys])
        return arena.at[phys.reshape(-1)].set(
            patched.reshape(-1, *arena.shape[1:]), mode="drop"
        )

    if layer["k"].dtype != jnp.int8:
        return {"k": put(layer["k"], k), "v": put(layer["v"], v)}

    cols = start[:, None] + jnp.arange(t)  # [b, t]
    at = table[rows, jnp.clip(cols // blk, 0, n_tbl - 1)]
    ok = valid & (cols < n_tbl * blk) & (at < n_blocks)
    at = (at[..., None] * nkv + jnp.arange(nkv)) * blk + (cols % blk)[..., None]
    at = jnp.where(ok[..., None], at, n_blocks * nkv * blk).reshape(-1)

    def put_scales(plane, scales):  # [b, t, nkv] onto column h*blk + offset
        flat = plane.reshape(-1).at[at].set(scales.reshape(-1), mode="drop")
        return flat.reshape(plane.shape)

    kq, ks = quant.quantize_kv(k)
    vq, vs = quant.quantize_kv(v)
    return {
        "k": put(layer["k"], kq),
        "v": put(layer["v"], vq),
        "k_scale": put_scales(layer["k_scale"], ks),
        "v_scale": put_scales(layer["v_scale"], vs),
    }


def paged_kv_gather(
    layer: Dict[str, jnp.ndarray], table: jnp.ndarray, dtype
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The gather read path: each row's table blocks copied back into the
    dense [b, n_tbl*block, nkv, hd] layout (dequantized for int8)."""
    n_blocks, nkv, blk, hd = layer["k"].shape
    b, n_tbl = table.shape

    def dense(arena):
        return arena[table].transpose(0, 1, 3, 2, 4).reshape(b, n_tbl * blk, nkv, hd)

    if layer["k"].dtype != jnp.int8:
        return dense(layer["k"]), dense(layer["v"])

    def dense_scale(plane):
        return (
            plane[table].reshape(b, n_tbl, nkv, blk)
            .transpose(0, 1, 3, 2).reshape(b, n_tbl * blk, nkv)
        )

    return (
        quant.dequantize_kv(dense(layer["k"]), dense_scale(layer["k_scale"]), dtype),
        quant.dequantize_kv(dense(layer["v"]), dense_scale(layer["v_scale"]), dtype),
    )


def _paged_decode_kernel(table_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                         quantized: bool):
    """One (slot, table-entry) cell: the block the table names has landed
    in VMEM for every kv head; mask invalid columns and fold each head's
    tile into its online softmax.

    table_ref  scalar prefetch [b, n_tbl] (unused here; drives index maps)
    q_ref      [1, nkv, group, hd]
    k_ref/v_ref [1, nkv, blk, hd]
    ks_ref/vs_ref [1, 1, nkv*blk] f32 (int8 arenas only)
    mask_ref   [1, 1, 1, blk] int32 key validity of this block's columns
    o_ref      [1, nkv, group, hd]
    m_scr/l_scr VMEM [nkv, group, 128] f32 running max / denominator
               (lane-broadcast), acc_scr VMEM [nkv, group, hd] numerator
    """
    import jax.experimental.pallas as pl

    if quantized:
        ks_ref, vs_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        mask_ref, o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)
    nt = pl.num_programs(1)
    nkv, blk = k_ref.shape[1], k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    valid = mask_ref[0, 0] > 0  # [1, blk]
    for h in range(nkv):
        q = q_ref[0, h].astype(jnp.float32)  # [group, hd]
        k = k_ref[0, h].astype(jnp.float32)  # [blk, hd]
        v = v_ref[0, h].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [group, blk]
        if quantized:
            s = s * ks_ref[0, :, h * blk:(h + 1) * blk]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h, :, 0:1]  # [group, 1]
        l_prev = l_scr[h, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Fully-masked-so-far rows keep m == NEG_INF; clamp the shift so
        # the exp below cannot blow up to exp(0)=1 on masked entries.
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, :, h * blk:(h + 1) * blk]
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == nt - 1)
    def _finalize():
        l = l_scr[:, :, 0:1]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def paged_attention_decode(
    q: jnp.ndarray,        # [b, nh, hd]
    k_arena: jnp.ndarray,  # [n_blocks, nkv, blk, hd]
    v_arena: jnp.ndarray,  # [n_blocks, nkv, blk, hd]
    table: jnp.ndarray,    # [b, n_tbl] int32 physical block ids
    key_mask: jnp.ndarray,  # [b, n_tbl*blk] key validity (1 = attend)
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [n_blocks, 1, nkv*blk] f32
    v_scale: Optional[jnp.ndarray] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused paged decode attention. Returns [b, nh, hd] in `out_dtype`
    (defaults to q's dtype). Grid is (b, n_tbl): each cell walks one table
    entry of one slot for every kv head, so a KV block is fetched once per
    step and shared by its whole q-head group. `table` rides scalar
    prefetch — the arena BlockSpec index maps dereference it, so block
    fetches are direct HBM→VMEM DMAs of the physical blocks (the zero
    block for never-written table slack, whose columns the mask kills)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nh, hd = q.shape
    n_blocks, nkv, blk, _ = k_arena.shape
    if nh % nkv != 0:
        raise ValueError(f"n_heads {nh} not divisible by n_kv_heads {nkv}")
    group = nh // nkv
    n_tbl = table.shape[1]
    quantized = k_arena.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 arenas require k_scale/v_scale planes")
    out_dtype = out_dtype or q.dtype

    # Head order matches the dense path's jnp.repeat(k, group, axis=2):
    # q head h attends kv head h // group, so [b, nh, hd] -> [b, nkv,
    # group, hd] keeps each kv head's q-group contiguous.
    qg = q.reshape(b, nkv, group, hd)
    maskh = key_mask.astype(jnp.int32).reshape(b, n_tbl, 1, blk)

    def slot_index(i, j, tbl_ref):
        return (i, 0, 0, 0)

    def kv_index(i, j, tbl_ref):
        return (tbl_ref[i, j], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, nkv, group, hd), slot_index),
        pl.BlockSpec((1, nkv, blk, hd), kv_index),
        pl.BlockSpec((1, nkv, blk, hd), kv_index),
    ]
    operands = [qg, k_arena, v_arena]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, 1, nkv * blk), lambda i, j, tbl_ref: (tbl_ref[i, j], 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    in_specs.append(
        pl.BlockSpec((1, 1, 1, blk), lambda i, j, tbl_ref: (i, j, 0, 0))
    )
    operands.append(maskh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_tbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, group, hd), slot_index),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, 128), jnp.float32),  # m (lane-broadcast)
            pltpu.VMEM((nkv, group, 128), jnp.float32),  # l
            pltpu.VMEM((nkv, group, hd), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=1.0 / np.sqrt(hd), quantized=quantized
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, group, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_decode",
    )(table.astype(jnp.int32), *operands)
    return out.reshape(b, nh, hd)


def paged_attention_reference(
    q: jnp.ndarray,
    k_arena: jnp.ndarray,
    v_arena: jnp.ndarray,
    table: jnp.ndarray,
    key_mask: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    out_dtype=None,
) -> jnp.ndarray:
    """XLA shadow of the gather read path in `models/transformer.py`
    (`decode_kernel=xla`): gather the table back to a dense view,
    dequantize int8, repeat kv heads, dense softmax with the -1e9 additive
    bias. Unit tests pin the kernel against this; the engine-level bitwise
    guarantee is on greedy token streams, where the blockwise-vs-dense
    summation-order ulps cannot flip an argmax that the -1e9/exact-0.0
    masking keeps stable."""
    nh, hd = q.shape[1], q.shape[2]
    nkv = k_arena.shape[1]
    out_dtype = out_dtype or q.dtype
    layer = {"k": k_arena, "v": v_arena}
    if k_arena.dtype == jnp.int8:
        layer.update(k_scale=k_scale, v_scale=v_scale)
    k, v = paged_kv_gather(layer, table, out_dtype)
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    bias = jnp.where(key_mask.astype(bool), 0.0, -1e9)[:, None, None, :]
    scores = jnp.einsum(
        "bhd,bshd->bhs", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(hd))
    scores = scores[:, :, None, :] + bias  # [b, nh, 1, S]
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return out[:, 0].astype(out_dtype)
