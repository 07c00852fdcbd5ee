"""Pallas paged-attention decode kernel (PagedAttention, Kwon et al. 2023)
and the paged KV arena's layout, which this module alone knows.

The gather read path (`paged_kv_gather`) serves decode steps by copying
every block of a slot's block table back into a dense [b, n_tbl*block,
nkv, hd] view and dequantizing int8 arenas into a SECOND materialized copy
before the dense contraction (which keeps K/V at n_kv_heads under GQA).
The kernel collapses the read side into one Pallas call whose time
follows the tokens resident, not the table's length:

* the physical blocks a call fetches ride **scalar prefetch**: the kernel
  reads a block's id there and the DMA engine fetches the arena block
  directly (all kv heads of the block in one contiguous transfer) — no
  gathered dense copy in HBM;
* int8 arenas are dequantized **in registers**: the per-token f32 scales
  multiply the [nkv, group, T] score / probability tiles, which is the
  `ops.quant.dequantize_kv` product re-associated — no dequantized copy;
* an online flash-style softmax (same (acc, m, l) carry and NEG_INF
  masking policy as `ops.attention._flash_fwd_kernel`) runs across a
  row's tiles, so the [group, S] score matrix never materializes;
* each kv head's whole q-head **group** multiplies against its fetched KV
  tile, so GQA divides KV bytes per step by the group factor.

Grid and tile. A grid step is one **tile** of one row: `E` consecutive
table entries (`_tile_entries`: 512 tokens' worth, at most 16, never past
the table: 16 entries of 32 in every cell of the benchmark), each entry's
[nkv, blk, hd] block of K and of V one transfer of its own, so a tile is E
block DMAs a side and the fixed cost of a step is paid once per 512
tokens. In the kernel the E blocks are laid side by side as one
[nkv, T = E*blk, hd] tile (an aligned concatenation: no copy) and every
kv head is contracted at once: scores are one `dot_general` batched over
nkv with [group, hd] query rows against the tile, the output one batched
`dot_general` of the probabilities against V's tile, K and V in the
arena's own type with float32 accumulation (a bfloat16 x bfloat16 product
accumulated in float32 is exact; a float32 arena keeps every operand
float32; probabilities are rounded to the output type before p·v only for
a bfloat16 or int8 arena, as the gather path rounds them). The same form
serves every group size: for group == 1 a multiply-and-reduce on the VPU
was measured beside it and took 1.8-2.0 times as long (PERF.md section 6,
PR 30). Running max and denominator are [nkv, group, 1] float32 state
written once per tile.

Fetch. How a tile's blocks reach VMEM is decided by the arena's shapes
alone (`copies_blocks`). Where one block is whole (sublane, lane) tiles
(heads of a multiple of 128, blocks of a multiple of 8 rows of float32, 16
of bfloat16, 32 of int8: every cell of the benchmark), the arenas (and an
int8 arena's scale planes) enter the call where they lie
(`memory_space=pl.ANY`) and **the kernel copies**: a scratch
[2 buffers, 2 sides, E, nkv, blk, hd] (and [2, 2, E, 1, nkv*blk] float32
for the planes), one `make_async_copy` a live entry a side on one DMA
semaphore a buffer, in a loop over the tile's LIVE entries that reads each
block's id from the table in SMEM. Grid step w starts step w + 1's copies
into the other buffer (across a row's end too: the next step's row, tile,
`n_live` and `n_first` are all in SMEM) before it waits for its own; `q`,
the mask's tile and the output are the call's three pipelined operands. An
entry that is not live is not copied; its place in the scratch holds
whatever it held: its scores are masked, and its VALUES are zeroed in the
scratch before the tile is multiplied (a row's first and last tile only),
because 0 x NaN is NaN. Elsewhere (heads of 64, the `-tiny` models: Mosaic
slices a memref by whole tiles only) **each entry's block is a `BlockSpec`
operand** of its own (2E of them, 4E with int8 planes; 256 tokens and at
most 8 entries a tile) whose index map reads the block's id, and the
pipeline fetches it. The body is one and the same. Measured at the cells'
call shapes (PERF.md section 6, PR 56): the pipeline's bookkeeping for 17
operands runs on the scalar core IN SERIES with the body, 0.33 us a step;
with 4 K/V heads, where a step's bytes take 0.64 us and the body about as
long, the copying form at 16 entries reads 90% of the roofline where the
operand form read 63% at 8 entries and 73% at 16, and it is no slower at
8 and 16 K/V heads. VMEM: the tile's buffers are 2 sides x E entries x 2
buffers x one block either way (16 K/V heads: 64 x 128 KiB = 8 MiB, which
is `_TILE_VMEM_BYTES`: the tile shrinks until they fit; 4 K/V heads:
2 MiB), beside them q, mask, output, the scale planes' rows and the
[nkv, group, T] scores; the call is compiled under `vmem_limit_bytes` =
12 MiB (`_VMEM_LIMIT_BYTES`; the v5e's default scoped limit is 16), which
Mosaic enforces and tests/test_kernels_compile_tpu.py holds for the
cells' shapes.

Schedule (`_live_walk`, a few small XLA operations on the table and the
mask, the same for every layer of a step). `n_live[i]` is row i's live
table entries: (its last attendable column + 1) rounded up to blocks, 0
for an all-masked row. The grid is ONE dimension over the tiles that hold
a live entry, rows in order (an all-masked row gets one step, which writes
its zeros), and its length `n_work` is read at run time: lengths are
data, not shapes, so one decode program serves every mix. A table entry
j >= n_live[i] is neither fetched nor multiplied: the copying kernel's
loop ends at `n_live`, and where blocks are operands (`_live_schedule`:
the operand form of this kernel, the latent and the index kernel) an
operand whose entry is not live names the block it held the step before,
and a repeated block index is not fetched again; so table slack (the zero
block, or an id >= n_blocks on a padding row) is never dereferenced. The
mask still rules INSIDE live entries: a mask with holes, a partly filled
last block, and shared read-only prefix blocks behave as on the gather
path.

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
  That holds for the PROGRAM only if a write in front of the read leaves
  the arena in that layout too: `paged_kv_write` (every write of more
  than one position a row, and a decode step's where the kernel does not
  write: "Write" below) scatters whole blocks over the arena's major
  dimension for that reason (a scatter into dims 0 and 2,
  `arena.at[phys, :, off]`, made XLA re-lay the whole arena in front of
  the write and back in front of the kernel or the gather, four arena
  copies a layer, in every decode step and every prefill:
  tests/test_kernels_compile_tpu.py holds write and read in one program).
* int8 scale planes are [n_blocks, 1, nkv*block] f32, head-major along
  the lane axis (column h*block + offset), so a block's scales are one
  lane-dense row and each head's slice is a static lane window.
* the key mask enters as [b, n_tiles, 1, T]: one tile per lane row.

Write. A decode step writes ONE position a row, and `paged_kv_write` pays
for it by the block: a gather, a select and a scatter of the whole block
that holds the position, a side a layer, one block after another (3.2 ms
of pythia-1.4b's 11.9 ms decode program at 64 rows x 24 layers, 8.6 ms of
ouro-2.6b's 37.2 at 8 rows x 192 layer calls: PERF.md section 6, PR 58).
Where the kernel copies a tile's blocks itself (`writes_in_kernel`: "Fetch"
above, and no int8 arena, whose scale planes take their elements one by
one) the block that holds a row's new column is already in the scratch
when its tile is multiplied, so the call takes the step's K and V rows
(`new_kv`, one more pipelined operand beside `q`, [1, 2, nkv, 1, hd]) and
their column (`column`, a scalar-prefetch operand: -1 where a row writes
nothing) and does the write itself: in the grid step whose tile holds the
column's entry, after the step has waited for its own copies and before the
tile is read, it selects the row into the one (sublane, lane) tile of K and
of V that holds it (16 rows of bfloat16, 8 of float32: a compare against an
iota, no one-row store) and starts one copy a side of those rows, all K/V
heads, from the scratch to the arena, which is the call's OUTPUT aliased to
its operand (`input_output_aliases`: the program donates the arena, the
engine's pool and a looped stack's scan carry do). The products read the
patched scratch, so what is attended to is what `paged_kv_write` in front
of the call would have put there, bit for bit; the copy back is waited for
at the end of the same grid step, behind the products, before the next
step starts the copies that overwrite that half of the scratch. The call is
still ONE Mosaic call a layer under the same name. Hazards, and why they do
not arise: (1) the block a row writes is its own: the block pool hands a
block that is being written to one row only (inference/paging.py: shared
prefix blocks are full and read-only), so the copies the step has started
for the NEXT grid step (another row's blocks, or a later tile of the same
row) never read rows in flight; (2) a fresh block written at offset 0 keeps
whatever the arena held behind the new row, as it does under
`paged_kv_write`: the mask rules; (3) a row with no live entry (a freed
slot, `attn_mask == 0`: the call site masks it) writes nothing and its
stale table is never read, and a row writes only if its walk holds the
column's entry (the query's own column is the row's last attendable one in
the engine's mask; with a window it lies in the band). tests/
test_paged_attention.py holds all of that against `paged_kv_write` in
front of the reference, every block of both arenas bit for bit.

Window. A sliding layer's call names its `window` (static): the mask loses
the columns that trail a row's last one by `window` or more (`band_mask`),
and the schedule leaves out the tiles in front of the band as it leaves out
those past the row's end: a row's walk starts at the tile that holds its
first attendable column, and inside that tile the entries in front of the
band are not fetched (`n_first`, a fifth scalar-prefetch operand, is where
the copying kernel's loop begins; an operand keeps the block it held, and
the latent kernel is told the first entry of that tile, `first`, where its
online softmax begins) and the mask rules the columns. Time then follows min(tokens resident, window). Such
a call is named `paged_decode_window` in the device trace; a call without a
window is the program it was before the window existed.

Latent. A latent-attention layer (`models/transformer.LatentAttention`)
caches ONE plane of `width` values a token: its normed latent (the value,
`values` columns) and the rotated key all heads share, 512 + 64 = 576 at
the published sizes. The arena holds two tokens a row, [n_blocks,
block / 2, 2 * width], as [v_2r | v_2r+1 | k_2r | k_2r+1] (`_pack_latent`):
a [block, 576] plane would be the same bytes, but 576 is 4.5 lane tiles,
and the TPU's default layout then makes the BLOCK dimension minor to save
the padding, so that XLA re-lays the whole arena in front of the kernel
and behind the write on every call (AOT for v5e, PR 37: two copies of
453 MB a layer a step); 1,152 is 9 lane tiles, the arena lies row-major
as it is declared, a block is one contiguous transfer of 36,864 bytes,
and every column slice the kernel takes starts on a lane tile. Its decode
runs absorbed: every one of the layer's query heads, `width` wide, reads
the SAME latent rows, and the values are the rows' leading columns, so
`paged_attention_latent` fetches a tile once (`_LATENT_TILE_TOKENS` tokens)
and feeds the MXU [heads, width] x [width, T] and [heads, T] x [T, values]: 2 x heads x (width + values) operations for
`width` x itemsize bytes a cached position, on the v5e's ridge at 128 heads
in bfloat16. Schedule, masking and the online softmax are the K/V kernel's
(`_live_schedule` as it is); there is no int8 form. The call is named
`paged_decode_latent` in the device trace. A banded latent layer's call names
its `window` as a sliding K/V layer's does ("Window" above: `band_mask`, the
windowed schedule, a walk that starts at `first`), over a plane of its own
width (1,024 + 64 = 1,088 at dots3-note's sizes: rows of 2,176 = 17 lane
tiles); such a call is named `paged_decode_latent_window`, and a call without
a window is the program it was before the window existed.

Index. A sparse latent layer (`LatentSpec.index_topk`) keeps the index's key
a token in a plane of its own beside the latent, [n_blocks, block, width]
(`init_paged_plane`; 128 wide at the published sizes: one lane tile, row-major
as declared). `paged_index_scores` walks a row's live blocks of it through the
table (scalar prefetch and `_live_schedule` as they are) and writes the
index's score of every cached column for the row's one query position:
[heads, width] x [width, T] on the MXU, relu, the weighted sum over heads, in
float32; 2 x heads x width operations for width x itemsize bytes a position,
under the ridge. It is named `paged_index_scores` in the device trace. The
step then reads the latents it chose by token address (`paged_latent_rows`:
a gather of arena rows, two tokens each) and no others.

`q` is [b, nh, hd] (ONE query position per row — the decode shape);
`table` is [b, n_tbl] int32; `key_mask` is [b, n_tbl*block] key validity
over logical columns. Rows whose mask is all-zero (inactive slots: the
call site masks a row that has no token this step) return exact 0.0 —
the engine overwrites their sampled token anyway.

Kernel selection lives with the caller (`InferenceEngine`, through
`ops.attention.kernel_mode`), which also counts per-dispatch fallbacks
for shapes this kernel does not express (multi-position spec-verify
queries, alibi biases, prefix tuning).
`paged_attention_reference` is the XLA shadow of the gather path, kept
here so tests can pin both semantics side by side.
"""

import functools
from typing import Dict, NamedTuple, Optional, Tuple

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


def init_paged_latent_layer(num_blocks: int, block_size: int, width: int, dtype) -> Dict[str, jnp.ndarray]:
    """One latent layer's zeroed arena: ONE plane of `width` values a token,
    two tokens a row, `[blocks, block / 2, 2 * width]` (module docstring,
    "Latent"). An int8 plane is refused: its scales, write and kernel are
    not written."""
    if jnp.dtype(dtype) == jnp.int8:
        raise NotImplementedError(
            "an int8 latent arena (kv_cache_dtype='int8' over latent_attention layers) is not "
            "supported: the latent plane is held in a floating type")
    if block_size % 2:
        raise ValueError(f"a latent arena holds two tokens a row: kv_block_size {block_size} must be even")
    return {"latent": jnp.zeros((num_blocks, block_size // 2, 2 * width), dtype)}


def _pack_latent(x, values: int):
    """[..., block, width] token rows -> [..., block / 2, 2 * width] arena
    rows: tokens 2r and 2r + 1 side by side, their leading `values` columns
    first, then their other columns: [v_2r | v_2r+1 | k_2r | k_2r+1]."""
    *lead, blk, width = x.shape
    pairs = x.reshape(*lead, blk // 2, 2, width)
    return jnp.concatenate([pairs[..., :values].reshape(*lead, blk // 2, 2 * values),
                            pairs[..., values:].reshape(*lead, blk // 2, 2 * (width - values))], axis=-1)


def _unpack_latent(rows, values: int):
    """`_pack_latent`'s inverse: [..., block / 2, 2 * width] -> [..., block, width]."""
    *lead, half, w2 = rows.shape
    head = rows[..., :2 * values].reshape(*lead, half, 2, values)
    tail = rows[..., 2 * values:].reshape(*lead, half, 2, w2 // 2 - values)
    return jnp.concatenate([head, tail], axis=-1).reshape(*lead, 2 * half, w2 // 2)


def _touched_blocks(table, start, valid, rows, t: int, n_blocks: int, blk: int):
    """The whole blocks a call's runs of t columns can straddle (`paged_kv_write`):
    their physical ids `phys` [b, n_touch] (n_blocks where nothing of this call
    lands), for each of their columns the call's position `src` [b, n_touch*blk]
    it holds, and whether one does, `live` [b, n_touch, blk]. `rows`: arange(b)[:, None]."""
    b, n_tbl = table.shape
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
    return phys, src, live


def paged_latent_write(layer, latent, table, start, valid=None, *, values: int) -> Dict[str, jnp.ndarray]:
    """`paged_kv_write` for a latent layer: `latent` [b, t, width] into the
    one plane, whole blocks patched over the arena's major dimension.
    `values`: the leading columns of a latent that are its value (`_pack_latent`)."""
    arena = layer["latent"]
    n_blocks, half, w2 = arena.shape
    blk, width = 2 * half, w2 // 2
    b, t = latent.shape[:2]
    valid = jnp.ones((b, t), bool) if valid is None else valid.astype(bool)
    rows = jnp.arange(b)[:, None]
    phys, src, live = _touched_blocks(table, start, valid, rows, t, n_blocks, blk)
    new = latent[rows, src].reshape(b, -1, blk, width)
    keep = jnp.broadcast_to(live[..., None], new.shape)
    patched = jnp.where(_pack_latent(keep, values), _pack_latent(new.astype(arena.dtype), values), arena[phys])
    return {"latent": arena.at[phys.reshape(-1)].set(patched.reshape(-1, half, w2), mode="drop")}


def paged_latent_gather(layer, table, *, values: int) -> jnp.ndarray:
    """The gather read path of a latent layer: each row's table blocks as
    one dense `[b, n_tbl*block, width]` view, a token a row."""
    rows = _unpack_latent(layer["latent"][table], values)  # [b, n_tbl, block, width]
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def init_paged_plane(num_blocks: int, block_size: int, width: int, dtype) -> jnp.ndarray:
    """A zeroed arena of one plain plane, a token a row: `[blocks, block,
    width]` (a sparse latent layer's index keys: module docstring, "Index")."""
    return jnp.zeros((num_blocks, block_size, width), dtype)


def paged_plane_write(layer, name: str, x, table, start, valid=None) -> Dict[str, jnp.ndarray]:
    """`paged_kv_write` for the plain plane `layer[name]`: `x` [b, t, width],
    whole blocks patched over the arena's major dimension."""
    arena = layer[name]
    n_blocks, blk, width = arena.shape
    b, t = x.shape[:2]
    valid = jnp.ones((b, t), bool) if valid is None else valid.astype(bool)
    rows = jnp.arange(b)[:, None]
    phys, src, live = _touched_blocks(table, start, valid, rows, t, n_blocks, blk)
    new = x[rows, src].reshape(b, -1, blk, width).astype(arena.dtype)
    patched = jnp.where(live[..., None], new, arena[phys])
    return {name: arena.at[phys.reshape(-1)].set(patched.reshape(-1, blk, width), mode="drop")}


def paged_plane_gather(arena, table) -> jnp.ndarray:
    """The gather read path of a plain plane: `[b, n_tbl*block, width]`."""
    rows = arena[table]  # [b, n_tbl, block, width]
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def paged_latent_rows(arena, table, columns, *, values: int) -> jnp.ndarray:
    """The latents at a row's chosen logical `columns` [b, k] -> [b, k,
    width], by token address: the arena row that holds the token (two tokens
    a row, `_pack_latent`) is gathered and the token's half of each part
    taken. Columns past the table read the table's last entry: the caller
    masks what it did not choose."""
    n_blocks, half, w2 = arena.shape
    blk, width = 2 * half, w2 // 2
    entry = jnp.clip(columns // blk, 0, table.shape[1] - 1)
    phys = jnp.take_along_axis(table, entry, axis=1)
    offset = columns % blk
    pairs = arena.reshape(n_blocks * half, w2)[phys * half + offset // 2]  # [b, k, 2 * width]
    odd = (offset % 2 == 1)[..., None]
    head = jnp.where(odd, pairs[..., values:2 * values], pairs[..., :values])
    tail = jnp.where(odd, pairs[..., width + values:], pairs[..., 2 * values:width + values])
    return jnp.concatenate([head, tail], axis=-1)


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
    view: patching its [1, nkv*block] rows makes XLA re-lay the plane.

    Who calls it (`models/transformer.py:Attention`): every prefill and
    insert (t > 1: whole blocks are written and the
    scatter is the right tool), every step of the gather read path, and a
    decode step (t == 1) in front of the kernel where the kernel does not
    write: int8 arenas and their planes, and arenas whose blocks are
    operands (heads of 64, the `-tiny` models). Elsewhere a decode step's
    write is `paged_attention_decode`'s own (module docstring, "Write")."""
    n_blocks, nkv, blk, _ = layer["k"].shape
    b, t = k.shape[:2]
    n_tbl = table.shape[1]
    valid = jnp.ones((b, t), bool) if valid is None else valid.astype(bool)
    rows = jnp.arange(b)[:, None]
    phys, src, live = _touched_blocks(table, start, valid, rows, t, n_blocks, blk)
    n_touch = phys.shape[1]

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


# VMEM budget (module docstring, "Grid and tile"): the tile's buffers may
# take `_TILE_VMEM_BYTES` and the tile shrinks to fit; the whole call is
# compiled under `_VMEM_LIMIT_BYTES`, which Mosaic enforces.
_TILE_VMEM_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT_BYTES = 12 * 1024 * 1024
# (tokens, entries) a grid step takes at most, by whether the kernel copies
# its blocks itself (module docstring, "Fetch": measured, PERF.md section 6, PR 56)
_TILE = {True: (512, 16), False: (256, 8)}


def _sublanes(dtype) -> int:
    """Rows of the type's (sublane, 128-lane) tile: 8 of float32, 16 of bfloat16, 32 of int8."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _vmem_block_bytes(nkv: int, blk: int, hd: int, dtype) -> int:
    """VMEM bytes of one [nkv, blk, hd] arena block: the last two dims
    padded to the type's (sublane, 128-lane) tile."""
    rows = -(-blk // _sublanes(dtype)) * _sublanes(dtype)
    return nkv * rows * (-(-hd // 128) * 128) * jnp.dtype(dtype).itemsize


def copies_blocks(nkv: int, blk: int, hd: int, dtype) -> bool:
    """Whether the kernel copies a tile's blocks itself (module docstring,
    "Fetch"): where Mosaic can slice one block out of the arena, which it
    does by whole (sublane, lane) tiles of the last two dims."""
    whole = hd % 128 == 0 and blk % _sublanes(dtype) == 0
    return whole and (jnp.dtype(dtype) != jnp.int8 or (nkv * blk) % 128 == 0)  # its planes: [1, nkv*blk] rows


def writes_in_kernel(arena) -> bool:
    """Whether a decode step's K and V reach this arena from `paged_attention_decode` itself
    (module docstring, "Write"): where the kernel copies a tile's blocks, and not into an
    int8 arena, whose scale planes take their elements one by one (`paged_kv_write`)."""
    _, nkv, blk, hd = arena.shape
    return arena.dtype != jnp.int8 and copies_blocks(nkv, blk, hd, arena.dtype)


def _tile_entries(n_tbl: int, nkv: int, blk: int, hd: int, dtype) -> int:
    """Table entries a grid step takes: `_TILE`'s tokens' worth, at most its
    entries and the whole table, shrunk until K and V tiles, double-buffered,
    fit `_TILE_VMEM_BYTES`."""
    tokens, most = _TILE[copies_blocks(nkv, blk, hd, dtype)]
    entries = max(1, min(tokens // blk, most, n_tbl))
    while entries > 1 and 4 * entries * _vmem_block_bytes(nkv, blk, hd, dtype) > _TILE_VMEM_BYTES:
        entries -= 1
    return entries


def _paged_decode_kernel(blocks_ref, row_ref, tile_ref, n_live_ref, *rest, entries: int, n_tbl: Optional[int],
                         scale: float, quantized: bool, p_dtype, windowed: bool = False, writes: bool = False):
    """One grid step: tile `tile_ref[w]` of row `row_ref[w]`; mask invalid
    columns and fold the tile into the row's online softmax, all kv heads in
    one batched product. `n_tbl` names how the tile's blocks reach VMEM
    (module docstring, "Fetch"): the table's length, and the step starts the
    copies of step w + 1's live blocks into the other half of a scratch and
    waits for its own; or None, and each entry's block is an operand that
    the pipeline fetched. `writes` (a call that copies, no int8 arena): the
    step whose tile holds the row's new column selects the step's K and V
    row into that entry's blocks in the scratch before the tile is
    multiplied, and copies the patched rows back to the arena (module
    docstring, "Write").

    blocks_ref scalar prefetch: the table, [b * n_tbl], that the copies read;
               or [n_steps * entries], what drives the operands' index maps
    row_ref / tile_ref scalar prefetch [n_steps]: the step's row and tile
    n_live_ref scalar prefetch [b]: each row's live table entries
    n_first_ref scalar prefetch [b], a windowed call that copies: each row's
               entries in front of its band (not copied, like those past `n_live`)
    col_ref    scalar prefetch [b], `writes`: the column a row's new K/V lands at, -1 for none
    q_ref      [1, nkv, group, hd]
    new_ref    [1, 2, nkv, 1, hd], `writes`: the row's new K and V in the arena's type
    K, V       the arenas where they lie, [n_blocks, nkv, blk, hd], and for
               int8 their scale planes, [n_blocks, 1, nkv*blk] f32;
               or `entries` x [1, nkv, blk, hd] each (and `entries` x [1, 1, nkv*blk])
    mask_ref   [1, 1, 1, entries*blk] int32 key validity of the tile
    o_ref      [1, nkv, group, hd]
    k_out, v_out  `writes`: the arenas again, the call's aliased outputs, which the write goes to
    kv_buf     VMEM [2 buffers, 2 sides, entries, nkv, blk, hd], sc_buf VMEM
               [2, 2, entries, 1, nkv*blk] f32 (int8), sem: a DMA semaphore a
               buffer; a call that copies only. wsem, `writes`: the write's semaphore
    m_scr/l_scr VMEM [nkv, group, 1] f32 running max / denominator,
               acc_scr VMEM [nkv, group, hd] numerator
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    E = entries
    copied = n_tbl is not None
    n_first_ref = col_ref = new_ref = None
    if windowed and copied:
        n_first_ref, rest = rest[0], rest[1:]
    if writes:
        col_ref, rest = rest[0], rest[1:]
    q_ref, rest = rest[0], rest[1:]
    if writes:
        new_ref, rest = rest[0], rest[1:]
    per = 1 if copied else E  # refs a plane: the plane where it lies, or an operand an entry
    n_planes = 4 if quantized else 2  # K, V, then an int8 arena's scale planes of K and of V
    planes = [rest[i * per:(i + 1) * per] for i in range(n_planes)]
    mask_ref, o_ref, *bufs, m_scr, l_scr, acc_scr = rest[n_planes * per:]
    if writes:
        *arenas_out, kv_buf, sem, wsem = bufs
        bufs = [kv_buf]
    elif copied:
        *bufs, sem = bufs  # kv_buf, then an int8 arena's sc_buf
    w = pl.program_id(0)
    r = row_ref[w]
    first_entry = tile_ref[w] * E
    n_live = n_live_ref[r]

    if copied:
        buf = w % 2

        def span(step):
            """Step `step`'s row, its tile's first entry and its live entries [lo, hi)."""
            row = row_ref[step]
            base = tile_ref[step] * E
            lo = base if n_first_ref is None else jnp.maximum(base, n_first_ref[row])
            return row, base, lo, jnp.minimum(base + E, n_live_ref[row])

        def copies(step, into, start: bool):
            """Start (or wait for) the copies of `step`'s live blocks into half
            `into` of the scratch; a wait needs a copy's size, not its source."""
            row, base, lo, hi = span(step)

            def one(e, carry):
                block = blocks_ref[row * n_tbl + e] if start else 0
                for i, (plane,) in enumerate(planes):
                    copy = pltpu.make_async_copy(
                        plane.at[block], bufs[i // 2].at[into, i % 2, e - base], sem.at[into])
                    copy.start() if start else copy.wait()
                return carry

            jax.lax.fori_loop(lo, hi, one, 0)

        @pl.when(w == 0)
        def _first():
            copies(0, 0, True)

        @pl.when(w + 1 < pl.num_programs(0))
        def _next():  # across a row's end as well: the next step's walk is in SMEM
            copies(w + 1, 1 - buf, True)

        copies(w, buf, False)
        _, _, lo, hi = span(w)

    def block(i, e, *at):
        """Entry e's block of plane i (K, V, K's scales, V's scales), or the part `at` of it."""
        return bufs[i // 2][(buf, i % 2, e, *at)] if copied else planes[i][e][(0, *at)]

    nkv, blk = (bufs[0] if copied else planes[0][0]).shape[-3:-1]

    def tile(side, dtype):
        """[nkv, entries*blk, hd]: the entries' blocks side by side."""
        return jnp.concatenate([block(side, e).astype(dtype) for e in range(E)], axis=1)

    def scale_tile(side):
        """[nkv, 1, entries*blk] from the planes' head-major lane rows."""
        return jnp.stack([
            jnp.concatenate([block(2 + side, e, slice(None), slice(h * blk, (h + 1) * blk)) for e in range(E)], axis=1)
            for h in range(nkv)
        ])

    if writes:
        # the row's new column, if it lands in this tile (its last one, where the engine calls:
        # the query's own column is the row's last attendable one): entry `at` of the tile,
        # row `off` of that block, inside the slab of `slab` rows (one sublane tile of the type)
        col = col_ref[r]
        at = jnp.maximum(col, 0) // blk - first_entry
        lands = (col >= 0) & (at >= 0) & (at < E)
        slab = _sublanes(kv_buf.dtype)

        def write_back(start: bool):
            """Select the step's K and V row into its slab of the scratch and start the slabs' copy
            to the arena, or wait for that copy."""
            off = jnp.maximum(col, 0) % blk
            rows = pl.ds(pl.multiple_of(off // slab * slab, slab), slab)
            place = jnp.clip(at, 0, E - 1)
            block = blocks_ref[r * n_tbl + first_entry + place] if start else 0
            for side, arena in enumerate(arenas_out):
                held = kv_buf.at[buf, side, place, :, rows, :]  # [nkv, slab, hd]
                if start:
                    here = jax.lax.broadcasted_iota(jnp.int32, held.shape, 1) == off % slab
                    held[...] = jnp.where(here, new_ref[0, side], held[...])
                copy = pltpu.make_async_copy(held, arena.at[block, :, rows, :], wsem)
                copy.start() if start else copy.wait()

        pl.when(lands)(lambda: write_back(True))

    @pl.when((w == 0) | (row_ref[jnp.maximum(w - 1, 0)] != r))  # the row's first tile
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first_entry < n_live)
    def _tile():
        if copied:
            @pl.when(hi - lo < E)
            def _dead():
                # no copy wrote a dead entry's place: its scores are masked, but
                # its values meet probabilities of 0, and 0 x NaN is NaN
                def zero(e, carry):
                    bufs[0][buf, 1, e - first_entry] = jnp.zeros(bufs[0].shape[3:], bufs[0].dtype)
                    return carry

                jax.lax.fori_loop(first_entry, lo, zero, 0)
                jax.lax.fori_loop(hi, first_entry + E, zero, 0)

        q = q_ref[0]  # [nkv, group, hd], already in the product's type
        valid = mask_ref[0, 0] > 0  # [1, T]
        s = jax.lax.dot_general(
            q, tile(0, q.dtype), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [nkv, group, T]
        if quantized:
            s = s * scale_tile(0)
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[:], l_scr[:]  # [nkv, group, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # Fully-masked-so-far rows keep m == NEG_INF; clamp the shift so
        # the exp below cannot blow up to exp(0)=1 on masked entries.
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=2, keepdims=True)
        m_scr[:] = m_new
        if quantized:
            p = jnp.where(valid, p * scale_tile(1), 0.0)  # a dead entry's scales are no copy's either
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(p_dtype), tile(1, p_dtype), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(first_entry + E >= n_live)  # the row's last tile
    def _finalize():
        l = l_scr[:]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)

    if writes:
        # behind the products, which hid it: the next step starts step w + 2's copies into this half
        pl.when(lands)(lambda: write_back(False))


def band_mask(key_mask, window: int):
    """`key_mask` [b, S] without the columns that trail a row's last
    attendable column (the query's own, written this step) by `window` or
    more: the sliding-window band over logical columns, which are positions
    wherever a row's columns are filled without holes, as the engine fills
    them."""
    cols = jnp.arange(key_mask.shape[1], dtype=jnp.int32)
    valid = key_mask.astype(bool)
    last_col = jnp.max(jnp.where(valid, cols + 1, 0), axis=1)
    return valid & (cols[None, :] >= (last_col - window)[:, None])


class _Walk(NamedTuple):
    """The grid's walk (`_live_walk`; module docstring, "Schedule")."""
    row: jnp.ndarray     # [n_steps] the step's row
    tile: jnp.ndarray    # [n_steps] and its tile of that row
    n_live: jnp.ndarray  # [b] each row's live table entries
    n_work: jnp.ndarray  # scalar: the steps that hold work, the grid's length
    n_tiles: int         # a row's tiles at most (static)
    n_first: Optional[jnp.ndarray]  # [b], `windowed`: a row's entries in front of its first attendable column
    tile0: Optional[jnp.ndarray]    # [b], `windowed`: the first tile a row's walk keeps
    step: jnp.ndarray    # [n_steps] arange


def _live_walk(table, key_mask, blk: int, entries: int, windowed: bool = False) -> _Walk:
    """The grid's walk, from the table and the mask. `windowed`, it leaves
    out the tiles in front of a row's first attendable column too (a banded
    mask: `band_mask`)."""
    b, n_tbl = table.shape
    n_tiles = -(-n_tbl // entries)
    n_steps = b * n_tiles
    cols = jnp.arange(n_tbl * blk, dtype=jnp.int32)
    last_col = jnp.max(jnp.where(key_mask.astype(bool), cols + 1, 0), axis=1)
    n_live = ((last_col + blk - 1) // blk).astype(jnp.int32)  # [b]
    # a row's tiles, one even for an all-masked row: its step writes the zeros
    tiles = jnp.maximum(1, (n_live + entries - 1) // entries)
    n_first = tile0 = None
    if windowed:
        first_col = jnp.min(jnp.where(key_mask.astype(bool), cols, n_tbl * blk), axis=1)
        n_first = jnp.minimum(first_col // blk, n_live).astype(jnp.int32)  # [b] entries in front
        tile0 = n_first // entries
        tiles = jnp.maximum(1, (n_live + entries - 1) // entries - tile0)
    ends = jnp.cumsum(tiles)
    n_work = ends[-1]
    step = jnp.arange(n_steps, dtype=jnp.int32)
    at = jnp.minimum(step, n_work - 1)  # past the work: never walked
    row = jnp.sum(at[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    tile = at - (ends - tiles)[row]
    if windowed:
        tile = tile + tile0[row]
    return _Walk(row, tile, n_live, n_work, n_tiles, n_first, tile0, step)


def _live_schedule(table, key_mask, blk: int, entries: int, windowed: bool = False):
    """`_live_walk` for a kernel whose blocks are `BlockSpec` operands (the
    latent and the index kernel, and `paged_attention_decode` where it does
    not copy), with the block each operand names in each step: `blocks`
    [n_steps*entries], then `row`, `tile`, `n_live`, `n_work`, n_tiles and,
    `windowed`, `first` [b], the first entry of the first tile a row's walk
    keeps."""
    n_tbl = table.shape[1]
    row, tile, n_live, n_work, n_tiles, n_first, tile0, step = _live_walk(table, key_mask, blk, entries, windowed)
    entry = tile[:, None] * entries + jnp.arange(entries, dtype=jnp.int32)[None, :]
    live = entry < n_live[row][:, None]  # [n_steps, entries]
    if windowed:
        # an entry in front of the band is, like one past the row's end,
        # neither fetched nor (the mask) attended to
        live &= entry >= n_first[row][:, None]
    table = jnp.pad(table.astype(jnp.int32), ((0, 0), (0, n_tiles * entries - n_tbl)))
    named = table[row[:, None], entry]
    # forward fill down each operand's column: where its entry is not
    # live the operand keeps the block it held the step before
    held = jax.lax.cummax(jnp.where(live, step[:, None], -1), axis=0)
    first = named.reshape(-1)[jnp.argmax(live.reshape(-1))]
    blocks = jnp.where(
        held >= 0, jnp.take_along_axis(named, jnp.maximum(held, 0), axis=0), first)
    if windowed:
        return blocks.reshape(-1), row, tile, n_live, n_work, n_tiles, tile0 * entries
    return blocks.reshape(-1), row, tile, n_live, n_work, n_tiles


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
    window: Optional[int] = None,
    new_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # the step's K and V, [b, nkv, hd] each
    column: Optional[jnp.ndarray] = None,  # [b] the logical column they land at
):
    """Fused paged decode attention. Returns [b, nh, hd] in `out_dtype`
    (defaults to q's dtype). Grid, tile and schedule: module docstring.
    `window` (static, a layer's own) keeps a row's last `window` columns
    (`band_mask`) and walks only the table entries that hold one of them;
    such a call is named `paged_decode_window` in the device trace.

    With `new_kv` and `column` the call WRITES the step's keys and values
    too, in place of a `paged_kv_write` in front of it (module docstring,
    "Write"; `writes_in_kernel` says for which arenas): row r's `new_kv`
    lands at `column[r]` and is attended to in the same call, and the
    return is (output, k_arena, v_arena), the arenas aliased to the
    operands. A row writes if its walk holds the table entry of `column[r]`:
    if `key_mask` attends to a column of that entry or of a later one (the
    engine's mask does: the query's own column is the row's last, unless the
    row has no token this step and is all-masked, and then it writes nothing,
    as `paged_kv_write` drops a position that is not valid).

    The call itself is one jitted function (`_paged_decode_call`): the layers
    of a program share its shapes, so a program traces and lowers the walk
    and the kernel's body once, not once a layer (tracing is most of a warm
    set-up: PERF.md section 5)."""
    # (not under the TPU interpreter, `interpret=pltpu.InterpretParams(...)`: its callbacks run JAX
    # operations of their own, which deadlock with the caller's next one behind a jit's asynchronous dispatch)
    call = _paged_decode_call if isinstance(interpret, bool) else _paged_decode_call.__wrapped__
    return call(q, k_arena, v_arena, table, key_mask, k_scale, v_scale, new_kv, column,
                out_dtype=jnp.dtype(out_dtype or q.dtype), interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret", "window"))
def _paged_decode_call(q, k_arena, v_arena, table, key_mask, k_scale, v_scale, new_kv, column, *,
                       out_dtype, interpret, window):
    """`paged_attention_decode`, its options static."""
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

    # The products' operand types. K and V enter in the arena's own type
    # (int8 converts exactly) unless q or the output is wider; a bfloat16
    # x bfloat16 product accumulated in float32 is exact. Probabilities
    # are rounded to the output type before p.v for a bfloat16 or int8
    # arena, as the gather path rounds them (`paged_attention_reference`),
    # and stay float32 for a float32 arena.
    kv_dtype = q.dtype if quantized else k_arena.dtype
    qk_dtype = jnp.promote_types(q.dtype, kv_dtype)
    p_dtype = jnp.float32 if kv_dtype == jnp.float32 else jnp.promote_types(out_dtype, kv_dtype)

    copied = copies_blocks(nkv, blk, hd, k_arena.dtype)
    writes = new_kv is not None
    if writes and not writes_in_kernel(k_arena):
        raise ValueError(f"the paged kernel does not write into a {k_arena.dtype} arena of {k_arena.shape[1:]} "
                         "blocks: `paged_kv_write` in front of the call does")
    E = _tile_entries(n_tbl, nkv, blk, hd, k_arena.dtype)
    windowed = window is not None
    if windowed:
        key_mask = band_mask(key_mask, window)
    T = E * blk
    # Head order matches the dense path's (and jnp.repeat(k, group, axis=2)):
    # q head h attends kv head h // group, so [b, nh, hd] -> [b, nkv,
    # group, hd] keeps each kv head's q-group contiguous.
    qg = q.reshape(b, nkv, group, hd).astype(qk_dtype)

    def slot_index(w, blocks_ref, row_ref, *_):
        return (row_ref[w], 0, 0, 0)

    def entry_index(e, ndim):
        def index(w, blocks_ref, *_):
            return (blocks_ref[w * E + e],) + (0,) * (ndim - 1)
        return index

    planes = [k_arena, v_arena] + ([k_scale, v_scale] if quantized else [])
    scratch = []
    if copied:
        # the arenas (and an int8 arena's planes) enter where they lie and
        # the kernel copies a tile's live blocks itself, by the table
        walk = _live_walk(table, key_mask, blk, E, windowed)
        row, tile, n_live, n_work, n_tiles = walk[:5]
        scalars = [table.astype(jnp.int32).reshape(-1), row, tile, n_live] + ([walk.n_first] if windowed else [])
        if writes:
            # a row writes where its walk copies the entry that holds the column: a live entry names a block
            column = column.astype(jnp.int32)
            entry = column // blk
            walked = (column >= 0) & (entry >= (walk.n_first if windowed else 0)) & (entry < n_live)
            scalars.append(jnp.where(walked, column, -1))
        operands = planes
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * len(planes)
        scratch = [pltpu.VMEM((2, 2, E) + plane.shape[1:], plane.dtype) for plane in planes[::2]]
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
    else:
        # each entry's block of each plane is an operand of its own
        blocks, row, tile, n_live, n_work, n_tiles, *_ = _live_schedule(table, key_mask, blk, E, windowed)
        scalars = [blocks, row, tile, n_live]
        operands = [plane for plane in planes for _ in range(E)]
        kv_specs = [pl.BlockSpec((1,) + plane.shape[1:], entry_index(e, plane.ndim))
                    for plane in planes for e in range(E)]
    maskh = jnp.pad(key_mask.astype(jnp.int32), ((0, 0), (0, n_tiles * T - n_tbl * blk)))
    maskh = maskh.reshape(b, n_tiles, 1, T)
    mask_spec = pl.BlockSpec(
        (1, 1, 1, T), lambda w, blocks_ref, row_ref, tile_ref, *_: (row_ref[w], tile_ref[w], 0, 0))

    q_specs, out_specs = [pl.BlockSpec((1, nkv, group, hd), slot_index)], pl.BlockSpec((1, nkv, group, hd), slot_index)
    out_shape, aliases, rows = jax.ShapeDtypeStruct((b, nkv, group, hd), out_dtype), {}, [qg]
    if writes:
        # the step's rows ride beside q, one operand for K and V; the arenas come back as
        # outputs aliased to their operands (counted with the scalars: q, the rows, K, V)
        rows.append(jnp.stack(new_kv, axis=1).astype(k_arena.dtype).reshape(b, 2, nkv, 1, hd))
        q_specs.append(pl.BlockSpec((1, 2, nkv, 1, hd), lambda w, blocks_ref, row_ref, *_: (row_ref[w], 0, 0, 0, 0)))
        out_specs = [out_specs] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (k_arena, v_arena)]
        aliases = {len(scalars) + 2: 1, len(scalars) + 3: 2}
        scratch.append(pltpu.SemaphoreType.DMA(()))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_work,),  # the steps that hold work, read at run time
        in_specs=q_specs + kv_specs + [mask_spec],
        out_specs=out_specs,
        scratch_shapes=scratch + [
            pltpu.VMEM((nkv, group, 1), jnp.float32),   # m
            pltpu.VMEM((nkv, group, 1), jnp.float32),   # l
            pltpu.VMEM((nkv, group, hd), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, entries=E, n_tbl=n_tbl if copied else None, scale=1.0 / np.sqrt(hd),
            quantized=quantized, p_dtype=p_dtype, windowed=windowed, writes=writes,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="paged_decode_window" if windowed else "paged_decode",
    )(*scalars, *rows, *operands, maskh)
    if writes:
        return out[0].reshape(b, nh, hd), out[1], out[2]
    return out.reshape(b, nh, hd)


# A latent tile is one plane and its rows are 4.5 lane tiles wide: 512 tokens
# a grid step, its blocks `BlockSpec` operands (`_live_schedule`).
_LATENT_TILE_TOKENS = 512
_MAX_LATENT_TILE_ENTRIES = 16


def _paged_latent_kernel(blocks_ref, row_ref, tile_ref, n_live_ref, *rest,
                         entries: int, scale: float, values: int, p_dtype, windowed: bool = False):
    """One grid step of `paged_attention_latent`: tile `tile_ref[w]` of row
    `row_ref[w]`, its `entries` latent blocks in VMEM one under another as
    [T / 2, 2 * width] rows of two tokens (`_pack_latent`). Every head's
    absorbed query multiplies the tile, and the value product reads the
    same tile's value columns: one fetch serves both. The tile's T columns
    are its even tokens, then its odd ones (the mask arrives in that order;
    a softmax does not mind). Scalar prefetch as `_paged_decode_kernel`,
    `first_ref` of a windowed call too.

    q_ref      [1, nh, values]: the queries against the value columns
    qr_ref     [1, 2 * nh, 2 * rest]: against the other columns of a row's two
               tokens, [q_rest | 0] for the even token over [0 | q_rest]
    c_refs     `entries` x [1, blk / 2, 2 * width]
    mask_ref   [1, 1, 1, T] int32 key validity, even tokens then odd
    o_ref      [1, nh, values]
    m_scr/l_scr VMEM [nh, 1] f32, acc_scr VMEM [nh, values] f32
    """
    import jax.experimental.pallas as pl

    E = entries
    first_ref = None
    if windowed:
        first_ref, rest = rest[0], rest[1:]
    q_ref, qr_ref, rest = rest[0], rest[1], rest[2:]
    c_refs, (mask_ref, o_ref, m_scr, l_scr, acc_scr) = rest[:E], rest[E:]
    w = pl.program_id(0)
    first_entry = tile_ref[w] * E
    n_live = n_live_ref[row_ref[w]]
    nh = q_ref.shape[1]

    @pl.when(first_entry == (0 if first_ref is None else first_ref[row_ref[w]]))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first_entry < n_live)
    def _tile():
        q, qr = q_ref[0], qr_ref[0]  # already in the product's type
        rows = jnp.concatenate([r[0].astype(q.dtype) for r in c_refs], axis=0)  # [T / 2, 2 * width]
        # [T, values]: the even tokens' value columns over the odd tokens'
        vals = jnp.concatenate([rows[:, :values], rows[:, values:2 * values]], axis=0)
        nt = (((1,), (1,)), ((), ()))
        s_rest = jax.lax.dot_general(qr, rows[:, 2 * values:], nt, preferred_element_type=jnp.float32)
        s = jax.lax.dot_general(q, vals, nt, preferred_element_type=jnp.float32)  # [nh, T]
        s = (s + jnp.concatenate([s_rest[:nh], s_rest[nh:]], axis=1)) * scale
        s = jnp.where(mask_ref[0, 0] > 0, s, NEG_INF)

        m_prev, l_prev = m_scr[:], l_scr[:]  # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # as `_paged_decode_kernel`: masked-so-far rows keep m == NEG_INF
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - shift)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(p_dtype), vals.astype(p_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(first_entry + E >= n_live)  # the row's last tile
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def paged_attention_latent(
    q: jnp.ndarray,          # [b, nh, width] absorbed queries
    arena: jnp.ndarray,      # [n_blocks, blk / 2, 2 * width]
    table: jnp.ndarray,      # [b, n_tbl] int32 physical block ids
    key_mask: jnp.ndarray,   # [b, n_tbl*blk] key validity (1 = attend)
    *,
    values: int,             # the leading columns of a latent that are its value
    scale: float,            # on the scores: 1 / sqrt(the decompressed query/key width)
    out_dtype=None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused paged decode attention over a latent arena (module docstring,
    "Latent"). Returns [b, nh, values] in `out_dtype` (defaults to q's):
    softmax(q . latent * scale) over a row's attendable latents, times their
    first `values` columns. Grid, schedule and masking as
    `paged_attention_decode`, its `window` too; named `paged_decode_latent`
    in the device trace, `paged_decode_latent_window` with a window."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nh, width = q.shape
    n_blocks, half, w2 = arena.shape
    blk, n_tbl = 2 * half, table.shape[1]
    out_dtype = jnp.dtype(out_dtype or q.dtype)
    qk_dtype = jnp.promote_types(q.dtype, arena.dtype)
    p_dtype = jnp.float32 if arena.dtype == jnp.float32 else jnp.promote_types(out_dtype, arena.dtype)

    E = max(1, min(_LATENT_TILE_TOKENS // blk, _MAX_LATENT_TILE_ENTRIES, n_tbl))
    windowed = window is not None
    if windowed:
        key_mask = band_mask(key_mask, window)
    blocks, row, tile, n_live, n_work, n_tiles, *first = _live_schedule(table, key_mask, blk, E, windowed)
    T = E * blk
    maskh = jnp.pad(key_mask.astype(jnp.int32), ((0, 0), (0, n_tiles * T - n_tbl * blk)))
    # a tile's even tokens, then its odd ones: the order the kernel's scores come in
    maskh = maskh.reshape(b, n_tiles, T // 2, 2).swapaxes(2, 3).reshape(b, n_tiles, 1, T)
    q = q.astype(qk_dtype)
    q_rest, zeros = q[..., values:], jnp.zeros((b, nh, width - values), qk_dtype)
    qr = jnp.concatenate([jnp.concatenate([q_rest, zeros], axis=-1),
                          jnp.concatenate([zeros, q_rest], axis=-1)], axis=1)  # [b, 2 nh, 2 rest]

    def slot_index(w, blocks_ref, row_ref, *_):
        return (row_ref[w], 0, 0)

    def entry_index(e):
        return lambda w, blocks_ref, *_: (blocks_ref[w * E + e], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(first),
        grid=(n_work,),  # the steps that hold work, read at run time
        in_specs=[pl.BlockSpec((1, nh, values), slot_index),
                  pl.BlockSpec((1, 2 * nh, 2 * (width - values)), slot_index)]
        + [pl.BlockSpec((1, half, w2), entry_index(e)) for e in range(E)]
        + [pl.BlockSpec((1, 1, 1, T),
                        lambda w, blocks_ref, row_ref, tile_ref, *_: (row_ref[w], tile_ref[w], 0, 0))],
        out_specs=pl.BlockSpec((1, nh, values), slot_index),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),       # m
            pltpu.VMEM((nh, 1), jnp.float32),       # l
            pltpu.VMEM((nh, values), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, entries=E, scale=scale, values=values, p_dtype=p_dtype,
                          windowed=windowed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, values), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="paged_decode_latent_window" if windowed else "paged_decode_latent",
    )(blocks, row, tile, n_live, *first, q[..., :values], qr, *([arena] * E), maskh)


def _paged_index_kernel(blocks_ref, row_ref, tile_ref, n_live_ref, q_ref, w_ref, *rest, entries: int):
    """One grid step of `paged_index_scores`: the index's score of the
    `entries * blk` columns of tile `tile_ref[w]` of row `row_ref[w]`.

    q_ref  [1, G, D] the row's index queries, w_ref [1, G, 1] float32 their weights
    k_refs `entries` x [1, blk, D]: the tile's blocks of index keys
    o_ref  [1, 1, 1, T] float32
    """
    import jax.experimental.pallas as pl

    k_refs, o_ref = rest[:entries], rest[entries]
    w = pl.program_id(0)

    @pl.when(tile_ref[w] * entries < n_live_ref[row_ref[w]])
    def _tile():
        q = q_ref[0]
        keys = jnp.concatenate([r[0].astype(q.dtype) for r in k_refs], axis=0)  # [T, D]
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[0, 0] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)


def paged_index_scores(
    q: jnp.ndarray,         # [b, G, D] the index's queries, one position a row
    w: jnp.ndarray,         # [b, G] float32 the heads' weights
    arena: jnp.ndarray,     # [n_blocks, blk, D] the index keys (`init_paged_plane`)
    table: jnp.ndarray,     # [b, n_tbl] int32 physical block ids
    key_mask: jnp.ndarray,  # [b, n_tbl*blk] key validity (1 = may be chosen)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """The index's scores of a row's cached columns (module docstring,
    "Index"): [b, n_tbl*blk] float32, sum_g w_g relu(q_g . k_j) at every
    column the mask allows and -inf elsewhere. One pass over each row's live
    table entries, as `paged_attention_decode` walks them; named
    `paged_index_scores` in the device trace."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, G, D = q.shape
    n_blocks, blk, _ = arena.shape
    n_tbl = table.shape[1]
    E = max(1, min(_LATENT_TILE_TOKENS // blk, _MAX_LATENT_TILE_ENTRIES, n_tbl))
    blocks, row, tile, n_live, n_work, n_tiles = _live_schedule(table, key_mask, blk, E)
    T = E * blk

    def slot_index(w_, blocks_ref, row_ref, *_):
        return (row_ref[w_], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_work,),  # the steps that hold work, read at run time
        in_specs=[pl.BlockSpec((1, G, D), slot_index), pl.BlockSpec((1, G, 1), slot_index)]
        + [pl.BlockSpec((1, blk, D), (lambda e: lambda w_, blocks_ref, *_: (blocks_ref[w_ * E + e], 0, 0))(e))
           for e in range(E)],
        out_specs=pl.BlockSpec(
            (1, 1, 1, T), lambda w_, blocks_ref, row_ref, tile_ref, *_: (row_ref[w_], tile_ref[w_], 0, 0)),
    )
    scores = pl.pallas_call(
        functools.partial(_paged_index_kernel, entries=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_tiles, 1, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="paged_index_scores",
    )(blocks, row, tile, n_live, q.astype(jnp.promote_types(q.dtype, arena.dtype)),
      w.astype(jnp.float32)[..., None], *([arena] * E))
    # a tile the walk left out was never written: the mask covers it
    return jnp.where(key_mask.astype(bool), scores.reshape(b, n_tiles * T)[:, :n_tbl * blk], -jnp.inf)


def pass_table(table: jnp.ndarray, t, passes: int, arena_blocks: int) -> jnp.ndarray:
    """A row's block table as pass `t` of a looped stack reads and writes through it
    (`TransformerLM.run_passes`): a looped layer's arena of `arena_blocks` blocks is `passes` pools
    laid end to end (`init_paged_kv_arena`), and pass t's keys and values of the block a row was
    handed lie at that block's id + t pools. An id past a pool's end (an insert's padding rows, a
    stale table: the write drops them) goes past the ARENA's end, and block 0, the zero block
    nobody is handed, becomes pass t's own first block, which nobody writes either. `t` may be
    traced. The one place the layout of a looped arena is written."""
    pool = arena_blocks // passes
    table = table.astype(jnp.int32)
    return jnp.where(table < pool, table + t * pool, arena_blocks)


def paged_index_reference(q, w, arena, table, key_mask):
    """XLA shadow of `paged_index_scores`: the plane gathered to a dense view."""
    keys = paged_plane_gather(arena, table).astype(q.dtype)  # [b, S, D]
    s = jnp.einsum("bgd,bsd->bgs", q, keys, preferred_element_type=jnp.float32)
    scores = jnp.einsum("bgs,bg->bs", jnp.maximum(s, 0.0), w.astype(jnp.float32))
    return jnp.where(key_mask.astype(bool), scores, -jnp.inf)


def paged_latent_reference(q, arena, table, key_mask, *, values: int, scale: float, out_dtype=None,
                           window: Optional[int] = None):
    """XLA shadow of a latent layer's gather read path (`LatentAttention`'s
    absorbed branch at t == 1): gather the table back to a dense view, dense
    softmax with the -1e9 additive bias, the value product on the leading
    `values` columns."""
    out_dtype = out_dtype or q.dtype
    if window is not None:
        key_mask = band_mask(key_mask, window)
    cached = paged_latent_gather({"latent": arena}, table, values=values)  # [b, S, width]
    bias = jnp.where(key_mask.astype(bool), 0.0, -1e9)[:, None, :]
    scores = jnp.einsum("bhc,bsc->bhs", q, cached, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(out_dtype)
    return jnp.einsum("bhs,bsc->bhc", probs, cached[..., :values]).astype(out_dtype)


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
    window: Optional[int] = None,
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
    if window is not None:
        key_mask = band_mask(key_mask, window)
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
