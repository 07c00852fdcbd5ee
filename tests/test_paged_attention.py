"""Pallas paged-attention decode kernel (trlx_tpu/ops/paged_attention.py)
and its engine wiring (inference.decode_kernel): the kernel in interpret
mode must match the gather read path — bitwise on greedy token streams
for f32 across slot reuse, block-boundary lengths and GQA ratios
(n_kv_heads ∈ {1, 2, n_heads}); within the established dequant tolerance
for int8 KV — while unsupported shapes fall back per dispatch with a
counted reason surfaced through kv_stats."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from trlx_tpu.inference import InferenceEngine
from trlx_tpu.ops import quant
from trlx_tpu.ops.attention import kernel_mode
from trlx_tpu.ops.paged_attention import (
    copies_blocks,
    _live_schedule,
    _tile_entries,
    paged_attention_decode,
    paged_attention_reference,
    paged_kv_write,
    writes_in_kernel,
)
from trlx_tpu.ops.sampling import GenerationConfig

EOS_FREE = 10_000  # an id the byte model never emits -> length-capped runs


def _build_trainer(preset, dtype="float32", extra=None):
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer.sft_trainer import SFTTrainer

    config = default_sft_config().evolve(
        model=dict(
            model_path=f"random:{preset}",
            model_extra_configs={"dtype": dtype, **(extra or {})},
        ),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, total_steps=0, tracker=None, batch_size=2),
    )
    return SFTTrainer(config)


@pytest.fixture(scope="module")
def trainers():
    """One tiny model per GQA ratio: gpt2-tiny (nkv == nh), llama-tiny
    (nkv == 2), bigcode-tiny (MQA, nkv == 1)."""
    return {p: _build_trainer(p) for p in ("gpt2-tiny", "llama-tiny", "bigcode-tiny")}


def make_engine(trainer, decode_kernel, max_new=8, **kw):
    gen_cfg = GenerationConfig(
        max_new_tokens=max_new, do_sample=False,
        eos_token_id=EOS_FREE, pad_token_id=trainer.tokenizer.pad_token_id,
    )
    return InferenceEngine(
        trainer.model, trainer.model_cfg, trainer.params, gen_cfg,
        num_slots=2, max_prompt_len=32, kv_paging=True, decode_kernel=decode_kernel,
        **{"kv_block_size": 8, **kw},
    )


def run_serial(engine, prompts, max_new=8, slot=0):
    """Decode each prompt to completion in the SAME slot — slot reuse with
    block reclaim between requests."""
    outs = []
    for p in prompts:
        engine.insert_requests([(np.asarray(p, np.int32), max_new)], [slot])
        toks = []
        for _ in range(max_new):
            t, lp, v, f = engine.step()
            if v[slot]:
                toks.append(int(t[slot]))
            if f[slot]:
                break
        engine.reclaim_slots([slot])
        outs.append(toks)
    return outs


# prompt lengths straddling the kv_block_size=8 boundaries: 7 (inside
# block 0), 8 (exactly one block), 9 (first token of block 1), 15/16/17
# (the block-2 boundary), plus slot-reuse across all of them
BOUNDARY_PROMPTS = [
    list(range(60, 60 + n)) for n in (7, 8, 9, 15, 16, 17)
]


# ----------------------------------------------------------------------
# The arena write, bitwise against a plain numpy scatter
# ----------------------------------------------------------------------

BLK, N_TBL, N_BLOCKS = 8, 4, 20

# name -> (arena dtype, nkv, hd, t, per-row start column, per-row valid
# positions of t, per-row block table or None for a random one)
WRITE_CASES = {
    # a decode step: one position a row, rows at and round block edges,
    # the last row inactive
    "decode_step": ("bfloat16", 4, 16, 1, [BLK - 1, BLK, 2 * BLK + 3, 5], [1, 1, 1, 0], None),
    # padding rows of an insert carry all-out-of-range tables (n_blocks
    # and beyond), a freed row a stale table behind a zero mask
    "out_of_range_blocks_dropped": (
        "float32", 4, 16, 3, [0, 0, 4], [3, 3, 0],
        [[N_BLOCKS] * N_TBL, [N_BLOCKS + 7] * N_TBL, [3, 4, 5, 6]]),
    # a right-padded prefill: only the first `valid` positions land
    "masked_right_pad": ("bfloat16", 4, 16, 12, [0, 0, 0], [12, 5, 0], None),
    # a speculative-verify write and a resumed prefill: t > 1 from the
    # middle of a block into the next two
    "crosses_block_boundaries": ("bfloat16", 4, 16, 13, [BLK - 2, 2 * BLK - 1], [13, 9], None),
    "gqa_two_kv_heads": ("bfloat16", 2, 16, 5, [6, 0, 11], [5, 2, 4], None),
    "gqa_one_kv_head": ("float32", 1, 16, 5, [6, 0, 11], [5, 2, 4], None),
    "int8_both_scale_planes": ("int8", 4, 16, 1, [BLK - 1, BLK, 2 * BLK + 3, 5], [1, 1, 1, 0], None),
    "int8_prefill_pad_drop_boundary": (
        "int8", 2, 16, 11, [0, BLK - 3, 0], [11, 7, 4],
        [[1, 2, 3, 4], [5, 6, 7, 8], [N_BLOCKS] * N_TBL]),
}


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_arena_write_is_a_plain_scatter_bitwise(case):
    """`paged_kv_write` under jit against a loop that stores each valid
    position's [nkv, hd] keys at arena[phys, :, off] (and its nkv scales
    at plane[phys, 0, h*blk + off]) and nothing else: every byte of the
    arena, written or not, has to agree."""
    dtype, nkv, hd, t, start, valid, table = WRITE_CASES[case]
    rng = np.random.RandomState(sorted(WRITE_CASES).index(case))
    b = len(start)
    dtype = jnp.dtype(dtype)
    # a non-zero arena: an untouched row must keep its bytes
    shape = (N_BLOCKS, nkv, BLK, hd)
    if dtype == jnp.int8:
        layer = {"k": rng.randint(-127, 128, shape), "v": rng.randint(-127, 128, shape),
                 "k_scale": rng.rand(N_BLOCKS, 1, nkv * BLK) + 0.5,
                 "v_scale": rng.rand(N_BLOCKS, 1, nkv * BLK) + 0.5}
    else:
        layer = {"k": rng.randn(*shape), "v": rng.randn(*shape)}
    layer = {name: jnp.asarray(a, jnp.float32 if "scale" in name else dtype)
             for name, a in layer.items()}
    k = jnp.asarray(rng.randn(b, t, nkv, hd), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, t, nkv, hd), jnp.bfloat16)
    if table is None:  # every entry its own block, as the block pool hands them out
        table = 1 + rng.permutation(N_BLOCKS - 1)[:b * N_TBL].reshape(b, N_TBL)
    table = np.asarray(table, np.int32)
    mask = np.arange(t)[None, :] < np.asarray(valid)[:, None]

    got = jax.jit(paged_kv_write)(
        layer, k, v, jnp.asarray(table), jnp.asarray(start, jnp.int32), jnp.asarray(mask))

    # where the engine's tables put a row's columns (inference/engine.py:
    # logical column c of row r is offset c % block of block table[r, c // block])
    cols = np.asarray(start)[:, None] + np.arange(t)[None, :]
    phys = np.take_along_axis(table, np.clip(cols // BLK, 0, N_TBL - 1), axis=1)
    phys = np.where(mask & (cols < N_TBL * BLK), phys, N_BLOCKS)
    off = cols % BLK

    want = {name: np.array(a) for name, a in layer.items()}
    stored = {"k": k, "v": v}
    if dtype == jnp.int8:
        # quantized as the write quantizes, under jit (eager rounds a
        # scale one ulp apart); the scatter is what is under test
        quantize = jax.jit(quant.quantize_kv)
        (stored["k"], ks), (stored["v"], vs) = quantize(k), quantize(v)
        stored.update(k_scale=ks, v_scale=vs)
    stored = {name: np.asarray(a.astype(layer[name].dtype)) for name, a in stored.items()}
    written = 0
    for r in range(b):
        for i in range(t):
            if not 0 <= phys[r, i] < N_BLOCKS:
                continue
            written += 1
            for name in ("k", "v"):
                want[name][phys[r, i], :, off[r, i]] = stored[name][r, i]
            for name in set(want) - {"k", "v"}:
                for h in range(nkv):
                    want[name][phys[r, i], 0, h * BLK + off[r, i]] = stored[name][r, i, h]
    in_range = [n for n, row in zip(valid, table) if row[0] < N_BLOCKS]
    assert written == sum(in_range), "the case writes what it says"
    assert set(got) == set(want)
    for name in want:
        assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype
        np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]), err_msg=name)


# ----------------------------------------------------------------------
# Kernel units: interpret-mode kernel vs the XLA gather-path reference
# ----------------------------------------------------------------------

def _random_paged_case(rng, nh, nkv, b=3, hd=16, blk=8, n_tbl=4, n_blocks=10):
    q = jnp.asarray(rng.randn(b, nh, hd), jnp.float32)
    ka = jnp.asarray(rng.randn(n_blocks, nkv, blk, hd), jnp.float32).at[0].set(0.0)
    va = jnp.asarray(rng.randn(n_blocks, nkv, blk, hd), jnp.float32).at[0].set(0.0)
    table = jnp.asarray(rng.randint(0, n_blocks, (b, n_tbl)), jnp.int32)
    # lengths at / around block boundaries, plus one inactive row
    lens = jnp.asarray([blk - 1, 2 * blk + 1, 0], jnp.int32)[:b]
    cols = jnp.arange(n_tbl * blk)[None, :]
    mask = (cols < lens[:, None]).astype(jnp.int32)
    return q, ka, va, table, mask, lens


def _quantize_arena(arena):
    """int8 arena + its [n_blocks, 1, nkv*blk] head-major scale plane."""
    q, scale = quant.quantize_kv(arena)
    return q, scale.reshape(arena.shape[0], 1, -1)


@pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2), (4, 1)])
def test_kernel_matches_reference_gqa(nh, nkv):
    rng = np.random.RandomState(0)
    q, ka, va, table, mask, lens = _random_paged_case(rng, nh, nkv)
    out_k = paged_attention_decode(q, ka, va, table, mask, interpret=True)
    out_r = paged_attention_reference(q, ka, va, table, mask)
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(out_k)[active], np.asarray(out_r)[active],
        rtol=1e-5, atol=1e-5,
    )
    # fully-masked rows: the kernel returns exact zero (the dense path's
    # uniform-softmax garbage is never emitted either way)
    assert bool(jnp.all(out_k[~active] == 0.0))


@pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2), (4, 1)])
def test_kernel_int8_in_kernel_dequant(nh, nkv):
    rng = np.random.RandomState(1)
    q, ka, va, table, mask, lens = _random_paged_case(rng, nh, nkv)
    kq, ks = _quantize_arena(ka)
    vq, vs = _quantize_arena(va)
    out_k = paged_attention_decode(
        q, kq, vq, table, mask, k_scale=ks, v_scale=vs, interpret=True
    )
    out_r = paged_attention_reference(
        q, kq, vq, table, mask, k_scale=ks, v_scale=vs
    )
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(out_k)[active], np.asarray(out_r)[active],
        rtol=1e-5, atol=1e-5,
    )


def test_kernel_requires_scales_for_int8():
    rng = np.random.RandomState(2)
    q, ka, va, table, mask, _ = _random_paged_case(rng, 4, 2)
    kq, ks = _quantize_arena(ka)
    vq, vs = _quantize_arena(va)
    with pytest.raises(ValueError, match="scale"):
        paged_attention_decode(q, kq, vq, table, mask, interpret=True)


# ----------------------------------------------------------------------
# The length-aware walk: ragged rows, tiles of several entries, slack
# ----------------------------------------------------------------------

N_TBL_RAGGED = 11  # a tile is 8 entries: the second tile is partial


def _ragged_case(rng, group, dtype, blk, nkv=2, hd=16, n_blocks=64, n_tbl=N_TBL_RAGGED, more=()):
    """One batch with every kind of row: a single token, exactly one
    block, one past a block boundary, the whole table, all-masked, live
    entries that are no multiple of the tile, and a mask with a hole
    inside a live entry (then rows of `more` positions). Every live entry
    names a block of its own; table slack past a row's live entries names
    an id >= n_blocks."""
    lens = np.array([1, blk, blk + 1, n_tbl * blk, 0, 9 * blk + 3, 3 * blk - 2, *more])
    b, nh = len(lens), nkv * group
    qdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.randn(b, nh, hd), qdtype)
    arenas = [jnp.asarray(rng.randn(n_blocks, nkv, blk, hd), jnp.float32) for _ in range(2)]
    mask = (np.arange(n_tbl * blk)[None, :] < lens[:, None]).astype(np.int32)
    mask[5, [2, blk + 1, 4 * blk]] = 0  # holes, one at a block's first column
    mask[6, blk:2 * blk] = 0            # a whole entry masked below the last live one
    n_live = -(-lens // blk)
    ids = 1 + rng.permutation(n_blocks - 1)
    table = np.full((b, n_tbl), n_blocks, np.int32) + rng.randint(0, 5, (b, n_tbl))
    at = 0
    for r in range(b):
        table[r, :n_live[r]] = ids[at:at + n_live[r]]
        at += n_live[r]
    scales = {}
    if dtype == "int8":
        (ka, scales["k_scale"]), (va, scales["v_scale"]) = map(_quantize_arena, arenas)
    else:
        ka, va = (a.astype(jnp.dtype(dtype)) for a in arenas)
    return q, ka, va, jnp.asarray(table), jnp.asarray(mask), scales, n_live


RAGGED = [(g, d, blk) for g in (1, 2, 4) for d in ("bfloat16", "float32", "int8") for blk in (16, 32)]


@pytest.mark.parametrize("group,dtype,blk", RAGGED, ids=lambda v: str(v))
def test_kernel_matches_reference_on_ragged_rows(group, dtype, blk):
    rng = np.random.RandomState(RAGGED.index((group, dtype, blk)))
    q, ka, va, table, mask, scales, n_live = _ragged_case(rng, group, dtype, blk)
    assert _tile_entries(N_TBL_RAGGED, ka.shape[1], blk, ka.shape[3], ka.dtype) == 8
    out_k = np.asarray(paged_attention_decode(
        q, ka, va, table, mask, interpret=True, **scales).astype(jnp.float32))
    # the reference gathers through the table: give slack the zero block
    n_blocks = ka.shape[0]
    out_r = np.asarray(paged_attention_reference(
        q, ka, va, jnp.where(table < n_blocks, table, 0), mask, **scales).astype(jnp.float32))
    active = n_live > 0
    # bfloat16: both sides round the probabilities and the output to it
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(out_k[active], out_r[active], rtol=tol, atol=tol)
    assert (out_k[~active] == 0.0).all()


# Where a block is whole (sublane, lane) tiles the kernel copies a tile's
# blocks itself (`copies_blocks`): 4 K/V heads of 128 in blocks of 32, the
# transcript cell's group of 7 and the chat cell's of 5, a table of 19 entries
# over tiles of 16, and a row one position past a tile's edge.
COPIED = dict(blk=32, nkv=4, hd=128, n_tbl=19, more=(16 * 32 + 1,), n_blocks=128)


def _interpreter(name):
    """`interpret=True`, or the TPU interpreter with every scratch a NaN until
    something writes it: the place of a dead entry in the kernel's scratch is
    written by no copy."""
    if name == "interpret":
        return True
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams(uninitialized_memory="nan")


COPIED_CASES = [(7, "bfloat16", "interpret"), (7, "bfloat16", "nan"), (5, "bfloat16", "interpret"),
                (7, "int8", "nan"), (5, "int8", "interpret"), (5, "float32", "nan"), (7, "float32", "interpret")]


@pytest.mark.parametrize("group,dtype,interpreter", COPIED_CASES, ids=lambda v: str(v))
def test_the_copying_kernel_matches_reference_on_ragged_rows(group, dtype, interpreter):
    rng = np.random.RandomState(COPIED_CASES.index((group, dtype, interpreter)))
    q, ka, va, table, mask, scales, n_live = _ragged_case(rng, group, dtype, **COPIED)
    assert copies_blocks(4, 32, 128, ka.dtype) and _tile_entries(19, 4, 32, 128, ka.dtype) == 16
    out_k = np.asarray(paged_attention_decode(
        q, ka, va, table, mask, interpret=_interpreter(interpreter), **scales).astype(jnp.float32))
    out_r = np.asarray(paged_attention_reference(
        q, ka, va, jnp.where(table < ka.shape[0], table, 0), mask, **scales).astype(jnp.float32))
    active = n_live > 0
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(out_k[active], out_r[active], rtol=tol, atol=tol)
    assert (out_k[~active] == 0.0).all()


# (group, arena, block rows, window, interpreter): heads of 128 in blocks of whole tiles, where
# the kernel copies a tile's blocks and so can write the step's row back (`writes_in_kernel`)
FUSED_WRITES = [(1, "bfloat16", 32, None, "interpret"), (7, "bfloat16", 16, None, "nan"),
                (7, "float32", 16, None, "interpret"), (1, "float32", 32, 40, "nan"),
                (7, "bfloat16", 32, 100, "interpret")]


@pytest.mark.parametrize("group,dtype,blk,window,interpreter", FUSED_WRITES, ids=lambda v: str(v))
def test_the_kernel_writes_the_step_s_kv_as_paged_kv_write_does(group, dtype, blk, window, interpreter):
    """`paged_attention_decode(..., new_kv=, column=)` against `paged_kv_write`
    in front of the reference: both arenas bit for bit in EVERY block, touched
    or not, and the outputs within the file's tolerance. The rows: offset 0 of a
    fresh block; offset blk - 1; a freed slot between live rows (no token this
    step, all-masked, its stale table naming blocks that are now other rows');
    a row of two tiles; a padding row whose table names ids past the arena; two
    rows behind one shared read-only prefix block; a row of one position."""
    rng = np.random.RandomState(FUSED_WRITES.index((group, dtype, blk, window, interpreter)))
    nkv, hd, n_tbl, n_blocks = 2, 128, 19, 48
    lens = np.array([3 * blk + 1, 2 * blk, 2 * blk + 5, 16 * blk + 5, 7, blk + 3, 2 * blk - 4, 1])  # with the new column
    valid = np.array([1, 1, 0, 1, 1, 1, 1, 1])
    b, n_live = len(lens), -(-lens // blk)
    ids = list(1 + rng.permutation(n_blocks - 1))
    table = np.zeros((b, n_tbl), np.int32)
    for r in range(b):
        table[r, :n_live[r]] = [ids.pop() for _ in range(n_live[r])]
    table[2, :3] = [table[0, 3], table[1, 1], table[3, 16]]  # stale: the blocks the live rows write
    table[4] = n_blocks + rng.randint(0, 5, n_tbl)           # a padding row
    table[6, 0] = table[5, 0]                                # the shared prefix block, full and read-only
    mask = (np.arange(n_tbl * blk)[None, :] < lens[:, None]) & (valid[:, None] > 0)
    mask[4] = False
    adtype = jnp.dtype(dtype)
    q = jnp.asarray(rng.randn(b, nkv * group, hd), adtype)
    ka, va = (jnp.asarray(rng.randn(n_blocks, nkv, blk, hd), adtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(b, 1, nkv, hd), jnp.float32) for _ in range(2))
    table, mask, column = jnp.asarray(table), jnp.asarray(mask.astype(np.int32)), jnp.asarray(lens - 1, jnp.int32)
    assert writes_in_kernel(ka) and _tile_entries(n_tbl, nkv, blk, hd, adtype) == 16

    want = paged_kv_write({"k": ka, "v": va}, k, v, table, column, jnp.asarray(valid)[:, None])
    out_r = np.asarray(paged_attention_reference(
        q, want["k"], want["v"], jnp.where(table < n_blocks, table, 0), mask, window=window).astype(jnp.float32))
    out_k, k_arena, v_arena = paged_attention_decode(
        q, ka, va, table, mask, interpret=_interpreter(interpreter), window=window,
        new_kv=(k[:, 0], v[:, 0]), column=column)
    np.testing.assert_array_equal(_bits(k_arena), _bits(want["k"]))
    np.testing.assert_array_equal(_bits(v_arena), _bits(want["v"]))
    assert not (_bits(k_arena) == _bits(ka)).all()  # and something was written
    active = np.asarray(mask).any(-1)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    out_k = np.asarray(out_k.astype(jnp.float32))
    np.testing.assert_allclose(out_k[active], out_r[active], rtol=tol, atol=tol)
    assert (out_k[~active] == 0.0).all()
    with pytest.raises(ValueError, match="does not write"):  # heads of 16: the operand form keeps `paged_kv_write`
        paged_attention_decode(q[..., :16], ka[..., :16], va[..., :16], table, mask, interpret=True,
                               new_kv=(k[:, 0, :, :16], v[:, 0, :, :16]), column=column)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int8"])
@pytest.mark.parametrize("form", ["operands", "copied-interpret", "copied-nan"])
def test_table_slack_is_never_dereferenced(dtype, form):
    """Everything a live entry does not name is poison: the zero block,
    the blocks no row holds, the scale planes' rows of both, and table
    slack names ids past the arena (the interpreter would clamp such an
    id onto the last block, poisoned too). The output must not change by
    a bit: a fetched dead entry would put 0 x NaN into p.v, and so would
    the place of a dead entry in the copying kernel's scratch."""
    rng = np.random.RandomState(7)
    shape, _, interpreter = form.partition("-")
    q, ka, va, table, mask, scales, n_live = _ragged_case(
        rng, 2, dtype, 16) if shape == "operands" else _ragged_case(rng, 7, dtype, **COPIED)
    assert copies_blocks(*ka.shape[1:], ka.dtype) == (shape == "copied")
    interpret = _interpreter(interpreter or "interpret")
    clean = paged_attention_decode(q, ka, va, table, mask, interpret=interpret, **scales)
    live_ids = np.concatenate([np.asarray(table)[r, :n] for r, n in enumerate(n_live)])
    dead = np.setdiff1d(np.arange(ka.shape[0]), live_ids)
    assert 0 in dead and ka.shape[0] - 1 in dead
    if dtype == "int8":
        poison = lambda a: a.at[dead].set(127)  # noqa: E731
        scales = {k: v.at[dead].set(jnp.nan) for k, v in scales.items()}
    else:
        poison = lambda a: a.at[dead].set(jnp.nan)  # noqa: E731
    got = paged_attention_decode(q, poison(ka), poison(va), table, mask, interpret=interpret, **scales)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_array_equal(_bits(got), _bits(clean))


@pytest.mark.parametrize("entries", [1, 4, 8])
def test_live_schedule_walks_live_entries_only(entries):
    """The grid's walk from the table and the mask: `n_live` per row, one
    step a live tile (one for an all-masked row, to write its zeros), and
    block ids of live entries only, each operand keeping what it held
    where its entry is dead."""
    rng = np.random.RandomState(3)
    blk = 16
    _, ka, _, table, mask, _, n_live = _ragged_case(rng, 1, "float32", blk)
    blocks, row, tile, got_live, n_work, n_tiles = jax.jit(
        _live_schedule, static_argnums=(2, 3))(table, mask, blk, entries)
    np.testing.assert_array_equal(got_live, n_live)
    steps = np.maximum(1, -(-n_live // entries))
    assert int(n_work) == steps.sum() and n_tiles == -(-N_TBL_RAGGED // entries)
    row, tile = np.asarray(row)[:int(n_work)], np.asarray(tile)[:int(n_work)]
    np.testing.assert_array_equal(row, np.repeat(np.arange(len(n_live)), steps))
    np.testing.assert_array_equal(tile, np.concatenate([np.arange(n) for n in steps]))
    blocks = np.asarray(blocks).reshape(-1, entries)[:int(n_work)]
    table = np.asarray(table)
    assert (blocks < ka.shape[0]).all(), "an id past the arena would be fetched"
    held = np.full(entries, table[0, 0])  # before its first live step: the call's first live block
    for w, (r, t) in enumerate(zip(row, tile)):
        for e in range(entries):
            if t * entries + e < n_live[r]:
                held[e] = table[r, t * entries + e]
            assert blocks[w, e] == held[e], (w, e)


def test_tile_follows_the_shapes():
    """Tile entries adapt to block size, table length and VMEM: 512 tokens
    and at most sixteen entries a step where the kernel copies its blocks,
    256 tokens and at most eight operands a side where they are operands."""
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    assert _tile_entries(20, 16, 32, 128, bf16) == 16  # the benchmark's cell: 8 MiB of tiles exactly
    assert _tile_entries(44, 16, 32, 128, bf16) == 16  # the chat shape
    assert _tile_entries(20, 16, 16, 128, bf16) == 16  # sixteen entries at most
    assert _tile_entries(3, 2, 16, 64, bf16) == 3      # never past the table
    assert _tile_entries(20, 8, 32, 128, i8) == 16     # the 7B GQA shape, int8
    assert _tile_entries(20, 16, 32, 128, f32) == 8    # shrunk to 8 MiB of tiles
    assert _tile_entries(20, 32, 32, 128, f32) == 4    # shrunk to fit VMEM
    assert _tile_entries(480, 4, 32, 128, bf16) == 16  # the transcript cell: 2 MiB of tiles
    assert _tile_entries(20, 12, 32, 64, bf16) == 8    # heads of 64: operands, eight a side
    assert _tile_entries(11, 2, 8, 16, bf16) == 8


def test_the_kernelcopies_blocks_that_are_whole_tiles():
    """Mosaic slices one block out of an arena by whole (sublane, lane) tiles
    of its last two dims (the 128 lanes; 8 rows of float32, 16 of bfloat16, 32
    of int8), and an int8 arena's scale planes by rows of whole lanes: there
    the kernel copies, elsewhere each block is an operand."""
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    assert copies_blocks(16, 32, 128, bf16) and copies_blocks(4, 32, 128, bf16) and copies_blocks(8, 32, 128, i8)
    assert copies_blocks(4, 16, 128, bf16) and copies_blocks(4, 8, 256, f32)
    assert not copies_blocks(12, 32, 64, bf16)   # heads of 64
    assert not copies_blocks(4, 8, 128, bf16)    # half a bfloat16 tile's rows
    assert not copies_blocks(4, 16, 128, i8)     # half an int8 tile's rows
    assert not copies_blocks(2, 32, 128, i8)     # a scale plane's row of 64 lanes


# ----------------------------------------------------------------------
# Engine-level greedy bit-identity: kernel (interpret) vs gather path
# ----------------------------------------------------------------------

@pytest.mark.parametrize("preset", ["gpt2-tiny", "llama-tiny", "bigcode-tiny"])
def test_greedy_bitwise_f32_slot_reuse_and_boundaries(trainers, preset):
    tr = trainers[preset]
    gather = run_serial(make_engine(tr, "xla"), BOUNDARY_PROMPTS)
    kernel = run_serial(make_engine(tr, "interpret"), BOUNDARY_PROMPTS)
    assert kernel == gather


def test_greedy_bitwise_bf16_kv(trainers):
    """bf16 KV arena: the kernel accumulates in f32 (like the gather
    path's f32 score einsum), so greedy streams stay bitwise."""
    tr = trainers["llama-tiny"]
    gather = run_serial(
        make_engine(tr, "xla", kv_cache_dtype="bf16"), BOUNDARY_PROMPTS
    )
    kernel = run_serial(
        make_engine(tr, "interpret", kv_cache_dtype="bf16"), BOUNDARY_PROMPTS
    )
    assert kernel == gather


@pytest.fixture(scope="module")
def wide_trainer():
    """llama-tiny with heads of 128: the width at which a block is whole (sublane, lane) tiles."""
    return _build_trainer("llama-tiny", extra=dict(head_width=128))


# (arena, block rows, heads of 128, whether the kernel writes): heads of 128 in blocks of whole
# tiles are what `writes_in_kernel` takes; an int8 arena and the `-tiny` presets' heads of 16 keep
# `paged_kv_write` in front of the kernel
WRITE_FORMS = {
    "bf16-kernel": ("bf16", 16, True, True),
    "int8-xla": ("int8", 16, True, False),
    "tiny-xla": ("bf16", 16, False, False),
}


@pytest.mark.parametrize("form", sorted(WRITE_FORMS))
def test_greedy_bitwise_where_the_kernel_writes_the_step_s_kv(form, trainers, wide_trainer):
    """A model whose heads are 128 wide over blocks of whole tiles: the decode
    step's K and V reach the arena from the paged kernel itself (no
    `paged_kv_write` in front of it), and the greedy streams are the gather
    path's; `kv_kernel_writes` counts those dispatches, and stays 0 where the
    XLA write is kept (an int8 arena, the `-tiny` presets' heads of 16)."""
    from trlx_tpu.ops.paged_attention import writes_in_kernel

    kv_dtype, blk, wide, writes = WRITE_FORMS[form]
    tr = wide_trainer if wide else trainers["llama-tiny"]
    kw = dict(kv_cache_dtype=kv_dtype, kv_block_size=blk, max_new=6)
    prompts = [list(range(60, 60 + n)) for n in (blk - 4, blk, blk + 1)]  # decode steps round a block's edge
    gather = run_serial(make_engine(tr, "xla", **kw), prompts, max_new=6)
    eng = make_engine(tr, "interpret", **kw)
    assert writes_in_kernel(eng._pool["layers"][0]["k"]) == writes
    assert eng._kv_write_form() == ("kernel" if writes else "xla")
    kernel = run_serial(eng, prompts, max_new=6)
    if kv_dtype == "int8":  # as `test_greedy_int8_within_dequant_tolerance`
        assert sum(a == b for a, b in zip(gather, kernel)) >= len(prompts) - 1, (gather, kernel)
    else:
        assert kernel == gather
    stats = eng.kv_stats()
    assert stats["kv_kernel_dispatches"] > 0 and stats["kv_kernel_fallbacks"] == {}
    assert stats["kv_kernel_writes"] == (stats["kv_kernel_dispatches"] if writes else 0)


def test_greedy_int8_within_dequant_tolerance(trainers):
    """int8 KV quantizes identically on both read paths; the tiny random
    model's greedy streams may rarely diverge at near-tie logits, the
    same tolerance test_paged_kv grants the gather path."""
    tr = trainers["gpt2-tiny"]
    gather = run_serial(
        make_engine(tr, "xla", kv_cache_dtype="int8"), BOUNDARY_PROMPTS
    )
    kernel = run_serial(
        make_engine(tr, "interpret", kv_cache_dtype="int8"), BOUNDARY_PROMPTS
    )
    matches = sum(a == b for a, b in zip(gather, kernel))
    assert matches >= len(BOUNDARY_PROMPTS) - 1, (gather, kernel)


def test_decode_kernel_xla_pins_todays_path(trainers):
    """decode_kernel='xla' must be byte-for-byte today's engine: same
    greedy stream as the default engine with kernels disabled."""
    tr = trainers["gpt2-tiny"]
    eng = make_engine(tr, "xla")
    assert eng._attn_kernel is None
    assert "kv_kernel_dispatches" in eng.kv_stats()
    out = run_serial(eng, BOUNDARY_PROMPTS[:2])
    assert eng.kv_stats()["kv_kernel_dispatches"] == 0
    assert eng.kv_stats()["kv_kernel_fallbacks"] == {}
    # default ctor value is "auto" -> gather path on CPU: identical
    default = make_engine(tr, "auto")
    assert default._attn_kernel is None
    assert run_serial(default, BOUNDARY_PROMPTS[:2]) == out


# ----------------------------------------------------------------------
# Dispatch counters and fallback reasons
# ----------------------------------------------------------------------

def test_kernel_dispatch_counters(trainers):
    tr = trainers["llama-tiny"]
    eng = make_engine(tr, "interpret")
    assert eng._attn_kernel == "interpret"  # explicit request off-TPU
    run_serial(eng, BOUNDARY_PROMPTS[:2], max_new=4)
    stats = eng.kv_stats()
    assert stats["kv_kernel_dispatches"] > 0
    assert stats["kv_kernel_fallbacks"] == {}


def test_live_entry_share_counts_the_columns_held(trainers, monkeypatch):
    """`kv_live_entry_share` is the host's own count of table entries the
    next decode step to be dispatched attends to, over slots x table
    entries: the columns fetched, the one the step in flight writes, and its
    own. The `trlx:engine.dispatch` span carries nothing: while a tracing
    session is active the step being queued says who it is in the counter
    span `trlx:engine.queued` in front of it (its `seq`, `ahead=1` where a
    step was in flight, the rows with a request), and the table walk in
    `trlx:engine.kv_walk`; off a session neither is formatted."""
    from trlx_tpu.observability import tracing

    eng = make_engine(trainers["llama-tiny"], "interpret", max_new=8)
    bs, n_tbl = eng.kv_block_size, eng._n_tbl
    assert eng.kv_stats()["kv_live_entry_share"] == 0.0
    eng.insert_requests([(np.arange(60, 67, dtype=np.int32), 8)], [1])  # 7 columns
    share = lambda cols: -(-cols // bs) / (2 * n_tbl)  # noqa: E731
    assert eng.kv_stats()["kv_live_entry_share"] == share(7 + 1)  # the step writes one
    seen, counted = [], []
    real_span = tracing.span
    monkeypatch.setattr(tracing, "span", lambda name, **a: (seen.append((name, a)), real_span(name, **a))[1])
    monkeypatch.setattr(tracing, "counters", lambda name, **values: counted.append((name, values)))
    eng.step()  # its own step writes column 8, the one it leaves in flight column 9
    assert seen.count(("engine.dispatch", {})) == 2 and counted == []
    assert eng.kv_stats()["kv_live_entry_share"] == share(8 + 1 + 1) == 2 / (2 * n_tbl)
    monkeypatch.setattr(tracing, "active", lambda: True)
    eng.step()
    assert seen.count(("engine.dispatch", {})) == 3
    assert [name for name, _ in counted] == ["engine.kv_walk", "engine.queued", "engine.fetched"]
    # which of the kernel's two fetch forms the step's K/V calls take: heads of 16 are no whole lane tile
    layers = eng.model_cfg.n_layers
    assert (counted[0][1]["calls_copied"], counted[0][1]["calls_operands"]) == (0, layers)
    assert counted[0][1]["kv_write"] == "xla" == eng._kv_write_form()  # and so `paged_kv_write` writes the step's K/V
    monkeypatch.setattr("trlx_tpu.ops.paged_attention.copies_blocks", lambda *shape: True)
    assert (eng._kv_walk()["calls_copied"], eng._kv_walk()["calls_operands"]) == (layers, 0)
    assert eng._kv_write_form() == "kernel"
    assert counted[1][1] == {"seq": 3, "ahead": 1, "rows": 1} and counted[2][1] == {"seq": 2}
    stats = eng.kv_stats()
    assert (stats["decode_steps_total"], stats["decode_steps_ahead_total"],
            stats["decode_outputs_masked_total"]) == (2, 2, 0)
    eng.reclaim_slots([1])
    assert eng.kv_stats()["kv_live_entry_share"] == 0.0


def test_alibi_falls_back_with_reason():
    tr = _build_trainer("bloom-tiny")  # alibi=True
    eng = make_engine(tr, "interpret")
    assert eng._kernel_unsupported == "alibi"
    kernel = run_serial(eng, BOUNDARY_PROMPTS[:1], max_new=4)
    stats = eng.kv_stats()
    assert stats["kv_kernel_dispatches"] == 0
    assert stats["kv_kernel_fallbacks"].get("alibi", 0) > 0
    # the fallback serves the gather path's exact tokens
    gather = run_serial(make_engine(tr, "xla"), BOUNDARY_PROMPTS[:1], max_new=4)
    assert kernel == gather


def test_invalid_decode_kernel_rejected(trainers):
    with pytest.raises(ValueError, match="decode_kernel"):
        make_engine(trainers["gpt2-tiny"], "mosaic")


# ----------------------------------------------------------------------
# Shared kernel-mode helper (env override + CPU safety)
# ----------------------------------------------------------------------

def test_kernel_mode_env_override(monkeypatch):
    # tier-1 runs on CPU devices: the rule resolves to the XLA paths, the
    # interpreter only by name, and a demand for the compiled kernel raises
    monkeypatch.delenv("TRLX_TPU_KERNELS", raising=False)
    assert kernel_mode() == "off"
    monkeypatch.setenv("TRLX_TPU_KERNELS", "off")
    assert kernel_mode() == "off"
    monkeypatch.setenv("TRLX_TPU_KERNELS", "interpret")
    assert kernel_mode() == "interpret"
    monkeypatch.setenv("TRLX_TPU_KERNELS", "pallas")
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        kernel_mode()
    monkeypatch.setenv("TRLX_TPU_KERNELS", "mosaic")
    with pytest.raises(ValueError, match="TRLX_TPU_KERNELS"):
        kernel_mode()


def test_compiled_kernel_request_raises_off_tpu(trainers, monkeypatch):
    """decode_kernel='pallas' never degrades to the interpreter: on CPU
    devices it raises, as does the env demand under 'auto'."""
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        make_engine(trainers["gpt2-tiny"], "pallas")
    monkeypatch.setenv("TRLX_TPU_KERNELS", "pallas")
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        make_engine(trainers["gpt2-tiny"], "auto")


def test_env_kill_switch_pins_gather_path(trainers, monkeypatch):
    monkeypatch.setenv("TRLX_TPU_KERNELS", "off")
    eng = make_engine(trainers["gpt2-tiny"], "interpret")
    assert eng._attn_kernel is None
