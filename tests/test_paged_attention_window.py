"""The paged decode kernel with a window (trlx_tpu/ops/paged_attention.py,
`paged_attention_decode(window=...)`), in interpret mode against
`paged_attention_reference`: a sliding layer keeps a row's last `window`
columns and the kernel walks only the table entries that hold one of them.
Windows smaller than, equal to and no multiple of the block; rows with fewer
positions than the window, a single one, none; groups of 3, 4 and 6 query
heads a K/V head; bfloat16 and int8 arenas. Then the same where the kernel
copies a tile's blocks itself (blocks of whole tiles: 4 K/V heads of 128,
groups of 7 and 5), under both interpreters."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from trlx_tpu.ops import quant
from trlx_tpu.ops.paged_attention import (
    copies_blocks,
    _live_schedule,
    _live_walk,
    _tile_entries,
    band_mask,
    paged_attention_decode,
    paged_attention_reference,
)

BLK, N_TBL, NKV, HD, N_BLOCKS = 8, 11, 2, 16, 64  # a tile is 8 entries: the second tile is partial
LENS = np.array([1, 5, BLK, BLK + 1, 40, N_TBL * BLK, 0, 63])


def case(rng, group, dtype, lens=LENS, blk=BLK, n_tbl=N_TBL, nkv=NKV, hd=HD):
    """Rows of every length (above), every live entry a block of its own,
    table slack past a row's live entries an id >= n_blocks."""
    b, nh = len(lens), nkv * group
    q = jnp.asarray(rng.randn(b, nh, hd), jnp.float32 if dtype == "float32" else jnp.bfloat16)
    arenas = [jnp.asarray(rng.randn(N_BLOCKS, nkv, blk, hd), jnp.float32) for _ in range(2)]
    mask = (np.arange(n_tbl * blk)[None, :] < lens[:, None]).astype(np.int32)
    n_live = -(-lens // blk)
    ids = 1 + rng.permutation(N_BLOCKS - 1)
    table = np.full((b, n_tbl), N_BLOCKS, np.int32) + rng.randint(0, 5, (b, n_tbl))
    at = 0
    for r in range(b):
        table[r, :n_live[r]] = ids[at:at + n_live[r]]
        at += n_live[r]
    scales = {}
    if dtype == "int8":
        def quantize(arena):  # int8 arena + its [n_blocks, 1, nkv*blk] head-major scale plane
            values, scale = quant.quantize_kv(arena)
            return values, scale.reshape(N_BLOCKS, 1, -1)

        (ka, scales["k_scale"]), (va, scales["v_scale"]) = map(quantize, arenas)
    else:
        ka, va = (a.astype(jnp.dtype(dtype)) for a in arenas)
    return q, ka, va, jnp.asarray(table), jnp.asarray(mask), scales


CASES = [(w, g, d) for w in (5, BLK, 19) for g in (3, 4, 6) for d in ("bfloat16", "int8")]
CASES += [(3 * BLK, 4, "float32")]


@pytest.mark.parametrize("window,group,dtype", CASES, ids=lambda v: str(v))
def test_windowed_kernel_matches_reference(window, group, dtype):
    rng = np.random.RandomState(CASES.index((window, group, dtype)))
    q, ka, va, table, mask, scales = case(rng, group, dtype)
    assert _tile_entries(N_TBL, NKV, BLK, HD, ka.dtype) == 8
    got = np.asarray(paged_attention_decode(
        q, ka, va, table, mask, interpret=True, window=window, **scales).astype(jnp.float32))
    zero_slack = jnp.where(table < N_BLOCKS, table, 0)  # the reference gathers through the table
    want = np.asarray(paged_attention_reference(
        q, ka, va, zero_slack, mask, window=window, **scales).astype(jnp.float32))
    # the band by hand, on the float32 case: softmax over the last `window` columns
    if dtype == "float32":
        r = 4  # 40 positions, window 24: columns 16..39
        k = np.asarray(ka)[np.asarray(table)[r, :5]].transpose(0, 2, 1, 3).reshape(40, NKV, HD)[16:]
        v = np.asarray(va)[np.asarray(table)[r, :5]].transpose(0, 2, 1, 3).reshape(40, NKV, HD)[16:]
        s = np.einsum("hd,shd->hs", np.asarray(q)[r], np.repeat(k, group, 1)) / np.sqrt(HD)
        p = np.exp(s - s.max(-1, keepdims=True))
        by_hand = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), np.repeat(v, group, 1))
        np.testing.assert_allclose(got[r], by_hand, atol=1e-5)
    active = LENS > 0
    tol = 1e-5 if dtype == "float32" else 2e-2  # bfloat16: both sides round probabilities and output to it
    np.testing.assert_allclose(got[active], want[active], rtol=tol, atol=tol)
    assert (got[~active] == 0.0).all()


def test_a_window_no_row_reaches_is_no_window():
    q, ka, va, table, mask, scales = case(np.random.RandomState(1), 6, "bfloat16")
    wide = paged_attention_decode(q, ka, va, table, mask, interpret=True, window=N_TBL * BLK)
    full = paged_attention_decode(q, ka, va, table, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(wide.astype(jnp.float32)), np.asarray(full.astype(jnp.float32)))


@pytest.mark.parametrize("window", [5, BLK, 19, 40])
def test_windowed_schedule_walks_the_band_and_nothing_else(window):
    """`first` and `n_live` bound each row's walk: one step a tile that
    holds an entry of the band (one for an all-masked row), and every named
    block an entry inside the band, so that poison everywhere else (the
    entries in front of the band among it) does not move the output by a bit."""
    rng = np.random.RandomState(3)
    q, ka, va, table, mask, _ = case(rng, 4, "float32")
    entries = 8
    banded = band_mask(mask, window)
    blocks, row, tile, n_live, n_work, n_tiles, first = jax.jit(
        _live_schedule, static_argnums=(2, 3, 4))(table, banded, BLK, entries, True)
    last = -(-LENS // BLK)
    front = np.maximum(LENS - window, 0) // BLK  # entries wholly in front of the band
    np.testing.assert_array_equal(n_live, last)
    np.testing.assert_array_equal(first, front // entries * entries)
    steps = np.maximum(1, -(-last // entries) - front // entries)
    assert int(n_work) == steps.sum()
    if window < 40:  # the longest row's first tile lies in front of its band
        assert steps.sum() < np.maximum(1, -(-last // entries)).sum()
    row, tile = np.asarray(row)[:int(n_work)], np.asarray(tile)[:int(n_work)]
    np.testing.assert_array_equal(row, np.repeat(np.arange(len(LENS)), steps))
    np.testing.assert_array_equal(tile, np.concatenate([f // entries + np.arange(n) for f, n in zip(front, steps)]))
    in_band = np.concatenate([np.asarray(table)[r, front[r]:last[r]] for r in range(len(LENS))])
    named = np.asarray(blocks).reshape(-1, entries)[:int(n_work)]
    assert np.isin(named, in_band).all(), "an entry outside the band would be fetched"
    clean = paged_attention_decode(q, ka, va, table, mask, interpret=True, window=window)
    dead = np.setdiff1d(np.arange(N_BLOCKS), in_band)
    poison = lambda a: a.at[dead].set(jnp.nan)  # noqa: E731
    got = paged_attention_decode(q, poison(ka), poison(va), table, mask, interpret=True, window=window)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32), np.asarray(clean).view(np.uint32))


# The copying kernel (`copies_blocks`): 4 K/V heads of 128 in blocks of 32, a
# table of 19 entries over tiles of 16. Rows of 1 token, one block, one past a
# block's and one past a tile's edge, the whole table, none; bands inside one
# block, that start inside the first tile and inside the second.
COPIED = dict(blk=32, n_tbl=19, nkv=4, hd=128)
COPIED_LENS = np.array([1, 32, 33, 16 * 32 + 1, 19 * 32, 0, 17 * 32 + 5])
COPIED_CASES = [(w, g, d, i) for (w, g, d), i in zip(
    [(5, 7, "bfloat16"), (100, 7, "bfloat16"), (100, 5, "int8"), (32, 5, "bfloat16"), (530, 7, "int8"), (100, 5, "float32")],
    ["nan", "interpret", "nan", "interpret", "interpret", "nan"])]


@pytest.mark.parametrize("window,group,dtype,interpreter", COPIED_CASES, ids=lambda v: str(v))
def test_the_copying_kernel_walks_the_band_and_nothing_else(window, group, dtype, interpreter):
    """Against the reference, and then with poison in every block outside
    the rows' bands (those in front of a band among them) and, under the TPU
    interpreter, NaN in every place of the kernel's scratch no copy wrote:
    not a bit of the output moves."""
    from jax.experimental.pallas import tpu as pltpu

    interpret = True if interpreter == "interpret" else pltpu.InterpretParams(uninitialized_memory="nan")
    rng = np.random.RandomState(COPIED_CASES.index((window, group, dtype, interpreter)))
    q, ka, va, table, mask, scales = case(rng, group, dtype, COPIED_LENS, **COPIED)
    assert copies_blocks(4, 32, 128, ka.dtype) and _tile_entries(19, 4, 32, 128, ka.dtype) == 16
    clean = paged_attention_decode(q, ka, va, table, mask, interpret=interpret, window=window, **scales)
    want = paged_attention_reference(
        q, ka, va, jnp.where(table < N_BLOCKS, table, 0), mask, window=window, **scales)
    active = COPIED_LENS > 0
    tol = 1e-5 if dtype == "float32" else 2e-2
    got = np.asarray(clean.astype(jnp.float32))
    np.testing.assert_allclose(got[active], np.asarray(want.astype(jnp.float32))[active], rtol=tol, atol=tol)
    assert (got[~active] == 0.0).all()

    last = -(-COPIED_LENS // 32)
    front = np.maximum(COPIED_LENS - window, 0) // 32  # entries wholly in front of the band
    walk = jax.jit(_live_walk, static_argnums=(2, 3, 4))(table, band_mask(mask, window), 32, 16, True)
    np.testing.assert_array_equal(walk.n_live, last)
    np.testing.assert_array_equal(walk.n_first, front)
    steps = np.maximum(1, -(-last // 16) - front // 16)
    n_work = int(walk.n_work)
    assert n_work == steps.sum() and walk.n_tiles == 2
    np.testing.assert_array_equal(np.asarray(walk.row)[:n_work], np.repeat(np.arange(len(last)), steps))
    np.testing.assert_array_equal(
        np.asarray(walk.tile)[:n_work], np.concatenate([f // 16 + np.arange(n) for f, n in zip(front, steps)]))

    in_band = np.concatenate([np.asarray(table)[r, front[r]:last[r]] for r in range(len(last))])
    dead = np.setdiff1d(np.arange(N_BLOCKS), in_band)
    if dtype == "int8":
        poison = lambda a: a.at[dead].set(127)  # noqa: E731
        scales = {k: v.at[dead].set(jnp.nan) for k, v in scales.items()}
    else:
        poison = lambda a: a.at[dead].set(jnp.nan)  # noqa: E731
    poisoned = paged_attention_decode(q, poison(ka), poison(va), table, mask, interpret=interpret, window=window, **scales)
    assert bool(jnp.isfinite(poisoned.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(poisoned.astype(jnp.float32)), got)
