"""`paged_attention_latent(..., window=)`: the absorbed paged decode over a
latent arena with a band, through the Pallas interpreter, against
`paged_latent_reference` with the same band and against the plain softmax by
hand; the windowed schedule (`_live_schedule(windowed=True)`) leaves out the
table entries in front of the band as it does for K/V by head."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from trlx_tpu.ops import paged_attention as paged  # noqa: E402

B, NH, DC, DR, BLK, N_BLOCKS, N_TBL = 4, 3, 32, 8, 4, 80, 18
WIDTH = DC + DR
LENS = [61, 6, 0, 35]


def _arena(rng, dtype):
    table = np.full((B, N_TBL), N_BLOCKS + 5, np.int32)  # dead: never dereferenced
    ids = iter(rng.permutation(np.arange(1, N_BLOCKS)))
    for r, n in enumerate(LENS):
        for j in range(-(-n // BLK)):
            table[r, j] = next(ids)
    table[1, 2:] = 0  # the zero block behind a short row
    mask = np.asarray([[1] * n + [0] * (N_TBL * BLK - n) for n in LENS], np.int32)
    tokens = jnp.asarray(rng.normal(size=(N_BLOCKS, BLK, WIDTH)), dtype)
    return table, mask, tokens, paged._pack_latent(tokens, DC)


# 6: ends inside a block (a block is 4); 11: spans three blocks and, from a row of 61, two tiles' worth of
# entries are left out in front of it; 513 over rows this short: bands nothing
@pytest.mark.parametrize("window", [1, 6, 11, 513])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_windowed_latent_kernel_against_its_plain_reference(window, dtype, tol, monkeypatch):
    monkeypatch.setattr(paged, "_LATENT_TILE_TOKENS", 16)  # tiles of 4 entries: a row of 61 has four
    rng = np.random.default_rng(window)
    table, mask, tokens, arena = _arena(rng, dtype)
    mask[3, 30] = 0  # a hole inside the band
    q = jnp.asarray(rng.normal(size=(B, NH, WIDTH)), jnp.float32)
    kw = dict(values=DC, scale=(16 + DR) ** -0.5, out_dtype=jnp.float32, window=window)
    safe = jnp.asarray(np.where(table >= N_BLOCKS, 0, table))  # the gather dereferences every entry
    want = paged.paged_latent_reference(q, arena, safe, jnp.asarray(mask), **kw)
    got = paged.paged_attention_latent(q, arena, jnp.asarray(table), jnp.asarray(mask), interpret=True, **kw)
    assert got.shape == (B, NH, DC)
    live = [0, 1, 3]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=tol)
    assert float(jnp.abs(got[2]).max()) == 0.0  # an all-masked row: zeros
    # by hand, row 0: softmax over its last `window` latents, the value their first DC columns
    rows = np.asarray(tokens, np.float32)[table[0, :16]].reshape(-1, WIDTH)[max(0, 61 - window):61]
    s = np.asarray(q[0]) @ rows.T * kw["scale"]
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got[0]), (p / p.sum(-1, keepdims=True)) @ rows[:, :DC], atol=tol)
    # without a window the call is the one it was
    plain = paged.paged_attention_latent(q, arena, jnp.asarray(table), jnp.asarray(mask), interpret=True,
                                         **{**kw, "window": None})
    if window >= max(LENS):
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=tol)


def test_the_windowed_walk_leaves_out_the_entries_in_front_of_the_band():
    """What the schedule hands the kernel for a band of 6 over rows of 61, 6,
    0 and 35 columns in tiles of 4 entries: each row's walk starts at the
    tile that holds its first column inside the band and no block in front
    of the band is named."""
    table, mask, _, _ = _arena(np.random.default_rng(0), jnp.float32)
    banded = paged.band_mask(jnp.asarray(mask), 6)
    assert [int(n) for n in banded.sum(-1)] == [6, 6, 0, 6]
    blocks, row, tile, n_live, n_work, n_tiles, first = paged._live_schedule(
        jnp.asarray(table), banded, BLK, 4, windowed=True)
    assert int(n_work) == 1 + 1 + 1 + 2 and n_tiles == 5  # row 3's band [29, 35) crosses a tile's edge
    assert [int(x) for x in n_live] == [16, 2, 0, 9] and [int(x) for x in first] == [12, 0, 0, 4]
    walked = {int(b) for b in np.asarray(blocks).reshape(-1, 4)[: int(n_work)].reshape(-1)}
    in_band = {int(table[r, c // BLK]) for r, n in enumerate(LENS) for c in range(max(0, n - 6), n)}
    assert walked == in_band
