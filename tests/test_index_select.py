"""The learned index of a sparse latent layer (`ops/sparse_attention.py`, and
`paged_index_scores` / `paged_latent_rows` of `ops/paged_attention.py`): its
scores against the dense products, the choice of the k largest against
`jax.lax.top_k` (ties toward the later position), the kernels through the
Pallas interpreter against their plain forms, and a decode step's read of
the chosen latents by token address."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from trlx_tpu.ops import paged_attention as paged  # noqa: E402
from trlx_tpu.ops import sparse_attention as sparse  # noqa: E402


def _index_inputs(rng, b, n, S, G=4, D=16):
    q = jnp.asarray(rng.normal(size=(b, n, G, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, n, G)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, S, D)), jnp.float32)
    return q, w, k


def test_index_scores_are_the_weighted_relu_of_every_heads_product():
    q, w, k = _index_inputs(np.random.default_rng(0), 2, 5, 11)
    want = np.einsum("bngs,bng->bns", np.maximum(np.einsum("bngd,bsd->bngs", q, k), 0.0), w)
    np.testing.assert_allclose(np.asarray(sparse.index_scores(q, w, k)), want, atol=1e-5)
    # a negative weight keeps its sign and a negative product adds nothing
    one = sparse.index_scores(-jnp.ones((1, 1, 1, 2)), jnp.ones((1, 1, 1)), jnp.ones((1, 3, 2)))
    assert np.asarray(one).tolist() == [[[0.0, 0.0, 0.0]]]


@pytest.mark.parametrize("k", [1, 6, 17, 40])
def test_topk_mask_is_the_set_top_k_names_with_ties_toward_the_later_column(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(3, 7, 40)).astype(np.float32)
    scores[0, :, 5:12] = 0.25  # a run of equal scores across the k-th place
    scores[1, 2] = -np.inf  # a row with nothing to choose
    scores[2, :, 20:] = -np.inf  # rows with fewer than k columns above -inf
    scores[2, 3, 3] = scores[2, 3, 9] = -0.0
    scores[2, 3, 4] = 0.0
    mask = np.asarray(jax.jit(lambda s: sparse.topk_mask(s, k))(jnp.asarray(scores)))
    cols, chosen = jax.jit(lambda s: sparse.topk_columns(s, k))(jnp.asarray(scores))
    for idx in np.ndindex(3, 7):
        row = scores[idx]
        # the plain rule: sort by (score, column), take the last k, keep what is above -inf
        order = sorted(range(40), key=lambda j: (row[j] + 0.0, j))[-k:]
        want = {j for j in order if row[j] > -np.inf}
        assert {j for j in np.flatnonzero(mask[idx]) if row[j] > -np.inf} == want, (idx, k)
        assert set(np.asarray(cols[idx])[np.asarray(chosen[idx])].tolist()) == want, (idx, k)
    if k >= 40:
        assert mask.all()


def test_topk_mask_breaks_a_tie_in_one_row_without_touching_the_others():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.0], [5.0, 4.0, 3.0, 2.0, 1.0]], jnp.float32)
    assert np.asarray(sparse.topk_mask(scores, 2)).tolist() == [[False, False, True, True, False],
                                                                [True, True, False, False, False]]
    cols, chosen = sparse.topk_columns(scores, 2)
    assert np.asarray(cols).tolist() == [[3, 2], [0, 1]] and bool(np.asarray(chosen).all())


def test_chosen_in_block_is_causal_valid_and_skips_the_index_while_everything_fits():
    rng = np.random.default_rng(3)
    q, w, k = _index_inputs(rng, 2, 4, 12)
    key_mask = jnp.asarray([[1] * 12, [1] * 9 + [0] * 3])
    allow = np.asarray(sparse.chosen_in_block(q, w, k, key_mask, first=8, topk=3))
    scores = np.asarray(sparse.index_scores(q, w, k))
    for b, i in np.ndindex(2, 4):
        ok = [j for j in range(12) if j <= 8 + i and key_mask[b, j]]
        want = set(sorted(ok, key=lambda j: (scores[b, i, j], j))[-3:])
        assert set(np.flatnonzero(allow[b, i]).tolist()) == want
    # a block that ends within topk columns: causal and valid, no score computed
    everything = np.asarray(sparse.chosen_in_block(q, w, k * np.nan, key_mask, first=8, topk=12))
    assert everything[0].tolist() == [[j <= 8 + i for j in range(12)] for i in range(4)]
    assert not everything[1][:, 9:].any()


def _head_inputs(rng, n, S, heads, dc=64, dn=128, dr=64, dv=128):
    """A sparse layer's prefill operands in bfloat16: each head's query in its
    two parts, the prompt's latents `c` and shared rotary key, and W_kvb."""
    bf16 = lambda x: jnp.asarray(x, jnp.bfloat16)
    return (bf16(rng.normal(size=(1, n, heads, dn)) * 0.3), bf16(rng.normal(size=(1, n, heads, dr)) * 0.3),
            bf16(rng.normal(size=(1, S, dc))), bf16(rng.normal(size=(1, S, dr))),
            bf16(rng.normal(size=(dc, heads, dn + dv)) * dc ** -0.5))


def _absorbed(q_nope, q_rope, c, k_rope, w_kvb, allow, dn=128):
    """The same attention as a decode step runs it: the query through W_uk
    against the latents, the latents' sum through W_uv (`masked_latent_reference`)."""
    q_abs = jnp.concatenate([jnp.einsum("bnhd,chd->bnhc", q_nope, w_kvb[..., :dn]), q_rope], axis=-1)
    o_lat = sparse.masked_latent_reference(q_abs, jnp.concatenate([c, k_rope], axis=-1), allow,
                                           values=c.shape[-1], scale=0.25)
    return jnp.einsum("bnhc,chv->bnhv", o_lat, w_kvb[..., dn:])


def test_the_prefill_kernels_through_the_interpreter_match_their_plain_forms(monkeypatch):
    """`sparse_index_scores` and `sparse_latent_fwd` at shapes their tiles
    divide (two tiles of queries, three of keys), bfloat16 operands as the
    cell's, a block of queries that stands behind 128 columns. The attention
    is per head (a key of 128 + 64 of which the 64 are the rotary part all
    heads share, values of 128; two groups of two heads, the two heads of a
    group one grid step) and is held to the ABSORBED form over the latents;
    a query that is allowed nothing gives zeros."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "kernel_mode", lambda: "interpret")
    monkeypatch.setattr(attention, "KERNEL_PATHS", {})  # the process's record: a file run earlier by this worker is in it
    monkeypatch.setattr(sparse, "INDEX_BLOCK_Q", 32)
    monkeypatch.setattr(sparse, "INDEX_BLOCK_K", 128)
    monkeypatch.setattr(sparse, "ATTEND_BLOCK_Q", 32)
    monkeypatch.setattr(sparse, "ATTEND_BLOCK_K", 128)
    rng = np.random.default_rng(5)
    n, S, first = 64, 384, 128
    q, w, k = (x.astype(jnp.bfloat16) for x in _index_inputs(rng, 1, n, S, G=4, D=128))
    got = sparse.index_scores(q, w, k)
    assert attention.KERNEL_PATHS["sparse_index_scores"].get("interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(sparse.index_scores_reference(q, w, k)),
                               rtol=2e-2, atol=2e-2)

    heads, values = 4, 128
    operands = _head_inputs(rng, n, S, heads)
    key_mask = jnp.ones((1, S), jnp.int32).at[0, 300:].set(0)
    allow = sparse.chosen_in_block(q, w, k, key_mask, first=first, topk=40)
    assert int(np.asarray(allow).sum(-1).max()) == 40
    allow = allow.at[0, 37].set(False)
    got = sparse.masked_latent_attention_by_groups(*operands, allow, group=2, scale=0.25, first=first)
    assert attention.KERNEL_PATHS["sparse_latent_fwd"].get("interpret")
    want = _absorbed(*operands, allow)
    assert got.shape == (1, n, heads, values)
    some = np.arange(n) != 37
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, some], np.asarray(want, np.float32)[:, some], atol=2e-2)
    assert not np.asarray(got, np.float32)[:, 37].any()
    # shapes the tiles do not divide take the plain form
    q_nope, q_rope, c, k_rope, w_kvb = operands
    assert sparse.masked_latent_attention_by_groups(
        q_nope[:, :5], q_rope[:, :5], c[:, :133], k_rope[:, :133], w_kvb, allow[:, :5, :133], group=2, scale=0.25,
        first=first).shape == (1, 5, heads, values)
    assert attention.KERNEL_PATHS["sparse_latent_fwd"].get("xla") == [(1, 5, 2, 128)]


@pytest.mark.parametrize("block", [0, 1, 2])
def test_a_block_of_a_traced_loop_is_the_block_at_a_host_integer(block, monkeypatch):
    """A prefill runs its blocks as one body under `jax.lax.map`, where a
    block stands is a traced scalar and every block is handed the WHOLE
    prompt: both kernels, through the interpreter, give each block what the
    plain forms give it (the attention per head, a head a grid step, against
    the absorbed form), the index's scores 0 on the tiles of columns behind
    the block's last query."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "kernel_mode", lambda: "interpret")
    for name, size in (("INDEX_BLOCK_Q", 32), ("INDEX_BLOCK_K", 128), ("ATTEND_BLOCK_Q", 32), ("ATTEND_BLOCK_K", 128),
                       ("ATTEND_HEADS", 1)):
        monkeypatch.setattr(sparse, name, size)
    rng = np.random.default_rng(11)
    n, S, heads = 128, 384, 4
    q, w, k = (x.astype(jnp.bfloat16) for x in _index_inputs(rng, 1, S, S, G=4, D=128))
    q_nope, q_rope, c, k_rope, w_kvb = _head_inputs(rng, S, S, heads)
    key_mask = jnp.ones((1, S), jnp.int32).at[0, 340:].set(0)

    def one(j):
        rows = lambda x: jax.lax.dynamic_slice_in_dim(x, j * n, n, axis=1)
        scores = sparse.index_scores(rows(q), rows(w), k, first=j * n)
        allow = sparse.chosen_in_block(rows(q), rows(w), k, key_mask, first=j * n, topk=40)
        return scores, allow, sparse.masked_latent_attention_by_groups(
            rows(q_nope), rows(q_rope), c, k_rope, w_kvb, allow, group=2, scale=0.25, first=j * n)

    scores, allow, out = (np.asarray(x[block], np.float32) for x in jax.jit(
        lambda: jax.lax.map(one, jnp.arange(S // n, dtype=jnp.int32)))())
    at = slice(block * n, block * n + n)
    seen = (block + 1) * n  # the block's last query stands at column seen - 1, the last column of a key tile
    monkeypatch.setattr(attention, "kernel_mode", lambda: "xla")
    np.testing.assert_allclose(scores[:, :, :seen], np.asarray(sparse.index_scores(q[:, at], w[:, at], k[:, :seen])),
                               rtol=2e-2, atol=2e-2)
    assert not scores[:, :, seen:].any()
    want = sparse.chosen_in_block(q[:, at], w[:, at], k, key_mask, first=block * n, topk=40)
    assert int(np.asarray(want).sum(-1).max()) == min(40, seen)
    # the kernel's scores and the plain form's differ in the last bits: a choice may differ at a near-tie
    assert (allow.astype(bool) != np.asarray(want)).mean() < 2e-3
    want = _absorbed(q_nope[:, at], q_rope[:, at], c, k_rope, w_kvb, jnp.asarray(allow.astype(bool)))
    np.testing.assert_allclose(out, np.asarray(want, np.float32), atol=2e-2)


def _paged_planes(rng, lens, n_tbl=6, blk=4, width=16):
    """Rows of unequal length in an arena of shuffled blocks: tables that end
    in dead entries (the zero block, an id past the arena)."""
    b = len(lens)
    n_blocks = 1 + b * n_tbl
    ids = rng.permutation(np.arange(1, n_blocks))
    table = np.zeros((b, n_tbl), np.int32)
    for r, n in enumerate(lens):
        live = -(-n // blk)
        table[r, :live] = ids[r * n_tbl: r * n_tbl + live]
        table[r, live:] = [0, n_blocks + 3][r % 2]
    key_mask = np.asarray([[1] * n + [0] * (n_tbl * blk - n) for n in lens], np.int32)
    return table, key_mask, n_blocks, blk, width


def test_paged_index_scores_walk_the_table_and_match_the_dense_products():
    rng = np.random.default_rng(7)
    lens = [21, 4, 0, 13]
    table, key_mask, n_blocks, blk, D = _paged_planes(rng, lens)
    keys = jnp.asarray(rng.normal(size=(4, 24, D)), jnp.float32)
    arena = paged.init_paged_plane(n_blocks, blk, D, jnp.float32)
    arena = paged.paged_plane_write({"index_k": arena}, "index_k", keys, jnp.asarray(table),
                                    jnp.zeros((4,), jnp.int32), jnp.asarray(key_mask))["index_k"]
    dense = paged.paged_plane_gather(arena, jnp.clip(jnp.asarray(table), 0, n_blocks - 1))
    for r, n in enumerate(lens):  # what was written is what a gather reads, and nothing past a row's end
        np.testing.assert_array_equal(np.asarray(dense[r, :n]), np.asarray(keys[r, :n]))
    assert float(jnp.abs(arena[0]).max()) == 0.0  # the zero block stays zero
    q = jnp.asarray(rng.normal(size=(4, 3, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
    key_mask = key_mask.copy()
    key_mask[0, 6] = 0  # a hole inside a live entry
    got = paged.paged_index_scores(q, w, arena, jnp.asarray(table), jnp.asarray(key_mask), interpret=True)
    want = np.einsum("bgs,bg->bs", np.maximum(np.einsum("bgd,bsd->bgs", q, keys), 0.0), w)
    live = key_mask.astype(bool)
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=1e-5)
    assert np.isneginf(np.asarray(got)[~live]).all()
    shadow = paged.paged_index_reference(q, w, arena, jnp.clip(jnp.asarray(table), 0, n_blocks - 1),
                                         jnp.asarray(key_mask))
    np.testing.assert_allclose(np.asarray(shadow)[live], want[live], atol=1e-5)


def test_paged_latent_rows_read_the_chosen_tokens_out_of_rows_of_two():
    rng = np.random.default_rng(9)
    lens = [21, 7]
    table, key_mask, n_blocks, blk, _ = _paged_planes(rng, lens)
    values, width = 6, 10
    latent = jnp.asarray(rng.normal(size=(2, 24, width)), jnp.float32)
    layer = paged.init_paged_latent_layer(n_blocks, blk, width, jnp.float32)
    arena = paged.paged_latent_write(layer, latent, jnp.asarray(table), jnp.zeros((2,), jnp.int32),
                                     jnp.asarray(key_mask), values=values)["latent"]
    columns = jnp.asarray([[20, 0, 7, 13, 2], [6, 5, 0, 1, 3]], jnp.int32)
    rows = paged.paged_latent_rows(arena, jnp.asarray(table), columns, values=values)
    for r in range(2):
        np.testing.assert_array_equal(np.asarray(rows[r]), np.asarray(latent[r])[np.asarray(columns[r])])
    # and the attention over them is the dense one over those positions
    q = jnp.asarray(rng.normal(size=(2, 3, width)), jnp.float32)
    chosen = jnp.asarray([[1, 1, 1, 1, 0], [1, 1, 1, 0, 0]], bool)
    got = sparse.attend_chosen(q, rows, chosen, values=values, scale=0.5)
    for r in range(2):
        keep = np.asarray(columns[r])[np.asarray(chosen[r])]
        s = np.einsum("hc,kc->hk", q[r], np.asarray(latent[r])[keep]) * 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ np.asarray(latent[r])[keep][:, :values]
        np.testing.assert_allclose(np.asarray(got[r]), want, atol=1e-5)
    none = sparse.attend_chosen(q, rows, jnp.zeros((2, 5), bool), values=values, scale=0.5)
    assert float(jnp.abs(none).max()) == 0.0
