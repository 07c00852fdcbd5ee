"""Attention over positions a learned index chose (DeepSeek-V3.2's sparse
attention, as `models/transformer.LatentAttention` runs a
"sparse_latent_attention" layer): the index's scores, the choice of the
`topk` largest, and latent attention over the chosen.

With G index heads of D, qI [.., n, G, D] and w [.., n, G] the queries'
(`LatentIndex.queries`) and kI [.., S, D] one key a position:

    I_t,j = sum_g w_t,g relu(qI_t,g . kI_j)                      float32

The attended set of query t is the `topk` attendable positions with the
largest I_t,j, every attendable position while there are no more than
`topk`, ties at the last place toward the LATER position. It is exact: a
threshold found by bisection over the scores' bits (`topk_mask`, 32 counting
passes over the scores and no sort) or `jax.lax.top_k` (`topk_columns`, where
the columns themselves are wanted) name the same set.

Three places run it. The dense paths (no cache, the dense families' cache,
the paged gather path) call `index_scores` and `topk_mask` and add the mask
to their bias. A prefill into an empty cache goes a block of queries at a
time (`chosen_in_block`, then `masked_latent_attention_by_groups`: each head's
queries over that head's keys and values, decompressed from the prompt's
latents a group of heads at a time, under the mask of the chosen, through a
fused forward, `masked_latent_attention`; where the block stands, `first`, may be a traced scalar, so
that the blocks of a prompt are one body of `jax.lax.map`, and both kernels
pass over the tiles of columns behind the block's last query). A
paged decode step scores a row's cached index keys through its block table
(`ops/paged_attention.paged_index_scores`), takes `topk_columns`, gathers
those latents (`paged_latent_rows`) and attends over them ABSORBED
(`attend_chosen`: the cache holds latents, and 2,048 of them a row).

On one TPU chip `index_scores` and `masked_latent_attention` are Pallas
kernels, named `sparse_index_scores` and `sparse_latent_fwd` in the device
trace; elsewhere (and for shapes their tiles do not divide) plain
`jax.numpy`. `attend_chosen` and the choice are XLA everywhere, under the
`jax.named_scope`s `sparse_latent_attend` and `sparse_index_choose`.
"""

import functools

import jax
import jax.numpy as jnp

from trlx_tpu.ops import attention
from trlx_tpu.ops.attention import NEG_INF, note_kernel_path

# Tiles of the two kernels: queries x keys of `sparse_index_scores`, and of
# `sparse_latent_fwd` (`ATTEND_HEADS` heads' queries against a tile of their
# keys and values: the heads of a grid step share the mask's tile). A key tile
# of 1,024 and not 512: every tile rescales a head's [queries, 128] accumulator
# and rewrites its running maximum and sum, a cost beside the tile's softmax
# that 512 keys do not carry (9.7 ps a (query, key, head) pair at 512 x 512,
# 6.0 at 1,024 x 1,024; 2,048 keys gain 0.2 more and lose it twice over on the
# tiles that straddle a block's diagonal: PERF.md section 6, PR 52).
INDEX_BLOCK_Q, INDEX_BLOCK_K = 256, 512
ATTEND_BLOCK_Q, ATTEND_BLOCK_K, ATTEND_HEADS = 1024, 1024, 4
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def index_scores_reference(q, w, k):
    """I [b, n, S] float32 from q [b, n, G, D], w [b, n, G], k [b, S, D], in
    plain products: [b, n, G, S] exists whole, so for small shapes."""
    s = jnp.einsum("bngd,bsd->bngs", q, k.astype(q.dtype), preferred_element_type=jnp.float32)
    return jnp.einsum("bngs,bng->bns", jax.nn.relu(s), w.astype(jnp.float32))


def _index_scores_kernel(first_ref, q_ref, w_ref, k_ref, o_ref, *, block_q, block_k):
    """q_ref [1, G, bq, D], w_ref [1, bq, G] f32, k_ref [1, bk, D] -> o_ref [1, bq, bk] f32;
    first_ref [1]: the column the call's first query stands at."""
    import jax.experimental.pallas as pl

    qb, kb = pl.program_id(1), pl.program_id(2)
    seen = kb * block_k <= first_ref[0] + qb * block_q + block_q - 1

    @pl.when(seen)
    def _scores():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for g in range(q_ref.shape[1]):  # unrolled: one [bq, D] x [D, bk] product a head
            s = jax.lax.dot_general(q_ref[0, g], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, g:g + 1]
        o_ref[0] = acc

    @pl.when(jnp.logical_not(seen))  # a tile wholly behind the block's last query: no query may choose from it
    def _nothing():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)


def _index_scores_pallas(q, w, k, first, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, G, D = q.shape
    S = k.shape[1]
    bq, bk = min(INDEX_BLOCK_Q, n), min(INDEX_BLOCK_K, S)

    def key_tile(i, j, kk, first_ref):  # a tile past the block's last query is not fetched again
        return (i, jnp.minimum(kk, (first_ref[0] + j * bq + bq - 1) // bk), 0)

    return pl.pallas_call(
        functools.partial(_index_scores_kernel, block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n // bq, S // bk),
            in_specs=[
                pl.BlockSpec((1, G, bq, D), lambda i, j, kk, first_ref: (i, 0, j, 0)),
                pl.BlockSpec((1, bq, G), lambda i, j, kk, first_ref: (i, j, 0)),
                pl.BlockSpec((1, bk, D), key_tile),
            ],
            out_specs=pl.BlockSpec((1, bq, bk), lambda i, j, kk, first_ref: (i, j, kk)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sparse_index_scores",
    )(first, q.transpose(0, 2, 1, 3), w.astype(jnp.float32), k.astype(q.dtype))


def _tiles(n: int, S: int, bq: int, bk: int) -> bool:
    """Whether [n, S] divides into the kernel's tiles, each aligned to the TPU's."""
    return n % min(bq, n) == 0 and S % min(bk, S) == 0 and n % 32 == 0 and S % 128 == 0


def index_scores(q, w, k, first=None):
    """I [b, n, S] float32 (module docstring): q [b, n, G, D] and w [b, n, G]
    of n query positions against one key a position, k [b, S, D]. With `first`
    (a prompt's block: query i stands at column `first + i`, a host integer or
    a traced scalar) the kernel passes over the tiles of columns behind the
    block's queries and leaves 0 there: a caller that gives it masks what no
    query may attend to. No gradient flows through it."""
    q, w, k = (jax.lax.stop_gradient(x) for x in (q, w, k))
    mode = attention.kernel_mode()
    if mode in ("pallas", "interpret") and _tiles(q.shape[1], k.shape[1], INDEX_BLOCK_Q, INDEX_BLOCK_K):
        note_kernel_path("sparse_index_scores", mode, q.shape)
        # without `first` every tile is in front of the last query's column
        first = k.shape[1] if first is None else first
        return _index_scores_pallas(q, w, k, jnp.asarray(first, jnp.int32).reshape(1),
                                    interpret=(mode == "interpret"))
    note_kernel_path("sparse_index_scores", "xla", q.shape)
    return index_scores_reference(q, w, k)


def _ordered(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as 0.0)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores, k: int):
    """[..., S] bool: the k largest of each row of `scores` (float32, -inf on
    what may not be chosen), ties at the k-th place toward the later column;
    every column of a row of no more than k. Exact and without a sort: the
    k-th largest value is found bit by bit, each bit one count over the row,
    and so is the column from which the ties at that value are taken. Every
    row costs the same passes whatever it holds (a branch for the rows with
    ties made a prefill's time follow its data: PERF.md section 6, PR 51)."""
    with jax.named_scope("sparse_index_choose"):
        scores = jax.lax.stop_gradient(scores)
        S = scores.shape[-1]
        if S <= k:
            return jnp.ones(scores.shape, bool)
        u = _ordered(scores)
        zero = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)

        def largest(bits: int, enough):
            """The largest uint32 t below 2**bits with `enough(t)`, a bit at a time from the top
            (`enough` holds at 0 and once false stays false as t grows)."""
            def bit(i, t):
                candidate = t | (jnp.uint32(1 << (bits - 1)) >> i.astype(jnp.uint32))
                return jnp.where(enough(candidate), candidate, t)

            return jax.lax.fori_loop(0, bits, bit, zero)

        kth = largest(32, lambda t: jnp.sum(u >= t, axis=-1, keepdims=True) >= k)
        above, at = u > kth, u == kth
        need = k - jnp.sum(above, axis=-1, keepdims=True)
        # of the columns that hold the k-th value, the `need` latest: those from column `first` on
        column = jnp.arange(S, dtype=jnp.uint32)
        first = largest(S.bit_length(), lambda c: jnp.sum(at & (column >= c), axis=-1, keepdims=True) >= need)
        return above | (at & (column >= first))


def topk_columns(scores, k: int):
    """(columns [..., k'] int32, chosen [..., k'] bool), k' = min(k, S): the
    same set as `topk_mask` as column numbers, `chosen` false where a row has
    fewer than k' columns above -inf."""
    with jax.named_scope("sparse_index_choose"):
        scores = jax.lax.stop_gradient(scores)
        S = scores.shape[-1]
        # the later column first among equals: `top_k` keeps the lower index
        values, at = jax.lax.top_k(jnp.flip(scores, -1), min(k, S))
        return (S - 1 - at).astype(jnp.int32), values > -jnp.inf


def chosen_in_block(q, w, k, key_mask, *, first, topk: int):
    """[b, n, S] bool: what each query of a prompt's block may attend to.
    The block's queries stand at columns `first` .. `first + n` (a host
    integer or a traced scalar: a block of `jax.lax.map`), `k` [b, S, D] and
    `key_mask` [b, S] are the prompt's index keys and their validity, the
    block's end or further: causal, valid, and among the `topk` largest index
    scores. A prompt of no more than `topk` columns computes no score."""
    n, S = q.shape[1], k.shape[1]
    cols = jnp.arange(S)[None, :]
    attendable = key_mask[:, None, :].astype(bool) & (cols <= first + jnp.arange(n)[:, None])[None]
    if S <= topk:
        return attendable
    scores = index_scores(q, w, k, first=first)
    return topk_mask(jnp.where(attendable, scores, -jnp.inf), topk) & attendable


def masked_latent_reference(q, latent, allow, *, values: int, scale: float):
    """The ABSORBED form of `masked_latent_attention`, what a decode step runs
    over the cache (`attend_chosen`) and the tests' yardstick for the per-head
    kernel: softmax(q . latent * scale) under `allow`, times the latents'
    leading `values` columns: q [b, n, nh, width] (each head's query through
    W_uk, then its rotary part), latent [b, S, width], allow [b, n, S] ->
    [b, n, nh, values] (W_uv still to come). [b, nh, n, S] scores exist whole."""
    scores = jnp.einsum("bnhc,bsc->bhns", q, latent, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(allow[:, None], scores, -1e9), axis=-1).astype(q.dtype)
    return jnp.einsum("bhns,bsc->bnhc", probs, latent[..., :values])


def masked_heads_reference(q_nope, q_rope, k_nope, k_rope, v, allow, *, scale: float):
    """`masked_latent_attention` in plain products (its shapes): [b, h, n, S]
    scores exist whole."""
    scores = jnp.einsum("bnhd,bshd->bhns", q_nope, k_nope, preferred_element_type=jnp.float32)
    scores = (scores + jnp.einsum("bnhr,bsr->bhns", q_rope, k_rope, preferred_element_type=jnp.float32)) * scale
    probs = jax.nn.softmax(jnp.where(allow[:, None], scores, -1e9), axis=-1).astype(q_nope.dtype)
    return jnp.einsum("bhns,bshd->bnhd", probs, v)


def _masked_heads_kernel(first_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, allow_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, scale, block_q, block_k, heads):
    """`heads` heads' `block_q` queries against `block_k` of their keys and
    values: the online softmax of `ops/attention._flash_fwd_kernel` with the
    operands in their own type, the mask an operand the heads share (widened
    once a tile), and a key in two parts, a head's own and the rotary part
    that is one vector for all heads.

    qn_ref [1, bq, heads * dn], qr_ref [1, bq, heads * 128]: each head's query
        against its keys and against the rotary key (padded to a lane tile)
    kn_ref [1, bk, heads * dn], v_ref [1, bk, heads * dv]: each head's keys and values
    kr_ref [1, bk, 128]: the rotary key, padded likewise
    allow_ref [1, bq, bk] int8
    first_ref [1]: the column the call's first query stands at
    """
    import jax.experimental.pallas as pl

    qb, kb = pl.program_id(2), pl.program_id(3)
    dn, lanes, dv = (ref.shape[-1] // heads for ref in (qn_ref, qr_ref, v_ref))

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a tile wholly behind the block's last query holds nothing it may see
    @pl.when(kb * block_k <= first_ref[0] + qb * block_q + block_q - 1)
    def _compute():
        nt = (((1,), (1,)), ((), ()))
        allowed = allow_ref[0].astype(jnp.int32) > 0
        kr = kr_ref[0]
        for h in range(heads):  # unrolled: the heads of a step share the mask's tile and the rotary key's
            s = jax.lax.dot_general(qn_ref[0, :, h * dn:(h + 1) * dn], kn_ref[0, :, h * dn:(h + 1) * dn], nt,
                                    preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qr_ref[0, :, h * lanes:(h + 1) * lanes], kr, nt,
                                        preferred_element_type=jnp.float32)
            s = jnp.where(allowed, s * scale, NEG_INF)
            m_prev, l_prev = m_scr[h, :, 0:1], l_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # NEG_INF is finite: what the mask took out is exp(-1e30 - shift) = 0 with no second select,
            # and a row that has seen nothing yet keeps l = acc = 0 under corr = exp(0)
            p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, :, h * dv:(h + 1) * dv]
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finalize():
        for h in range(heads):
            l = l_scr[h, :, 0:1]
            o_ref[0, :, h * dv:(h + 1) * dv] = (acc_scr[h] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _masked_heads_pallas(q_nope, q_rope, k_nope, k_rope, v, allow, scale: float, first, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, nh, dn = q_nope.shape
    S, dv = v.shape[1], v.shape[-1]
    bq, bk = min(ATTEND_BLOCK_Q, n), min(ATTEND_BLOCK_K, S)
    heads = max(h for h in range(1, ATTEND_HEADS + 1) if nh % h == 0)
    lanes = -(-q_rope.shape[-1] // 128) * 128
    pad = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, lanes - x.shape[-1]),))
    flat = lambda x: x.reshape(*x.shape[:2], -1)  # [.., heads, d] -> heads side by side: a head is a tile of lanes

    def last_seen(j, first_ref):  # the last tile of columns a query of tile j may see
        return (first_ref[0] + j * bq + bq - 1) // bk

    def query_tile(i, g, j, kk, first_ref):
        return (i, j, g)

    def key_tile(i, g, j, kk, first_ref):  # a tile past the block's last query is not fetched again
        return (i, jnp.minimum(kk, last_seen(j, first_ref)), g)

    out = pl.pallas_call(
        functools.partial(_masked_heads_kernel, scale=scale, block_q=bq, block_k=bk, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nh // heads, n // bq, S // bk),
            in_specs=[
                pl.BlockSpec((1, bq, heads * dn), query_tile),
                pl.BlockSpec((1, bq, heads * lanes), query_tile),
                pl.BlockSpec((1, bk, heads * dn), key_tile),
                pl.BlockSpec((1, bk, lanes), lambda i, g, j, kk, first_ref: key_tile(i, 0, j, kk, first_ref)),
                pl.BlockSpec((1, bk, heads * dv), key_tile),
                pl.BlockSpec((1, bq, bk),
                             lambda i, g, j, kk, first_ref: (i, j, jnp.minimum(kk, last_seen(j, first_ref)))),
            ],
            out_specs=pl.BlockSpec((1, bq, heads * dv), query_tile),
            scratch_shapes=[
                pltpu.VMEM((heads, bq, 128), jnp.float32),  # m (broadcast over lanes)
                pltpu.VMEM((heads, bq, 128), jnp.float32),  # l
                pltpu.VMEM((heads, bq, dv), jnp.float32),   # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, nh * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sparse_latent_fwd",
    )(jnp.asarray(first, jnp.int32).reshape(1), flat(q_nope), flat(pad(q_rope)), flat(k_nope), pad(k_rope), flat(v),
      allow.astype(jnp.int8))
    return out.reshape(b, n, nh, dv)


def masked_latent_attention(q_nope, q_rope, k_nope, k_rope, v, allow, *, scale: float, first=0):
    """Latent attention of a block of queries under a mask, PER HEAD over
    keys and values decompressed from the latents (2 x (dn + dr + dv)
    operations a (query, key, head) pair where the absorbed form pays
    2 x (2 dc + dr)): q_nope [b, n, h, dn] and q_rope [b, n, h, dr] (the
    block stands at columns `first` onward, a host integer or a traced
    scalar), k_nope [b, S, h, dn] and v [b, S, h, dv] of the same heads,
    k_rope [b, S, dr] the rotary key all heads share, of the prompt's columns,
    the block's end or further (the kernel passes over the tiles behind the
    block), allow [b, n, S] -> [b, n, h, dv]. `allow` holds the causal
    structure; a query it allows nothing gives zeros."""
    k_nope, k_rope, v = (x.astype(q_nope.dtype) for x in (k_nope, k_rope, v))
    mode = attention.kernel_mode()
    if (mode in ("pallas", "interpret") and q_nope.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and _tiles(q_nope.shape[1], v.shape[1], ATTEND_BLOCK_Q, ATTEND_BLOCK_K)):
        note_kernel_path("sparse_latent_fwd", mode, q_nope.shape)
        return _masked_heads_pallas(q_nope, q_rope, k_nope, k_rope, v, allow, scale, first,
                                    interpret=(mode == "interpret"))
    note_kernel_path("sparse_latent_fwd", "xla", q_nope.shape)
    return masked_heads_reference(q_nope, q_rope, k_nope, k_rope, v, allow, scale=scale)


def masked_latent_attention_by_groups(q_nope, q_rope, c, k_rope, w_kvb, allow, *, group: int, scale: float, first=0):
    """`masked_latent_attention` of every head, `group` heads at a time over
    keys and values decompressed from the prompt's latents for that group: c
    [b, S, dc], w_kvb [dc, h, dn + dv] (W_uk beside W_uv, by head), the rest as
    there -> [b, n, h, dv]. The groups are one traced body run in turn
    (`jax.lax.map`), and the keys and values of all heads never exist at once."""
    nh, dn = q_nope.shape[2:]
    # [groups, .., group, d]: a group's heads of the weights, and of the queries
    by_group = lambda x, axis: jnp.moveaxis(
        x.reshape(*x.shape[:axis], nh // group, group, *x.shape[axis + 1:]), axis, 0)

    def heads(of):
        w, q_nope, q_rope = of
        return masked_latent_attention(
            q_nope, q_rope, jnp.einsum("bsc,chn->bshn", c, w[..., :dn]), k_rope,
            jnp.einsum("bsc,chv->bshv", c, w[..., dn:]), allow, scale=scale, first=first)

    out = jax.lax.map(heads, (by_group(w_kvb, 1), by_group(q_nope, 2), by_group(q_rope, 2)))
    return jnp.moveaxis(out, 0, 2).reshape(*out.shape[1:3], nh, -1)


def attend_chosen(q, rows, chosen, *, values: int, scale: float, out_dtype=None):
    """A decode step's absorbed attention over the latents it chose: q
    [b, nh, width], rows [b, k, width] (`paged_latent_rows`), chosen [b, k]
    -> [b, nh, values]; a row that chose nothing (a slot without a token)
    gives zeros. Softmax in float32, probabilities rounded to the output's
    type before the value product, as the paged kernels round them."""
    out_dtype = out_dtype or q.dtype
    with jax.named_scope("sparse_latent_attend"):
        rows = rows.astype(q.dtype)
        scores = jnp.einsum("bhc,bkc->bhk", q, rows, preferred_element_type=jnp.float32) * scale
        scores = jnp.where(chosen[:, None, :], scores, NEG_INF)
        top = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.where(chosen[:, None, :], jnp.exp(scores - jnp.where(top <= NEG_INF / 2, 0.0, top)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / jnp.where(l > 0, l, 1.0)).astype(out_dtype)
        return jnp.einsum("bhk,bkc->bhc", p, rows[..., :values].astype(out_dtype),
                          preferred_element_type=jnp.float32).astype(out_dtype)
