"""Operations and bytes of attention over a cached latent (MLA), from shapes:
the yardstick of `paged_latent_roofline` and `flash_latent_roofline`, kept
beside roofline.py (whose `least_seconds` prices what this returns)."""


def paged_decode_latent(positions: float, rows: int, heads: int, width: int, values: int,
                        cache_bytes: int = 2, dtype_bytes: int = 2):
    """One decode step's absorbed attention for one latent layer over
    `positions` cached positions in all (summed over the rows). Every query
    head, `width` wide, multiplies every position's latent (2 * width
    operations) and weighs its leading `values` columns (2 * values). Bytes:
    each position's latent read ONCE for all heads; a row's queries read and
    its outputs written."""
    flops = 2 * heads * (width + values) * positions
    nbytes = positions * width * cache_bytes + rows * heads * (width + values) * dtype_bytes
    return flops, nbytes


def flash_fwd_latent(rows_heads: int, t: int, qk_dim: int, v_dim: int, dtype_bytes: int = 2):
    """A causal self-attention forward over decompressed keys and values,
    [rows x heads, t, qk_dim] queries and keys against [rows x heads, t,
    v_dim] values: q k^T is 2 * qk_dim and p v 2 * v_dim operations a
    (query, key) pair of the causal half. Bytes: q and k, v read once, the
    output (v_dim wide) written once. Values padded to qk_dim would be
    priced the same: the real work."""
    pairs = t * (t + 1) // 2
    flops = 2 * (qk_dim + v_dim) * rows_heads * pairs
    nbytes = rows_heads * t * (2 * qk_dim + 2 * v_dim) * dtype_bytes
    return flops, nbytes
