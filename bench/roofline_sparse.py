"""Operations and bytes of the two kernels a sparse / banded latent stack adds
to a decode step, from shapes: the yardstick of `paged_index_roofline` and
`paged_latent_window_roofline`, kept beside roofline.py (whose
`least_seconds` prices what this returns)."""

from benchlib.files import load_module


def paged_index_scores(positions: float, rows: int, heads: int, dim: int, cache_bytes: int = 2,
                       dtype_bytes: int = 2):
    """One decode step's index scores for one sparse layer over `positions`
    cached positions in all (summed over the rows): each of the index's
    `heads` queries, `dim` wide, multiplies every position's one key (2 * dim
    operations a head; the relu and the weighted sum over heads are not
    counted). Bytes: each position's key read once, its float32 score written
    once; a row's queries and weights read."""
    flops = 2 * heads * dim * positions
    nbytes = positions * (dim * cache_bytes + 4) + rows * heads * (dim * dtype_bytes + 4)
    return flops, nbytes


def paged_decode_latent_window(positions: float, rows: int, window: int, heads: int, width: int, values: int,
                               cache_bytes: int = 2, dtype_bytes: int = 2):
    """One decode step's absorbed attention for one banded latent layer:
    `roofline_latent.paged_decode_latent` over the positions inside the band,
    `window` a row at most (`positions` resident in all, over `rows` rows of
    like length: the backlog's)."""
    in_band = rows * min(float(window), positions / max(rows, 1))
    return load_module("roofline_latent.py").paged_decode_latent(in_band, rows, heads, width, values,
                                                                 cache_bytes, dtype_bytes)
