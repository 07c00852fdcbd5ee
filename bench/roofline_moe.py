"""Operations and bytes of the expert layer's grouped matrix product, from
its shapes: the yardstick of `moe_gmm_roofline`, kept beside roofline.py
(whose `least_seconds` prices what this returns)."""


def grouped_matmul(rows: float, d_in: int, d_out: int, experts_held: int, dtype_bytes: int = 2):
    """`rows` real dispatch rows, each against one of `experts_held`
    matrices [d_in, d_out]: 2 * d_in * d_out operations a row. Bytes: every
    held matrix read once (a call that leaves an expert without rows reads
    less; a decode step's few rows still meet nearly all of them), each real
    row read once and its result written once."""
    flops = 2.0 * rows * d_in * d_out
    nbytes = (experts_held * d_in * d_out + rows * (d_in + d_out)) * dtype_bytes
    return flops, nbytes


def expected_rows(static_rows: int, experts: int, experts_held: int, real_token_share: float) -> float:
    """The dispatch rows a call is expected to multiply. Its buffer holds
    `static_rows`, one for every assignment of every position (tokens x
    experts a token: what admits no dropped token); the real ones are the
    assignments of real tokens (`real_token_share` of the positions: padding
    is dispatched nowhere) that meet an expert held here, experts_held /
    experts of them under a router that favours none."""
    return static_rows * real_token_share * experts_held / experts
