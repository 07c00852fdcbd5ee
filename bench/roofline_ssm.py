"""Operations and bytes of a Mamba-2 (SSD) recurrence, from shapes: the
yardstick of `ssd_decode_roofline`, kept beside roofline.py (whose
`least_seconds` prices what this returns). A call is priced by the
recurrence's own work, whatever implements it."""


def ssd_decode(live_rows: float, heads: int, d_state: int, d_head: int, groups: int,
               state_bytes: int = 4, vector_bytes: int = 4):
    """One decode step of one layer over `live_rows` rows: a head's matrix of
    d_state x d_head is decayed by one factor (N P operations), written with a
    rank-one update (2 N P) and read against C (2 N P): 5 N P. Bytes: the
    matrix read once and written once; x and the output (d_head a head each),
    B and C (d_state a group each) and dt (one a head) once. A row with no
    request costs nothing here."""
    flops = 5 * d_state * d_head * heads * live_rows
    nbytes = (2 * heads * d_state * d_head * state_bytes
              + (2 * heads * d_head + 2 * groups * d_state + heads) * vector_bytes) * live_rows
    return flops, nbytes
