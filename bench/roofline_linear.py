"""Operations and bytes of a delta-rule recurrence (Kimi delta attention),
from shapes: the yardstick of `kda_decode_roofline`, kept beside roofline.py
(whose `least_seconds` prices what this returns). A call is priced by the
recurrence's own work, whatever implements it."""


def kda_decode(live_rows: float, heads: int, dk: int, dv: int, state_bytes: int = 4, vector_bytes: int = 4):
    """One decode step of one layer over `live_rows` rows: a head's matrix
    of dk x dv is decayed by row (dk dv operations), read against the key
    (2 dk dv), written with a rank-one update (2 dk dv) and read against the
    query (2 dk dv): 7 dk dv. Bytes: the matrix read once and written once;
    q, k, the decay (dk each), v and the output (dv each) and the write
    strength once. A row with no request costs nothing here."""
    flops = 7 * dk * dv * heads * live_rows
    nbytes = (2 * dk * dv * state_bytes + (3 * dk + 2 * dv + 1) * vector_bytes) * heads * live_rows
    return flops, nbytes
