"""Operations and bytes a kernel's call needs, from its shapes. The
benchmark's yardstick: kept beside peaks.json, not read from the program.

A roofline share is (the least time the chip could take: the larger of
operations / peak FLOP/s and bytes / peak bytes/s) / (the kernel's device
time in the trace). Which of the two bounds it is reported with the number.
"""


def flash_fwd(batch: int, t: int, heads: int, head_dim: int, dtype_bytes: int = 2):
    """Causal self-attention forward over [batch, t, heads, head_dim]: q k^T
    and p v are 2 * t * t * head_dim multiply-adds each per head, of which
    the causal half is needed. Bytes: q, k and v read once, the output
    written once (the log-sum-exp row is 1/head_dim of that: left out)."""
    flops = 2 * (2 * t * t * head_dim) * heads * batch / 2
    nbytes = 4 * batch * t * heads * head_dim * dtype_bytes
    return flops, nbytes


def paged_decode(resident_tokens: int, rows: int, heads: int, kv_heads: int, head_dim: int,
                 kv_bytes: int = 2, dtype_bytes: int = 2):
    """One decode step's attention for one layer: every row's query against
    the tokens resident for it (`resident_tokens` summed over rows). q k^T
    and p v are 2 * head_dim operations per query head per resident token
    each. Bytes: every resident token's k and v read once (kv_heads of
    them), q read and the output written per row."""
    flops = 2 * (2 * head_dim) * heads * resident_tokens
    nbytes = 2 * resident_tokens * kv_heads * head_dim * kv_bytes \
        + 2 * rows * heads * head_dim * dtype_bytes
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict, dtype: str = "bfloat16"):
    """(seconds, which bound) on a device with `peaks` (a row of peaks.json)."""
    by_flops = flops / peaks["flops_per_s"][dtype]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
