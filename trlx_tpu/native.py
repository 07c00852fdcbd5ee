"""ctypes bindings for the native host-side data engine (native/
trlx_native.cpp).

The shared library is compiled on first use (g++) from the tracked source
into a per-user cache directory OUTSIDE the source tree, named by the
source's hash — a library that does not match what git would commit can
never be loaded, and nothing is written under `native/`. Every entry point
has a numpy fallback so the package works on machines without a
toolchain; `backend()` says which of the two is in use.
`TRLX_TPU_NO_NATIVE=1` forces the fallback.

Reference parity note: the reference's host-side collation runs inside
torch's native DataLoader/tensor machinery (SURVEY.md §2.6); this module
is the explicit TPU-native equivalent of that surface.
"""

import ctypes
import hashlib
import os
import subprocess
from typing import List, Optional

import numpy as np

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native", "trlx_native.cpp",
)

_lib = None
_load_attempted = False


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    cache = os.environ.get("XDG_CACHE_HOME") or os.path.expanduser("~/.cache")
    return os.path.join(cache, "trlx_tpu", f"libtrlx_native-{digest}.so")


def _build(lib_path: str) -> bool:
    os.makedirs(os.path.dirname(lib_path), exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"  # rename in: concurrent builders race safely
    try:
        cmd = ["g++", "-O3", "-shared", "-fPIC", _SRC, "-o", tmp]
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0:
            logger.warning(f"native build failed: {proc.stderr.decode()[:500]}")
            return False
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning(f"native build unavailable: {e}")
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def backend() -> str:
    """Which collate path this process runs: "native" or "numpy"."""
    return "native" if get_lib() is not None else "numpy"


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled library, building it on first call; None if unusable."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("TRLX_TPU_NO_NATIVE"):
        return None
    if not os.path.exists(_SRC):
        return None
    lib_path = _lib_path()
    if not os.path.exists(lib_path) and not _build(lib_path):
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError as e:
        logger.warning(f"native library load failed: {e}")
        return None

    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.pad_stack_i32.argtypes = [
        ctypes.POINTER(i32p), i64p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int, i32p,
    ]
    lib.pad_stack_f32.argtypes = [
        ctypes.POINTER(f32p), i64p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_float, ctypes.c_int, f32p,
    ]
    lib.ppo_collate.argtypes = [
        ctypes.POINTER(i32p), i64p, ctypes.POINTER(i32p), i64p,
        ctypes.POINTER(f32p), i64p, ctypes.POINTER(f32p), i64p,
        ctypes.POINTER(f32p), i64p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int,
        i32p, i32p, f32p, f32p, f32p,
    ]
    _lib = lib
    logger.info("native data engine loaded")
    return _lib


def _as_rows(seqs: List[np.ndarray], dtype) -> tuple:
    """Contiguous per-row arrays + (pointer array, length array)."""
    rows = [np.ascontiguousarray(np.asarray(s).ravel(), dtype=dtype) for s in seqs]
    ctype = ctypes.c_int32 if dtype == np.int32 else ctypes.c_float
    ptrs = (ctypes.POINTER(ctype) * len(rows))(
        *[r.ctypes.data_as(ctypes.POINTER(ctype)) for r in rows]
    )
    lens = np.asarray([len(r) for r in rows], dtype=np.int64)
    return rows, ptrs, lens


def pad_stack(
    seqs: List[np.ndarray], pad_value, max_len: int, dtype, left: bool = False
) -> np.ndarray:
    """Pad-and-stack rows into [n, max_len]; C++ when available."""
    dtype = np.dtype(dtype)
    lib = get_lib() if dtype in (np.int32, np.float32) else None
    if lib is None:
        out = np.full((len(seqs), max_len), pad_value, dtype=dtype)
        for i, s in enumerate(seqs):
            s = np.asarray(s)[:max_len]
            if left:
                out[i, max_len - len(s):] = s
            else:
                out[i, : len(s)] = s
        return out

    out = np.empty((len(seqs), max_len), dtype=dtype)
    rows, ptrs, lens = _as_rows(seqs, dtype)
    i64p = lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    if dtype == np.int32:
        lib.pad_stack_i32(
            ptrs, i64p, len(rows), max_len, int(pad_value), int(left),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
    else:
        lib.pad_stack_f32(
            ptrs, i64p, len(rows), max_len, float(pad_value), int(left),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
    return out


def ppo_collate(elems, max_q: int, max_r: int, max_p: int, pad_id: int, left_queries: bool):
    """Fused PPORLBatch collation. Returns (queries, responses, logprobs,
    values, rewards) numpy arrays."""
    lib = get_lib()
    n = len(elems)
    if lib is None:
        q = pad_stack([e.query_tensor for e in elems], pad_id, max_q, np.int32, left=left_queries)
        r = pad_stack([e.response_tensor for e in elems], pad_id, max_r, np.int32)
        lp = pad_stack([e.logprobs for e in elems], 0.0, max_p, np.float32)
        v = pad_stack([e.values for e in elems], 0.0, max_p, np.float32)
        rw = pad_stack([e.rewards for e in elems], 0.0, max_p, np.float32)
        return q, r, lp, v, rw

    q_rows, q_ptrs, q_lens = _as_rows([e.query_tensor for e in elems], np.int32)
    r_rows, r_ptrs, r_lens = _as_rows([e.response_tensor for e in elems], np.int32)
    lp_rows, lp_ptrs, lp_lens = _as_rows([e.logprobs for e in elems], np.float32)
    v_rows, v_ptrs, v_lens = _as_rows([e.values for e in elems], np.float32)
    rw_rows, rw_ptrs, rw_lens = _as_rows([e.rewards for e in elems], np.float32)

    out_q = np.empty((n, max_q), np.int32)
    out_r = np.empty((n, max_r), np.int32)
    out_lp = np.empty((n, max_p), np.float32)
    out_v = np.empty((n, max_p), np.float32)
    out_rw = np.empty((n, max_p), np.float32)

    i64 = ctypes.POINTER(ctypes.c_int64)
    lib.ppo_collate(
        q_ptrs, q_lens.ctypes.data_as(i64),
        r_ptrs, r_lens.ctypes.data_as(i64),
        lp_ptrs, lp_lens.ctypes.data_as(i64),
        v_ptrs, v_lens.ctypes.data_as(i64),
        rw_ptrs, rw_lens.ctypes.data_as(i64),
        n, max_q, max_r, max_p, int(pad_id), int(left_queries),
        out_q.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_r.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_lp.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_rw.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out_q, out_r, out_lp, out_v, out_rw
