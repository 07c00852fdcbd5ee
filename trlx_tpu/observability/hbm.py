"""Device-memory (HBM) ledger: the analytic byte model + a measured
live-usage overlay + OOM forensics.

The analytic side moves the byte math that was scattered across
`scripts/scale_memory_check.py` (params / optimizer budgeting) and
`inference/engine.py::kv_stats` (paged-arena bytes incl. int8 scale
planes) into one importable module — the `flops.py` extraction pattern
from PR 15: any change to the model moves the offline checker, the live
ledger, and the engine's own accounting together. The measured side
overlays what is *actually resident*: `device.memory_stats()` where the
backend provides it (TPU/GPU), a `jax.live_arrays()` sum on CPU — always
guarded by `.is_deleted()`, because sampling can race a jitted step that
donated its inputs (the PR 12 `active_slots` lesson: a deleted Array's
data is gone but its `shape`/`dtype`/`nbytes` metadata is not, and
touching anything else raises).

An `HBMLedger` hangs off the same `PhaseTimeline` hooks as the goodput
ledger (`timeline.hbm = ledger`): every phase boundary takes one sample
into per-phase peak watermarks (`hbm_peak_bytes{phase=...}` gauges),
which surface in healthz, goodput.json, the bench phase JSON, and the
`hbm/*` tracker stat family.

OOM forensics: `oom_postmortem()` catches XLA RESOURCE_EXHAUSTED at the
train-step and engine-dispatch boundaries and dumps a memory postmortem
— ledger snapshot, kv/session/adapter occupancy, the largest live
buffers, and the compile history — once per site via `maybe_dump`,
before the error re-raises. The bundle answers the question a raw
RESOURCE_EXHAUSTED never does: *what held the memory*.
"""

import threading
from typing import Any, Callable, Dict, List, Optional

from trlx_tpu.observability.postmortem import maybe_dump
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

GiB = 1024 ** 3

# HBM bytes per chip by device kind — the capacity row PEAK_FLOPS
# (observability/flops.py) is the compute row of.
HBM_BYTES = [
    ("v5 lite", 16 * GiB),  # TPU v5e
    ("v5e", 16 * GiB),
    ("v5p", 95 * GiB),
    ("v4", 32 * GiB),
    ("v6", 32 * GiB),  # trillium
]


def _itemsize(dtype) -> int:
    import numpy as np

    return int(np.dtype(dtype).itemsize)


def device_hbm_bytes(device=None) -> int:
    """Capacity of one device: the backend's own `bytes_limit` when
    memory_stats is available, else the device-kind table, else 0
    (unknown — CPU hosts; callers treat 0 as "no capacity bound")."""
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
    except Exception:  # no backend / host-only tooling
        return 0
    try:
        stats = dev.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:
        pass
    kind = getattr(dev, "device_kind", "").lower()
    for tag, cap in HBM_BYTES:
        if tag in kind:
            return cap
    return 0


# ----------------------------------------------------------------------
# Analytic byte model (shared: scale_memory_check, engine, trainer, bench)
# ----------------------------------------------------------------------


def params_bytes(n_params: int, dtype_bytes: int = 4) -> int:
    return int(n_params) * int(dtype_bytes)


def optimizer_bytes(n_trainable: int, dtype_bytes: int = 4,
                    moments: int = 2) -> int:
    """AdamW state: `moments` f32 trees (mu, nu) mirroring the TRAINABLE
    partition leaf-for-leaf (frozen params carry no state)."""
    return int(n_trainable) * int(dtype_bytes) * int(moments)


def grads_bytes(n_trainable: int, dtype_bytes: int = 4) -> int:
    """The gradient tree materialized between backward and the optimizer
    update (donated through, but live at the peak)."""
    return int(n_trainable) * int(dtype_bytes)


def kv_arena_bytes(n_layers: int, kv_heads: int, head_dim: int,
                   n_blocks: int, block_size: int, dtype="float32") -> int:
    """Paged KV arena: per-layer K and V of `n_blocks x kv_heads x
    block_size x head_dim` elements (layout: ops/paged_attention.py), plus
    per-(block, head, position) f32 scale planes when the cache quantizes
    to int8. Logical bytes: the TPU pads a head_dim under 128 up to a full
    lane tile. THE formula `engine.kv_stats` reports — the engine delegates
    here, so the offline budget and the live counter can never drift."""
    import numpy as np

    itemsize = _itemsize(dtype)
    n = (2 * n_layers * n_blocks * block_size * kv_heads * head_dim
         * itemsize)
    if np.dtype(dtype) == np.int8:  # f32 scale planes
        n += 2 * n_layers * n_blocks * block_size * kv_heads * 4
    return int(n)


def latent_arena_bytes(n_layers: int, latent_width: int, n_blocks: int,
                       block_size: int, dtype="bfloat16") -> int:
    """Paged latent arena: `n_blocks x block_size x latent_width` elements a
    latent-attention layer, `latent_width` what a token caches there (the
    latent plane, and a sparse layer's index keys beside it; layouts:
    ops/paged_attention.py, "Latent" and "Index"); no int8 form, so no scale planes."""
    return int(n_layers * n_blocks * block_size * latent_width * _itemsize(dtype))


def paged_arena_bytes(cfg, n_blocks: int, block_size: int, dtype="float32") -> int:
    """The paged arena `init_paged_kv_arena` allocates for `cfg`, from what
    each layer keeps a token (`cfg.layer_keeps`): K and V by head for its
    attention layers; for a latent layer its latent plane, of its own kind's
    width, and the index's keys where it keeps them; nothing for a layer
    whose state is a slot's (`slot_state_bytes` counts that)."""
    latent_of = getattr(cfg, "latent_of", lambda kind: None)
    latent = [i for i in range(cfg.n_layers) if latent_of(cfg.layer_op(i)) is not None]
    # K/V layers, a looped stack's once a pass (`LayerKeeps.passes`: a pool a pass, end to end)
    by_head = sum(cfg.layer_keeps(i).passes for i in range(cfg.n_layers)
                  if cfg.layer_keeps(i).token and i not in latent)
    return (kv_arena_bytes(by_head, cfg.kv_heads, cfg.head_dim, n_blocks, block_size, dtype)
            + sum(latent_arena_bytes(1, sum(cfg.cache_planes(i)), n_blocks, block_size, dtype) for i in latent))


def slot_state_bytes(cfg, num_slots: int, dtype="float32") -> int:
    """What `init_paged_kv_arena` allocates beside the arena: each layer's
    arrays a slot (`cfg.layer_keeps(i).slot`: a convolution's last inputs in
    the cache's type, a recurrent matrix in its own), `num_slots` rows."""
    return int(num_slots) * cfg.slot_state_bytes_per_slot(dtype)


def kv_cache_bytes(n_layers: int, kv_heads: int, head_dim: int,
                   batch: int, cache_len: int, dtype="float32") -> int:
    """Dense (non-paged) per-slot KV pool: K and V of
    `batch x cache_len x kv_heads x head_dim` per layer."""
    return int(2 * n_layers * batch * cache_len * kv_heads * head_dim
               * _itemsize(dtype))


def decode_state_bytes(cfg, batch: int, cache_len: int, dtype="float32") -> int:
    """The fused sampler's cache for `cfg` (`init_kv_cache`): K/V tables for
    the attention layers only, one latent plane for each latent layer, plus
    the short convolution's state, the last `conv_kernel - 1` inputs a
    channel, for each `conv` layer."""
    # full and sliding layers alike hold a table of `cache_len` (a sliding
    # layer gives nothing back yet: ROADMAP R4)
    if not hasattr(cfg, "layer_keeps"):  # a config that names no layer kinds at all
        return kv_cache_bytes(cfg.n_layers, cfg.kv_heads, cfg.head_dim, batch, cache_len, dtype)
    return int(batch * cache_len * cfg.cached_values_per_token * _itemsize(dtype)
               + slot_state_bytes(cfg, batch, dtype))


def trunk_cache_bytes(rows: int, seq_len: int, d_model: int,
                      dtype="float32") -> int:
    """Frozen-trunk activation cache: the `[rows, seq_len, d_model]` state
    entering the first trainable block, kept for a PPO cycle
    (`PPOTrainer._trunk_cache_available` holds it to a share of the
    device)."""
    return int(rows) * int(seq_len) * int(d_model) * _itemsize(dtype)


def analytic_train_components(
    cfg,
    n_params: int,
    n_trainable: int,
    minibatch: int,
    seq_length: int,
    rollout_rows: int = 0,
    max_new_tokens: int = 0,
    param_dtype_bytes: int = 4,
    kv_dtype="float32",
) -> Dict[str, int]:
    """Itemized per-process analytic budget for one PPO train config:
    params + AdamW moments + a grads tree + the rollout decode KV cache
    (the generation high-water mark). Used by `scale_memory_check.py`
    (divided across the mesh there) and by the live ledger's analytic
    account; activation temps are XLA's to report
    (`compiled.memory_analysis()`), not modeled here."""
    kv = 0
    if rollout_rows and seq_length:
        kv = decode_state_bytes(cfg, rollout_rows, seq_length, kv_dtype)
    out = {
        "params_bytes": params_bytes(n_params, param_dtype_bytes),
        "optimizer_bytes": optimizer_bytes(n_trainable, 4),
        "grads_bytes": grads_bytes(n_trainable, param_dtype_bytes),
        "kv_cache_bytes": kv,
    }
    out["total_bytes"] = sum(out.values())
    return out


# ----------------------------------------------------------------------
# Measured live usage
# ----------------------------------------------------------------------


def live_array_bytes() -> int:
    """Sum of `nbytes` over the process's live (undeleted) jax Arrays —
    the CPU fallback for `device.memory_stats()`. Donation-safe: a
    deleted Array keeps shape/dtype/nbytes metadata; only its buffer is
    gone, and `is_deleted()` is the documented probe."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            if a.is_deleted():
                continue
            total += int(a.nbytes)
        except Exception:  # pragma: no cover - exotic array types
            continue
    return total


def largest_live_buffers(n: int = 15) -> List[Dict[str, Any]]:
    """Top-`n` live Arrays by size — the "what held the memory" section
    of an OOM postmortem. Metadata only; never touches buffer data."""
    import jax

    rows: List[Dict[str, Any]] = []
    try:
        arrays = jax.live_arrays()
    except Exception:  # pragma: no cover
        return rows
    for a in arrays:
        try:
            if a.is_deleted():
                continue
            rows.append({
                "shape": list(a.shape),
                "dtype": str(a.dtype),
                "nbytes": int(a.nbytes),
            })
        except Exception:  # pragma: no cover
            continue
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:n]


class HBMLedger:
    """Analytic account + measured watermarks for one device's memory.

    Attach to a `PhaseTimeline` with ``timeline.hbm = ledger`` — every
    phase boundary samples live usage into that phase's peak watermark.
    Components call `set_component` with their analytic bytes (KV arena,
    trunk cache, resident adapters) as they size them."""

    def __init__(self, capacity_bytes: Optional[int] = None, device=None):
        self._lock = threading.Lock()
        self.device = device
        self.capacity_bytes = (int(capacity_bytes) if capacity_bytes
                               else device_hbm_bytes(device))
        self.components: Dict[str, Dict[str, Any]] = {}
        self.watermarks: Dict[str, int] = {}  # phase -> peak measured bytes
        self.peak_bytes = 0
        self.samples = 0
        self.source: Optional[str] = None  # memory_stats | live_arrays

    # -- analytic account ---------------------------------------------

    def set_component(self, name: str, nbytes: int, **detail) -> None:
        with self._lock:
            self.components[str(name)] = {"bytes": int(nbytes), **detail}

    def analytic_total(self) -> int:
        with self._lock:
            return sum(c["bytes"] for c in self.components.values())

    # -- measured overlay ---------------------------------------------

    def measure(self) -> int:
        """One reading of live device memory (bytes). Prefers the
        backend's allocator stats; falls back to the live-Array sum."""
        try:
            import jax

            dev = self.device if self.device is not None else jax.devices()[0]
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            self.source = "memory_stats"
            # peak_bytes_in_use is the allocator's own high-water mark —
            # strictly better than our sampled peak when present
            peak = stats.get("peak_bytes_in_use")
            if peak:
                with self._lock:
                    self.peak_bytes = max(self.peak_bytes, int(peak))
            return int(stats["bytes_in_use"])
        self.source = "live_arrays"
        return live_array_bytes()

    def sample(self, phase: str = "unphased") -> int:
        """Measure and fold into the phase's (and the global) peak."""
        used = self.measure()
        with self._lock:
            self.samples += 1
            if used > self.watermarks.get(phase, -1):
                self.watermarks[phase] = used
            if used > self.peak_bytes:
                self.peak_bytes = used
        return used

    def observe_phase(self, name: str, t0: float, t1: float,
                      first: bool = False,
                      attrs: Optional[Dict[str, Any]] = None) -> None:
        """PhaseTimeline hook (same shape as GoodputLedger's): one sample
        at each phase end, keyed by the phase name."""
        self.sample(phase=name)

    # -- output --------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            analytic = sum(c["bytes"] for c in self.components.values())
            return {
                "capacity_bytes": self.capacity_bytes,
                "analytic": {
                    "components": {n: dict(c)
                                   for n, c in sorted(self.components.items())},
                    "total_bytes": analytic,
                    "headroom_bytes": (
                        self.capacity_bytes - analytic
                        if self.capacity_bytes else None
                    ),
                },
                "measured": {
                    "peak_bytes": self.peak_bytes,
                    "per_phase_peak_bytes": dict(sorted(self.watermarks.items())),
                    "samples": self.samples,
                    "source": self.source,
                },
            }

    def drain_stats(self) -> Dict[str, float]:
        """``hbm/*`` floats for the tracker."""
        with self._lock:
            analytic = sum(c["bytes"] for c in self.components.values())
            out = {
                "hbm/peak_bytes": float(self.peak_bytes),
                "hbm/analytic_bytes": float(analytic),
            }
            if self.capacity_bytes:
                out["hbm/capacity_bytes"] = float(self.capacity_bytes)
                out["hbm/peak_utilization"] = (
                    self.peak_bytes / self.capacity_bytes)
            return out

    def render_prometheus(self, ns: str = "trlx_tpu") -> str:
        """`hbm_peak_bytes{phase=...}` watermark gauges + capacity /
        analytic totals for /metrics concatenation."""
        snap = self.snapshot()
        esc = lambda s: s.replace("\\", "\\\\").replace('"', '\\"')
        lines = [
            f"# HELP {ns}_hbm_peak_bytes peak measured device bytes per phase",
            f"# TYPE {ns}_hbm_peak_bytes gauge",
        ]
        for phase, peak in snap["measured"]["per_phase_peak_bytes"].items():
            lines.append(f'{ns}_hbm_peak_bytes{{phase="{esc(phase)}"}} {peak}')
        lines.append(f'{ns}_hbm_peak_bytes{{phase="all"}} '
                     f'{snap["measured"]["peak_bytes"]}')
        lines += [
            f"# HELP {ns}_hbm_capacity_bytes device HBM capacity",
            f"# TYPE {ns}_hbm_capacity_bytes gauge",
            f"{ns}_hbm_capacity_bytes {snap['capacity_bytes']}",
            f"# HELP {ns}_hbm_analytic_bytes analytic component total",
            f"# TYPE {ns}_hbm_analytic_bytes gauge",
            f"{ns}_hbm_analytic_bytes {snap['analytic']['total_bytes']}",
        ]
        return "\n".join(lines) + "\n"


# ----------------------------------------------------------------------
# OOM forensics
# ----------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM when allocating")


def is_oom_error(exc: BaseException) -> bool:
    """True for XLA RESOURCE_EXHAUSTED / allocator OOM errors, matched on
    the message (jaxlib's XlaRuntimeError carries the status name in
    str())."""
    s = f"{type(exc).__name__}: {exc}"
    return any(m in s for m in _OOM_MARKERS)


def oom_postmortem(
    site: str,
    exc: BaseException,
    hbm: Optional[HBMLedger] = None,
    compile_ledger=None,
    context: Optional[Dict[str, Any]] = None,
    config: Optional[Dict[str, Any]] = None,
    out_dir: str = "logs/postmortems",
) -> Optional[str]:
    """Dump a memory postmortem for an OOM caught at `site`, once per
    site (`maybe_dump` registry). `context` values may be callables —
    they are evaluated here, best-effort, so the failing path never pays
    for them until it is already dead. Returns the bundle dir (first
    fire) or None. Callers re-raise the original error regardless."""
    detail: Dict[str, Any] = {
        "site": str(site),
        "error": f"{type(exc).__name__}: {exc}"[:4000],
    }
    if hbm is not None:
        try:
            hbm.sample(phase=f"oom:{site}")
            detail["hbm"] = hbm.snapshot()
        except Exception:  # pragma: no cover - best effort
            pass
    if compile_ledger is not None:
        try:
            detail["compile"] = compile_ledger.snapshot()
        except Exception:  # pragma: no cover - best effort
            pass
    for key, val in (context or {}).items():
        try:
            detail[key] = val() if callable(val) else val
        except Exception as e:  # a dead engine may not answer kv_stats
            detail[key] = f"<unavailable: {type(e).__name__}: {e}>"
    try:
        detail["largest_live_buffers"] = largest_live_buffers()
    except Exception:  # pragma: no cover - best effort
        pass
    return maybe_dump(
        f"oom:{site}", trigger=f"oom-{site}", out_dir=out_dir,
        detail=detail, config=config,
    )
