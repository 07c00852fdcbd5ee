"""Compile ledger: per-function recompile accounting with retrace-storm
forensics.

The codebase carries dozens of load-bearing "no recompile" invariants —
the sentinel restore path re-donates into the same train-step program,
the adapter store stacks factors at fixed shapes so multi-tenant decode
never retraces, the pipelined scheduler keys its builds so a checkpoint
swap reuses programs — but until now they were enforced only by
comments. One silent retrace of a 6B train step costs a ~20-minute
recompile on a pod; this module makes every compile an *event*:

- ``ledgered_jit(fn, name=..., budget=..., ledger=...)`` wraps the
  repo's jit entry points. **Ledger off (None) it returns exactly
  ``jax.jit(fn, **jit_kwargs)``** — no wrapper object, no per-call
  bookkeeping, bitwise-identical programs (pinned by
  tests/test_compile_hbm.py). Ledger on, the traced body sets a
  thread-local marker that only fires on a cache miss (tracing *is* the
  miss), so steady-state calls pay one monotonic read and two attribute
  touches.
- every compile records the function's **abstract argument signature**
  (per-leaf path -> ``dtype[shape]`` + weak-type flag, static kwargs by
  repr) computed *after* the call from array metadata — donation deletes
  buffers but `.shape`/`.dtype` survive, so signature capture never
  resurrects a donated Array.
- a **retrace-storm detector** flags any function compiled more than its
  declared budget and emits the signature *diff* against the previous
  compile — the exact leaf whose shape/dtype churned — into a
  flight-recorder ring, the ``compile/*`` tracker stat family,
  ``trlx_tpu_compiles_total{fn=...}`` Prometheus series, and a
  once-per-fn postmortem bundle via `maybe_dump`.
- `jax.monitoring` listeners (installed once per process, forwarded to
  every live ledger through a weak registry) supply true backend-compile
  seconds and — when `train.compilation_cache_dir` wires the persistent
  compilation cache — cache hit/miss counts, so a warm-start run shows
  up as compiles with near-zero backend seconds.

Like the tracer and the flight recorders, ledgers are explicit context
objects: components hold ``compile_ledger = None`` and every wrap site
routes through it — there is no ambient "current ledger" to leak across
tests or replicas.
"""

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from trlx_tpu.observability.flight_recorder import FlightRecorder
from trlx_tpu.observability.postmortem import maybe_dump
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

#: every live CompileLedger, so the process-wide jax.monitoring listeners
#: (installed at most once; jax has no public unregister) can forward
#: backend-compile durations and persistent-cache hit/miss events without
#: pinning ledgers past their owner's lifetime
_ledgers: "weakref.WeakSet" = weakref.WeakSet()
_ledgers_lock = threading.Lock()
_monitoring_installed = False

# jax.monitoring event names (stable since jax 0.4.x)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/tracing_duration"  # jaxpr trace, when emitted
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _forward(method: str, *args) -> None:
    with _ledgers_lock:
        targets = list(_ledgers)
    for led in targets:
        try:
            getattr(led, method)(*args)
        except Exception:  # pragma: no cover - never raise into jax
            pass


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _forward("_note_backend_compile", float(duration_secs))
    elif event == _TRACE_EVENT:
        _forward("_note_trace_duration", float(duration_secs))


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_MISS_EVENT:
        _forward("_note_cache", False)
    elif event == _CACHE_HIT_EVENT:
        _forward("_note_cache", True)


def install_monitoring() -> bool:
    """Register the process-wide jax.monitoring forwarders (idempotent).
    Returns True when the listeners are installed (now or earlier),
    False when jax.monitoring is unavailable."""
    global _monitoring_installed
    if _monitoring_installed:
        return True
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:  # pragma: no cover - very old jax
        return False
    _monitoring_installed = True
    return True


# ----------------------------------------------------------------------
# Abstract argument signatures
# ----------------------------------------------------------------------


def _describe_leaf(leaf: Any) -> str:
    """One leaf -> a short stable string: arrays as ``dtype[shape]``
    (``~`` suffix for weak types — a python-scalar promotion flipping an
    argument between weak and strong dtype is a classic silent retrace),
    everything else by truncated repr (static/tree-structure leaves)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        weak = "~" if getattr(leaf, "weak_type", False) else ""
        dims = ",".join(str(d) for d in shape)
        return f"{dtype}[{dims}]{weak}"
    r = repr(leaf)
    return r if len(r) <= 64 else r[:61] + "..."


def arg_signature(args: tuple, kwargs: Optional[dict] = None) -> Tuple[Tuple[str, str], ...]:
    """Flatten (args, kwargs) with tree paths and describe every leaf.
    Reads only shape/dtype metadata, which survives donation — safe to
    call on arguments a jitted call just consumed."""
    import jax

    leaves_with_paths, _ = jax.tree_util.tree_flatten_with_path(
        (args, kwargs or {})
    )
    out = []
    for path, leaf in leaves_with_paths:
        try:
            key = jax.tree_util.keystr(path)
        except Exception:  # pragma: no cover
            key = str(path)
        try:
            out.append((key, _describe_leaf(leaf)))
        except Exception:  # pragma: no cover - exotic leaf repr
            out.append((key, "<unprintable>"))
    return tuple(out)


def signature_diff(
    prev: Optional[Tuple[Tuple[str, str], ...]],
    cur: Tuple[Tuple[str, str], ...],
) -> List[Dict[str, Optional[str]]]:
    """Per-leaf diff between two signatures: exactly the leaves whose
    abstract value changed (``before``/``after``), appeared (``before``
    None) or vanished (``after`` None). Empty when the signatures match —
    a retrace with an empty diff means the *function object* churned
    (a rebuilt closure), which the storm detail calls out."""
    if prev is None:
        return []
    a, b = dict(prev), dict(cur)
    out: List[Dict[str, Optional[str]]] = []
    for key in list(a) + [k for k in b if k not in a]:
        va, vb = a.get(key), b.get(key)
        if va != vb:
            out.append({"leaf": key, "before": va, "after": vb})
    return out


# ----------------------------------------------------------------------
# The ledger
# ----------------------------------------------------------------------


class _FnRecord:
    __slots__ = ("name", "budget", "compiles", "calls", "compile_wall_s",
                 "last_signature", "storms")

    def __init__(self, name: str, budget: int):
        self.name = name
        self.budget = int(budget)
        self.compiles = 0
        self.calls = 0
        self.compile_wall_s = 0.0
        self.last_signature: Optional[Tuple[Tuple[str, str], ...]] = None
        self.storms = 0


class CompileLedger:
    """Per-function compile accounting for one trainer / engine / bench
    run. Thread-safe: wrap sites run on the driver thread, the jax
    monitoring forwarders on whichever thread compiles."""

    def __init__(self, ring_capacity: int = 256,
                 postmortem_dir: str = "logs/postmortems",
                 config: Optional[Dict[str, Any]] = None):
        self._lock = threading.Lock()
        self.fns: Dict[str, _FnRecord] = {}
        self.recorder = FlightRecorder("compile_ledger", ring_capacity)
        self.storms: List[Dict[str, Any]] = []
        self.postmortem_dir = postmortem_dir
        self.config = config
        self.backend_compile_s = 0.0  # XLA time, from jax.monitoring
        self.trace_s = 0.0  # jaxpr tracing time, when jax emits it
        self.cache_hits = 0  # persistent compilation cache (when wired)
        self.cache_misses = 0
        self._tls = threading.local()
        with _ledgers_lock:
            _ledgers.add(self)
        install_monitoring()

    # -- jax.monitoring intake (any thread) ----------------------------

    def _note_backend_compile(self, seconds: float) -> None:
        with self._lock:
            self.backend_compile_s += seconds

    def _note_trace_duration(self, seconds: float) -> None:
        with self._lock:
            self.trace_s += seconds

    def _note_cache(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    # -- wrap sites ----------------------------------------------------

    def declare_budget(self, name: str, budget: int) -> None:
        with self._lock:
            rec = self.fns.get(name)
            if rec is None:
                self.fns[name] = _FnRecord(name, budget)
            else:
                rec.budget = int(budget)

    def jit(self, fn: Callable, name: Optional[str] = None,
            budget: int = 1, **jit_kwargs) -> Callable:
        """jax.jit `fn` with compile interception. The inner wrapper runs
        INSIDE the trace (it executes only on a cache miss — tracing is
        the miss), flagging a thread-local; the outer wrapper reads the
        flag and records the compile with the call's argument signature."""
        import jax

        fn_name = name or getattr(fn, "__name__", "fn") or "fn"
        self.declare_budget(fn_name, budget)
        tls = self._tls

        def _traced(*args, **kwargs):
            tls.compiled = True
            return fn(*args, **kwargs)

        _traced.__name__ = getattr(fn, "__name__", fn_name)
        _traced.__doc__ = fn.__doc__
        jitted = jax.jit(_traced, **jit_kwargs)

        def _call(*args, **kwargs):
            prev = getattr(tls, "compiled", False)
            tls.compiled = False
            t0 = time.monotonic()
            try:
                out = jitted(*args, **kwargs)
            finally:
                compiled, tls.compiled = tls.compiled, prev
            if compiled:
                # metadata-only signature: safe after donation
                self._note_compile(fn_name, arg_signature(args, kwargs),
                                   time.monotonic() - t0)
            else:
                with self._lock:
                    rec = self.fns.get(fn_name)
                    if rec is not None:
                        rec.calls += 1
            return out

        _call.__name__ = fn_name
        _call._ledgered = True  # introspection hook for tests
        _call._jitted = jitted  # escape hatch (.lower etc.)
        return _call

    def _note_compile(self, name: str,
                      sig: Tuple[Tuple[str, str], ...],
                      wall_s: float) -> None:
        with self._lock:
            rec = self.fns.get(name)
            if rec is None:
                rec = self.fns[name] = _FnRecord(name, 1)
            rec.compiles += 1
            rec.calls += 1
            rec.compile_wall_s += wall_s
            prev_sig, rec.last_signature = rec.last_signature, sig
            over = rec.compiles > rec.budget
            storm: Optional[Dict[str, Any]] = None
            if over:
                rec.storms += 1
                diff = signature_diff(prev_sig, sig)
                storm = {
                    "fn": name,
                    "compiles": rec.compiles,
                    "budget": rec.budget,
                    "wall_s": round(wall_s, 6),
                    "diff": diff,
                    # empty diff at identical signatures = the jit CACHE
                    # was lost (rebuilt closure / new wrapper), not an
                    # argument churn — a different bug, called out as such
                    "cause": (
                        "argument signature churn" if diff
                        else "program cache lost (same signature recompiled)"
                    ),
                    "signature": list(sig),
                }
                self.storms.append(storm)
        self.recorder.record(
            "compile", fn=name, n=rec.compiles, wall_s=round(wall_s, 4),
            over_budget=over,
        )
        if storm is not None:
            logger.warning(
                f"retrace storm: {name} compiled {rec.compiles}x "
                f"(budget {rec.budget}); churned leaves: "
                + (", ".join(
                    f"{d['leaf']}: {d['before']} -> {d['after']}"
                    for d in storm["diff"]) or "none (cache lost)")
            )
            maybe_dump(
                f"retrace-storm:{name}",
                trigger=f"retrace-storm-{name}",
                out_dir=self.postmortem_dir,
                detail={**storm, "previous_signature":
                        list(prev_sig) if prev_sig else None},
                recorders=[self.recorder],
                config=self.config,
            )

    # -- output --------------------------------------------------------

    def counts(self) -> Dict[str, int]:
        """{fn: compiles} — the steady-state stability probe (cycle N
        counts must equal cycle 1 counts)."""
        with self._lock:
            return {n: r.compiles for n, r in self.fns.items()}

    def total_compiles(self) -> int:
        with self._lock:
            return sum(r.compiles for r in self.fns.values())

    def total_storms(self) -> int:
        with self._lock:
            return len(self.storms)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "functions": {
                    n: {
                        "compiles": r.compiles,
                        "budget": r.budget,
                        "calls": r.calls,
                        "compile_wall_s": round(r.compile_wall_s, 6),
                        "over_budget": r.compiles > r.budget,
                        "last_signature": (
                            list(r.last_signature)
                            if r.last_signature is not None else None
                        ),
                    }
                    for n, r in sorted(self.fns.items())
                },
                "total_compiles": sum(r.compiles for r in self.fns.values()),
                "storms": list(self.storms),
                "backend_compile_s": round(self.backend_compile_s, 6),
                "trace_s": round(self.trace_s, 6),
                "persistent_cache": {
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                },
            }

    def drain_stats(self) -> Dict[str, float]:
        """``compile/*`` floats for the tracker: totals plus one counter
        per over-budget function (quiet functions stay out of the logs)."""
        with self._lock:
            out: Dict[str, float] = {
                "compile/total": float(
                    sum(r.compiles for r in self.fns.values())),
                "compile/storms": float(len(self.storms)),
                "compile/backend_s": self.backend_compile_s,
                "compile/cache_hits": float(self.cache_hits),
                "compile/cache_misses": float(self.cache_misses),
            }
            for n, r in self.fns.items():
                if r.compiles > r.budget:
                    key = "".join(c if c.isalnum() or c in "._-[]" else "_"
                                  for c in n)
                    out[f"compile/over_budget/{key}"] = float(r.compiles)
        return out

    def render_prometheus(self, ns: str = "trlx_tpu") -> str:
        """`trlx_tpu_compiles_total{fn=...}` counters + storm/cache
        series for /metrics concatenation (dedupe_metadata-compatible)."""
        snap = self.snapshot()
        esc = lambda s: s.replace("\\", "\\\\").replace('"', '\\"')
        lines = [
            f"# HELP {ns}_compiles_total jit compiles per wrapped function",
            f"# TYPE {ns}_compiles_total counter",
        ]
        for name, rec in snap["functions"].items():
            lines.append(
                f'{ns}_compiles_total{{fn="{esc(name)}"}} {rec["compiles"]}')
        lines += [
            f"# HELP {ns}_retrace_storms_total over-budget recompiles",
            f"# TYPE {ns}_retrace_storms_total counter",
            f"{ns}_retrace_storms_total {len(snap['storms'])}",
            f"# HELP {ns}_backend_compile_seconds_total XLA compile seconds",
            f"# TYPE {ns}_backend_compile_seconds_total counter",
            f"{ns}_backend_compile_seconds_total {snap['backend_compile_s']}",
            f"# HELP {ns}_compile_cache_hits_total persistent compilation cache hits",
            f"# TYPE {ns}_compile_cache_hits_total counter",
            f"{ns}_compile_cache_hits_total {snap['persistent_cache']['hits']}",
            f"# HELP {ns}_compile_cache_misses_total persistent compilation cache misses",
            f"# TYPE {ns}_compile_cache_misses_total counter",
            f"{ns}_compile_cache_misses_total {snap['persistent_cache']['misses']}",
        ]
        return "\n".join(lines) + "\n"


def ledgered_jit(fn: Callable, name: Optional[str] = None, budget: int = 1,
                 ledger: Optional[CompileLedger] = None,
                 **jit_kwargs) -> Callable:
    """The repo's jit entry point. ``ledger=None`` (observability off)
    returns **exactly** ``jax.jit(fn, **jit_kwargs)`` — the pre-ledger
    program, bitwise identical, zero wrapper overhead. With a ledger,
    compiles of `fn` are intercepted and accounted under `name` against
    `budget`."""
    if ledger is None:
        import jax

        return jax.jit(fn, **jit_kwargs)
    return ledger.jit(fn, name=name, budget=budget, **jit_kwargs)
