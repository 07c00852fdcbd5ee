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
- the process's **build account** (`account()`, installed by `import
  trlx_tpu`, always on) hears every trace to a jaxpr, every lowering and
  every backend compile or cache read from `jax.monitoring`, by program;
  a ledger's backend seconds and persistent-cache hits and misses are the
  account's, so a warm-start run shows up as compiles with near-zero
  backend seconds.

Like the tracer and the flight recorders, ledgers are explicit context
objects: components hold ``compile_ledger = None`` and every wrap site
routes through it — there is no ambient "current ledger" to leak across
tests or replicas.
"""

import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from trlx_tpu.observability import tracing
from trlx_tpu.observability.flight_recorder import FlightRecorder
from trlx_tpu.observability.postmortem import maybe_dump
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

# ----------------------------------------------------------------------
# The build account
# ----------------------------------------------------------------------

# jax.monitoring names (JAX 0.9.0). Each of the three spans comes with a
# `fun_name`, and a scalar of the same name when it STARTS.
_TRACE_SPAN = "/jax/core/compile/jaxpr_trace_duration"
_KINDS = {
    _TRACE_SPAN: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # a compile or a read from the persistent cache: what the runtime waits for
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}

#: `trlx:build.program` spans a `write()`, heaviest first; the rest is one
#: `name=(others)`
_PROGRAMS_WRITTEN = 64

_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _program_name(fun_name: str) -> str:
    """One key a program: a trace reports `decode`, its lowering and its
    backend compile `jit(decode)`. Blanks go, since the name stands in a
    counter span's name (`<unnamed wrapped function>`)."""
    wrapped = _WRAPPED.match(fun_name)
    return "_".join((wrapped.group(1) if wrapped else fun_name).split()) or "?"


class _Program:
    """What one `fun_name` cost: events and seconds by kind, and where on
    the account's clock the first began and the last ended."""

    __slots__ = ("events", "seconds", "first_at", "last_at")

    def __init__(self, at: float):
        self.events = dict.fromkeys(_KINDS.values(), 0)
        self.seconds = dict.fromkeys(_KINDS.values(), 0.0)
        self.first_at = self.last_at = at

    def add(self, other: "_Program") -> None:
        for kind in self.events:
            self.events[kind] += other.events[kind]
            self.seconds[kind] += other.seconds[kind]
        self.first_at = min(self.first_at, other.first_at)
        self.last_at = max(self.last_at, other.last_at)

    def counters(self) -> Dict[str, float]:
        return {
            "builds": self.events["backend"],
            **{f"{kind}_s": round(s, 4) for kind, s in self.seconds.items()},
            "first_at_s": round(self.first_at, 3), "last_at_s": round(self.last_at, 3),
        }


class BuildAccount:
    """What the process has spent building programs, heard from
    `jax.monitoring`: every trace to a jaxpr, every lowering, every backend
    compile or read from the persistent cache, folded into one row a
    program (memory follows the number of function names, not the uptime),
    and the cache's hits, misses and seconds. Always on: a listener adds to
    a row under a lock, nothing runs between builds, no `jit` is wrapped.

    Traces nest (`jnp.matmul`, itself jitted, reports a trace of its own
    inside the program's that calls it), so a thread's outermost trace is
    the one kept and `trace_s` counts no second twice. JAX hands
    `time.time()` pairs; durations are kept from them, and positions as
    seconds since the account was made."""

    def __init__(self):
        self._lock = threading.Lock()
        self._made_at = time.time()
        self._programs: Dict[str, _Program] = {}
        self._cache = {"cache_hits": 0, "cache_misses": 0, "cache_read_s": 0.0, "saved_s": 0.0}
        self._marks: Dict[str, Dict[str, float]] = {}
        self._open = threading.local()  # .traces: jaxpr traces this thread is inside

    # -- jax.monitoring intake (the thread that builds) -----------------

    def _on_start(self, event: str, value: float, **kwargs) -> None:
        if event == _TRACE_SPAN:
            self._open.traces = getattr(self._open, "traces", 0) + 1

    def _on_span(self, event: str, start: float, end: float,
                 fun_name: str = "?", **kwargs) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        if kind == "trace":
            # a release that sends no start hears every trace as outermost
            inside = self._open.traces = max(getattr(self._open, "traces", 1) - 1, 0)
            if inside:
                return
        name = _program_name(fun_name)
        with self._lock:
            row = self._programs.get(name)
            if row is None:
                row = self._programs[name] = _Program(start - self._made_at)
            row.events[kind] += 1
            row.seconds[kind] += end - start
            row.last_at = end - self._made_at
        if tracing.active():
            # on the trace's clock, at the build's end: a program built inside
            # a traced window stands next to the idle gap it made
            tracing.counters("build.event", kind=kind, name=name,
                             ms=round(1e3 * (end - start), 3))

    def _on_seconds(self, event: str, seconds: float, **kwargs) -> None:
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            with self._lock:
                self._cache[key] += seconds

    def _on_count(self, event: str, **kwargs) -> None:
        key = _CACHE_COUNTS.get(event)
        if key is not None:
            with self._lock:
                self._cache[key] += 1

    # -- readers --------------------------------------------------------

    def _totals(self) -> Dict[str, float]:
        rows = self._programs.values()
        return {
            "programs": len(rows),
            "builds": sum(r.events["backend"] for r in rows),
            **{f"{kind}_s": sum(r.seconds[kind] for r in rows) for kind in _KINDS.values()},
            **self._cache,
            "at_s": time.time() - self._made_at,
        }

    def totals(self) -> Dict[str, float]:
        """From the process's start to this moment: `programs` (distinct
        names), `builds` (backend events), `trace_s`, `lower_s`, `backend_s`
        (compiles and cache reads together), the persistent cache's
        `cache_hits`, `cache_misses`, `cache_read_s`, `saved_s`, and `at_s`,
        this moment on the account's clock."""
        with self._lock:
            return self._totals()

    def programs(self) -> Dict[str, Dict[str, Any]]:
        """{name: {"events": {kind: n}, "seconds": {kind: s}, "first_at", "last_at"}}"""
        with self._lock:
            return {name: {"events": dict(r.events), "seconds": dict(r.seconds),
                           "first_at": r.first_at, "last_at": r.last_at}
                    for name, r in self._programs.items()}

    def mark(self, name: str) -> None:
        """Keep the totals of this moment under `name`, the FIRST time the
        name is seen: where an operator would say "ready"."""
        if name in self._marks:
            return
        with self._lock:
            self._marks.setdefault(name, self._totals())

    def marks(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: dict(totals) for name, totals in self._marks.items()}

    def write(self) -> None:
        """Into the active tracing session, as counter spans (the names
        carry the numbers; `tracing.stop()` calls this before it closes the
        session): one `trlx:build.total` a mark and one `mark=end`, and one
        `trlx:build.program` a program, heaviest first."""
        with self._lock:
            totals = {**self._marks, "end": self._totals()}
            rows = sorted(self._programs.items(), key=lambda kv: -sum(kv[1].seconds.values()))
            if len(rows) > _PROGRAMS_WRITTEN:
                others = _Program(rows[_PROGRAMS_WRITTEN][1].first_at)
                for _, row in rows[_PROGRAMS_WRITTEN:]:
                    others.add(row)
                rows = rows[:_PROGRAMS_WRITTEN] + [("(others)", others)]
            rows = [(name, row.counters()) for name, row in rows]
        for mark, at_mark in totals.items():
            tracing.counters("build.total", mark=mark, **{
                k: round(v, 4) if isinstance(v, float) else v for k, v in at_mark.items()})
        for name, counters in rows:
            tracing.counters("build.program", name=name, **counters)

    def render_prometheus(self, ns: str = "trlx_tpu") -> str:
        """The process's build totals for /metrics (dedupe_metadata-compatible)."""
        t = self.totals()
        lines = []
        for series, value, what in (
            ("build_trace_seconds_total", t["trace_s"], "seconds tracing functions to jaxprs"),
            ("build_lower_seconds_total", t["lower_s"], "seconds lowering jaxprs to modules"),
            ("build_backend_seconds_total", t["backend_s"],
             "seconds in backend compiles and persistent-cache reads"),
            ("build_cache_misses_total", t["cache_misses"], "persistent compilation cache misses"),
            ("build_programs_total", t["programs"], "distinct programs built"),
        ):
            lines += [f"# HELP {ns}_{series} {what}", f"# TYPE {ns}_{series} counter",
                      f"{ns}_{series} {round(value, 6)}"]
        return "\n".join(lines) + "\n"


_account: Optional[BuildAccount] = None
_install_lock = threading.Lock()


def install_monitoring() -> BuildAccount:
    """Make the process's build account and register its listeners with
    `jax.monitoring` (idempotent; jax keeps them for the process's life).
    `import trlx_tpu` calls this, so that the first `jit` is heard."""
    global _account
    with _install_lock:
        if _account is None:
            from jax import monitoring

            made = BuildAccount()
            monitoring.register_scalar_listener(made._on_start)
            monitoring.register_event_time_span_listener(made._on_span)
            monitoring.register_event_duration_secs_listener(made._on_seconds)
            monitoring.register_event_listener(made._on_count)
            _account = made
    return _account


def account() -> BuildAccount:
    """The process's build account."""
    return _account or install_monitoring()


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
    run: compiles, calls, budgets and storms of the functions IT wraps.
    Backend seconds, trace seconds and the persistent cache's hits and
    misses are the process's, read from the build account (`account()`)
    and the same in every ledger. Thread-safe."""

    def __init__(self, ring_capacity: int = 256,
                 postmortem_dir: str = "logs/postmortems",
                 config: Optional[Dict[str, Any]] = None):
        self._lock = threading.Lock()
        self.fns: Dict[str, _FnRecord] = {}
        self.recorder = FlightRecorder("compile_ledger", ring_capacity)
        self.storms: List[Dict[str, Any]] = []
        self.postmortem_dir = postmortem_dir
        self.config = config
        self._tls = threading.local()

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
        built = account().totals()
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
                "backend_compile_s": round(built["backend_s"], 6),
                "trace_s": round(built["trace_s"], 6),
                "lower_s": round(built["lower_s"], 6),
                "persistent_cache": {
                    "hits": built["cache_hits"],
                    "misses": built["cache_misses"],
                },
            }

    def drain_stats(self) -> Dict[str, float]:
        """``compile/*`` floats for the tracker: this ledger's totals, one
        counter per over-budget function (quiet functions stay out of the
        logs), and the process's build totals."""
        built = account().totals()
        with self._lock:
            out: Dict[str, float] = {
                "compile/total": float(
                    sum(r.compiles for r in self.fns.values())),
                "compile/storms": float(len(self.storms)),
                **{f"compile/{key}": float(built[key]) for key in (
                    "trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses",
                    "programs")},
            }
            for n, r in self.fns.items():
                if r.compiles > r.budget:
                    key = "".join(c if c.isalnum() or c in "._-[]" else "_"
                                  for c in n)
                    out[f"compile/over_budget/{key}"] = float(r.compiles)
        return out

    def render_prometheus(self, ns: str = "trlx_tpu") -> str:
        """`trlx_tpu_compiles_total{fn=...}` counters + the storm series
        for /metrics concatenation (dedupe_metadata-compatible); the
        process's build totals are `BuildAccount.render_prometheus`."""
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
