"""Policy inference server: HTTP front-end over the continuous-batching
engine, with Prometheus metrics and checkpoint hot-reload.

Follows the `RewardModelServer` pattern (trlx_tpu/serving.py): a
dependency-free `ThreadingHTTPServer`, JSON in/out, and an optional
`resilience.FaultInjector` for deterministic failure tests.

Endpoints:

- ``POST /generate`` — ``{"prompt": str}`` or ``{"prompt_ids": [...]}``
  plus optional ``max_new_tokens`` / ``deadline_s`` / ``adapter_id``
  (multi-tenant serving: which LoRA adapter decodes this request;
  omitted = the base policy). Answers
  ``{"id", "text", "token_ids", "finish_reason", "latency_s"}``.
  Backpressure: a full queue answers **503 with a Retry-After header**
  (the shared HTTP client retries those transparently); an expired
  deadline answers **504**.
- ``GET /healthz`` — liveness + slot/queue/reload snapshot (plus the
  resident adapter set on multi-tenant servers, which fleet routers use
  for adapter affinity).
- ``GET /metrics`` — Prometheus text: queue depth, slot occupancy,
  prefill/decode/request latency histograms, tokens/sec (per-adapter
  labeled series on multi-tenant servers), plus ``slo_burn_rate``
  gauges; latency buckets carry OpenMetrics exemplar trace ids when
  tracing is on.
- ``GET /debug/slo`` — the SLO burn-rate report: per-SLO fast/slow
  window burn rates, alert states, lifetime error budget.
- ``GET/POST /admin/adapters`` — multi-tenant control plane: GET lists
  resident + on-disk adapters and store stats; POST takes one of
  ``{"load": name}`` / ``{"evict": name}`` / ``{"reload": name}``.
- ``GET /debug/trace?last=N`` — the last N completed request traces
  (span trees, JSON), when ``inference.tracing`` is on.

Every POST /generate gets a ``request_id`` at ingress (``X-Request-Id``
header or freshly minted) that appears in the reply, every error body,
and the request log line. With tracing on, a router-supplied trace id
(payload ``trace_id`` or ``X-Trace-Id`` header) threads the replica's
spans into the caller's cross-process timeline via the reply's
``trace`` field.

Hot-reload: with `watch_dir` set, a daemon thread polls for the newest
**manifest-complete** checkpoint (PR 1's `resilience` validation — a
half-written checkpoint is never loaded) and atomically swaps the new
params into the engine; in-flight requests keep their KV cache and
continue on the new weights at their next decode step.
"""

import ast
import json
import os
import queue
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

import numpy as np

from trlx_tpu import resilience
from trlx_tpu.inference.adapters import AdapterError
from trlx_tpu.inference.scheduler import DrainingError, QueueFullError, Scheduler
from trlx_tpu.inference.sessions import (
    SessionBusyError,
    SessionLimitError,
    SessionResetError,
)
from trlx_tpu.inference.metrics import dedupe_metadata
from trlx_tpu.observability.compile_ledger import account as build_account
from trlx_tpu.observability.slo import SLOEngine
from trlx_tpu.observability.tracing import new_id
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def load_checkpoint_params(directory: str) -> Dict:
    """Restore the merged policy param tree from a trainer checkpoint
    (`TPUTrainer.save` layout: orbax `state/` holding flat tuple-keyed
    `train_params` + `frozen_params`). Orbax renders tuple keys as their
    string repr, so keys are literal_eval'd back and the two partitions
    unflattened into one nested tree. Optimizer state is ignored."""
    import orbax.checkpoint as ocp
    from flax import traverse_util

    raw = ocp.PyTreeCheckpointer().restore(os.path.join(directory, "state"))
    flat: Dict[tuple, Any] = {}
    for part in ("train_params", "frozen_params"):
        for k, v in (raw.get(part) or {}).items():
            key = ast.literal_eval(k) if isinstance(k, str) and k.startswith("(") else (k,)
            flat[tuple(key)] = v
    if not flat:
        raise ValueError(f"checkpoint at {directory} holds no policy params")
    return traverse_util.unflatten_dict(flat)


class CheckpointWatcher(threading.Thread):
    """Poll `watch_dir` for newer manifest-complete checkpoints and swap
    them into the engine. Truncated/mid-write checkpoints are invisible
    (no manifest), so a swap is always a complete state.

    With a `scheduler`, each swap is **drain-on-sync**: admission pauses,
    in-flight requests decode to completion (bounded by
    `drain_timeout_s`), the params swap, and admission resumes — no
    request ever mixes tokens from two checkpoints. `reloading` is True
    for the whole window, which flips the server's `/healthz` readiness
    off so a fleet router routes around the replica mid-swap."""

    def __init__(self, engine, watch_dir: Optional[str], interval_s: float = 5.0,
                 metrics=None, loader=load_checkpoint_params,
                 scheduler=None, drain_timeout_s: float = 30.0):
        super().__init__(name="trlx-tpu-ckpt-watcher", daemon=True)
        self.engine = engine
        self.watch_dir = watch_dir
        self.interval_s = interval_s
        self.metrics = metrics
        self.loader = loader
        self.scheduler = scheduler
        self.drain_timeout_s = float(drain_timeout_s)
        self.loaded_step: Optional[int] = None
        self.loaded_path: Optional[str] = None
        self._loaded_key = None  # (path, step, wall_time) of the live params
        self.reloads = 0
        self.reloading = False  # True while a swap is in flight (readiness off)
        self._reload_lock = threading.Lock()  # poll loop vs /admin/reload
        self._stop = threading.Event()

    def poll_once(self) -> bool:
        """One scan; returns True if a new checkpoint was swapped in."""
        if not self.watch_dir:
            return False  # admin-reload-only watcher (supervised replicas)
        path = resilience.find_latest_valid_checkpoint(self.watch_dir)
        if path is None:
            return False
        return self.load_path(path)

    def load_path(self, path: str) -> bool:
        """Drain-swap to the manifest-complete checkpoint at `path` (the
        core of `poll_once`, also driven directly by ``POST
        /admin/reload`` for supervisor-orchestrated rolling sync).
        Returns False when `path` is already live or fails to load."""
        manifest = resilience.read_manifest(path)
        if manifest is None:
            logger.warning(f"hot-reload: {path} has no complete manifest; refusing")
            return False
        step = int(manifest.get("step", -1))
        # key on (path, step, wall_time): a re-promotion into the SAME
        # directory name (atomic dir swap) is still picked up
        key = (path, step, manifest.get("wall_time"))
        with self._reload_lock:
            if key == self._loaded_key:
                return False
            self.reloading = True
            try:
                try:
                    params = self.loader(path)
                except Exception as e:
                    logger.warning(f"hot-reload: failed to load {path}: {e}")
                    return False
                if self.scheduler is not None:
                    if not self.scheduler.drain(self.drain_timeout_s):
                        logger.warning(
                            "hot-reload: drain timed out after "
                            f"{self.drain_timeout_s}s; swapping with requests in flight"
                        )
                self.engine.set_params(params)
            finally:
                if self.scheduler is not None:
                    self.scheduler.resume_admission()
                self.reloading = False
            self.loaded_step, self.loaded_path = step, path
            self._loaded_key = key
            self.reloads += 1
        if self.metrics is not None:
            self.metrics.inc("checkpoint_reloads_total")
            self.metrics.set_gauge("checkpoint_step", step)
        logger.info(f"hot-reload: serving checkpoint {path} (step {step})")
        return True

    # -- per-adapter hot-reload (multi-tenant serving) ------------------

    def poll_adapters(self) -> int:
        """Scan the adapter store for resident adapters whose on-disk
        checkpoint moved and hot-reload each — the per-tenant analogue of
        `poll_once`, draining only that adapter's slots instead of the
        whole replica. Returns the number of adapters swapped."""
        store = getattr(self.engine, "adapter_store", None)
        if store is None:
            return 0
        swapped = 0
        for name in store.changed():
            if self.reload_adapter(name):
                swapped += 1
        return swapped

    def reload_adapter(self, name: str) -> bool:
        """Drain-swap ONE adapter: admission for that tenant pauses, its
        in-flight requests decode to completion, the factors re-read into
        the same stack slot (fixed shape — no recompile) and its salted
        prefix blocks flush (cached K/V was computed under the old
        factors). Other tenants keep decoding throughout. Returns False
        when the on-disk version already matches."""
        store = self.engine.adapter_store
        if self.scheduler is not None:
            if not self.scheduler.drain_tenant(name, self.drain_timeout_s):
                logger.warning(
                    f"adapter hot-reload: drain of '{name}' timed out after "
                    f"{self.drain_timeout_s}s; deferring to the next poll"
                )
                self.scheduler.resume_tenant(name)
                return False
        try:
            try:
                reloaded = store.reload(name)
            except Exception as e:
                logger.warning(f"adapter hot-reload: failed for '{name}': {e}")
                return False
            if reloaded:
                self.engine.flush_adapter_prefixes(name)
                if self.metrics is not None:
                    self.metrics.inc(
                        "adapter_reload_events_total", labels={"adapter": str(name)}
                    )
                logger.info(f"adapter hot-reload: '{name}' serving new factors")
            return reloaded
        finally:
            if self.scheduler is not None:
                self.scheduler.resume_tenant(name)

    def run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.poll_once()
            except Exception:  # pragma: no cover - keep watching
                logger.exception("checkpoint watcher scan failed")
            try:
                self.poll_adapters()
            except Exception:  # pragma: no cover - keep watching
                logger.exception("adapter watcher scan failed")

    def stop(self) -> None:
        self._stop.set()


class InferenceServer:
    """Serve a `Scheduler` (and its engine) over HTTP."""

    def __init__(
        self,
        scheduler: Scheduler,
        tokenizer=None,
        host: str = "0.0.0.0",
        port: int = 8600,
        watch_dir: Optional[str] = None,
        reload_interval_s: float = 5.0,
        fault_injector: Optional["resilience.FaultInjector"] = None,
        checkpoint_loader=load_checkpoint_params,
        drain_on_term_s: float = 30.0,
        tracer=None,
        slos=None,
        slo_postmortem_dir: Optional[str] = None,
    ):
        self.scheduler = scheduler
        self.engine = scheduler.engine
        self.metrics = scheduler.metrics
        # SLO burn-rate engine over this replica's own registry: fed by
        # snapshot-diffing the scheduler's histograms/counters on every
        # /metrics scrape or /debug/slo poll (no hook in the request
        # path). Alert transitions land in the scheduler's flight
        # recorder when one exists.
        self.slo = SLOEngine(
            slos=slos,
            recorder=getattr(scheduler, "recorder", None),
            postmortem_dir=slo_postmortem_dir,
        )
        # one tracer per replica, shared with the scheduler: the server
        # opens traces at ingress, the scheduler closes them at finish
        self.tracer = tracer if tracer is not None else getattr(scheduler, "tracer", None)
        self.tokenizer = tokenizer
        if tokenizer is not None and getattr(scheduler, "detokenize", None) is None:
            # stop-sequence scanning and /chat text replies need id->text
            scheduler.detokenize = lambda ids: tokenizer.decode(list(ids))
        self.host = host
        self.port = port
        self.fault_injector = fault_injector
        self.drain_on_term_s = float(drain_on_term_s)
        # the watcher always exists (it is also the /admin/reload
        # drain-swap implementation); its poll thread only starts when a
        # watch_dir is configured — supervised replicas run without one
        # and reload exclusively on the supervisor's explicit paths
        self.watcher = CheckpointWatcher(
            self.engine, watch_dir or None, reload_interval_s, self.metrics,
            loader=checkpoint_loader, scheduler=self.scheduler,
        )
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._shutdown_done = False

    # ------------------------------------------------------------------

    @property
    def ready(self) -> bool:
        """Readiness (vs liveness): able to take traffic NOW — the engine
        holds weights, no checkpoint reload is draining/swapping, and the
        scheduler is not in reject-new drain mode."""
        if not self.engine.has_params:
            return False
        if self.watcher.reloading:
            return False
        if not self.scheduler.accepting:
            return False
        return True

    def _effective_checkpoint_step(self) -> Optional[int]:
        """The checkpoint step reported to routers. The stale-checkpoint
        fault overrides it so staleness handling is testable without
        producing real stale checkpoints."""
        injector = self.fault_injector
        override = getattr(injector, "stale_checkpoint_step", None) if injector else None
        if override is not None:
            return int(override)
        return self.watcher.loaded_step

    # ------------------------------------------------------------------

    def _encode_prompt(self, payload: Dict, truncate: bool = True) -> np.ndarray:
        if "prompt_ids" in payload:
            return np.asarray(payload["prompt_ids"], np.int32).reshape(-1)
        if "prompt" in payload:
            if self.tokenizer is None:
                raise ValueError("server has no tokenizer; send prompt_ids")
            ids = np.asarray(
                self.tokenizer.encode(str(payload["prompt"])), np.int32
            )
            # /chat never truncates: silently dropping leading tokens
            # would desync the turn from the session's retained history
            return ids[-self.engine.max_prompt_len :] if truncate else ids
        raise ValueError("payload needs 'prompt' or 'prompt_ids'")

    @staticmethod
    def _parse_stop(payload: Dict) -> Optional[List[str]]:
        stop = payload.get("stop")
        if stop is None:
            return None
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list):
            raise ValueError("'stop' must be a string or a list of strings")
        return [str(s) for s in stop]

    def _handle_generate(self, payload: Dict,
                         request_id: Optional[str] = None) -> Dict:
        ids = self._encode_prompt(payload)
        unsupported = set(payload) - {
            "prompt", "prompt_ids", "max_new_tokens", "deadline_s", "n",
            "adapter_id", "trace_id", "stop", "stream",
        }
        if unsupported:
            raise ValueError(
                f"unsupported request keys {sorted(unsupported)}; sampling "
                "knobs are fixed at server start (inference.gen_kwargs)"
            )
        n = int(payload.get("n", 1))
        adapter_id = payload.get("adapter_id")
        stop = self._parse_stop(payload)
        tracer = self.tracer
        traces = None
        if tracer is not None:
            # trace_id arrives from the router (payload or X-Trace-Id
            # header, merged by the handler); absent = locally originated
            trace_id = payload.get("trace_id")
            traces = [
                tracer.new_trace(trace_id=trace_id, request_id=request_id)
                for _ in range(n)
            ]
        if n == 1:
            reqs = [self.scheduler.submit(
                ids,
                max_new_tokens=payload.get("max_new_tokens"),
                deadline_s=payload.get("deadline_s"),
                adapter_id=adapter_id,
                request_id=request_id,
                trace=(traces[0] if traces else None),
                stop_sequences=stop,
            )]
        else:
            # GRPO-style fan-out: one prompt, n independent completions —
            # enqueued adjacently so a paged engine shares the prompt's
            # KV blocks across the whole group (one full prefill)
            reqs = self.scheduler.submit_n(
                ids, n,
                max_new_tokens=payload.get("max_new_tokens"),
                deadline_s=payload.get("deadline_s"),
                adapter_id=adapter_id,
                request_id=request_id,
                traces=traces,
                stop_sequences=stop,
            )
        for req in reqs:
            req.wait()
        # anchor the serialize span at the scheduler's finish timestamp
        # (the decode span's end) so the handler wake-up latency is
        # attributed to the reply handoff instead of an untraced gap
        t_ser0 = 0.0
        if traces is not None:
            t_ser0 = min(
                (r.finish_time for r in reqs if r.finish_time is not None),
                default=time.monotonic(),
            )
        step = self._effective_checkpoint_step()

        def seq(req):
            out = {
                "id": req.id,
                "token_ids": req.token_ids,
                "token_logprobs": req.token_logprobs,
                "finish_reason": req.finish_reason,
                "latency_s": req.latency_s,
                "ttft_s": req.ttft_s,
                # which weights produced this rollout — routers enforce
                # the staleness bound per-reply, not just per-probe
                "checkpoint_step": step,
            }
            if request_id is not None:
                out["request_id"] = request_id
            if req.finish_reason not in ("eos", "length", "stop"):
                # which pipeline stage the request died in — the 504
                # body surfaces this (satellite: stage attribution)
                out["stage"] = req.stage
            if self.tokenizer is not None:
                out["text"] = self.tokenizer.decode(req.token_ids)
            return out

        if n == 1:
            out = seq(reqs[0])
            if traces is not None:
                # reply-build time (incl. detokenization); the final
                # json.dumps + socket write is sub-ms and not covered
                traces[0].add("serialize", t_ser0, time.monotonic())
                out["trace_id"] = traces[0].trace_id
                out["trace"] = traces[0].to_dict()["spans"]
            return out
        reasons = [r.finish_reason for r in reqs]
        if "shutdown" in reasons:
            worst = "shutdown"
        elif "deadline" in reasons:
            worst = "deadline"
        else:
            worst = reasons[0]
        result = {
            "n": n,
            "sequences": [seq(r) for r in reqs],
            "finish_reason": worst,
            "checkpoint_step": step,
        }
        if request_id is not None:
            result["request_id"] = request_id
        if worst not in ("eos", "length", "stop"):
            bad = next(r for r in reqs if r.finish_reason == worst)
            result["stage"] = bad.stage
        if traces is not None:
            t_ser1 = time.monotonic()
            merged = []
            for tr in traces:
                tr.add("serialize", t_ser0, t_ser1)
                merged.extend(tr.to_dict()["spans"])
            result["trace_id"] = traces[0].trace_id
            result["trace"] = merged
        return result

    # ------------------------------------------------------------------
    # Sessions (/chat) and token streaming (SSE)
    # ------------------------------------------------------------------

    def _submit_chat(self, payload: Dict, request_id: Optional[str] = None,
                     stream_q=None):
        """Resolve the session, build the full-conversation prompt, and
        submit the turn. Returns ``(req, sess, trace)``. On any submit
        failure the session's busy flag is cleared so the turn can be
        retried."""
        store = getattr(self.engine, "session_store", None)
        if store is None:
            raise ValueError(
                "sessions are off (start the server with inference.sessions)"
            )
        unsupported = set(payload) - {
            "session_id", "prompt", "prompt_ids", "max_new_tokens",
            "deadline_s", "adapter_id", "stream", "stop", "trace_id",
        }
        if unsupported:
            raise ValueError(
                f"unsupported chat request keys {sorted(unsupported)}; "
                "sampling knobs are fixed at server start (inference.gen_kwargs)"
            )
        turn_ids = self._encode_prompt(payload, truncate=False)
        adapter_id = payload.get("adapter_id")
        session_id = payload.get("session_id")
        if session_id is None:
            # new sessions only via an OMITTED id: treating an unknown id
            # as "create" would silently misread delta tokens as a full
            # prompt after an eviction the client didn't see
            sess = store.create(adapter_id)
        else:
            sess = store.begin_turn(str(session_id), adapter_id)
        try:
            full_ids = (
                np.concatenate([sess.tokens, turn_ids])
                if sess.tokens.size else turn_ids
            )
            trace = None
            if self.tracer is not None:
                trace = self.tracer.new_trace(
                    trace_id=payload.get("trace_id"), request_id=request_id
                )
            req = self.scheduler.submit(
                full_ids,
                max_new_tokens=payload.get("max_new_tokens"),
                deadline_s=payload.get("deadline_s"),
                adapter_id=adapter_id,
                request_id=request_id,
                trace=trace,
                stop_sequences=self._parse_stop(payload),
                session=sess,
                stream=stream_q,
            )
        except BaseException:
            store.end_turn(sess)
            raise
        return req, sess, trace

    def _chat_reply(self, req, sess, trace, request_id: Optional[str]) -> Dict:
        out = {
            "id": req.id,
            "session_id": sess.id,
            "turn": sess.turns,
            "token_ids": req.token_ids,
            "token_logprobs": req.token_logprobs,
            "finish_reason": req.finish_reason,
            "latency_s": req.latency_s,
            "ttft_s": req.ttft_s,
            "checkpoint_step": self._effective_checkpoint_step(),
            # per-turn retention stats: a follow-up turn asserts
            # retained_hit and that prefill_tokens is only its delta
            "retained_blocks": sess.last_reused_blocks,
            "retained_hit": sess.last_reused_blocks > 0,
            "prefill_tokens": sess.last_prefill_tokens,
            "session_tokens": int(sess.tokens.size),
        }
        if request_id is not None:
            out["request_id"] = request_id
        if req.finish_reason not in ("eos", "length", "stop"):
            out["stage"] = req.stage
        if self.tokenizer is not None:
            out["text"] = self.tokenizer.decode(req.token_ids)
        if trace is not None:
            t0 = req.finish_time if req.finish_time is not None else time.monotonic()
            trace.add("serialize", t0, time.monotonic())
            out["trace_id"] = trace.trace_id
            out["trace"] = trace.to_dict()["spans"]
        return out

    def _handle_chat(self, payload: Dict,
                     request_id: Optional[str] = None) -> Dict:
        req, sess, trace = self._submit_chat(payload, request_id)
        req.wait()
        return self._chat_reply(req, sess, trace, request_id)

    def _handle_stream(self, handler, path: str, payload: Dict,
                       request_id: Optional[str] = None) -> None:
        """Server-sent-events token streaming for /generate and /chat.

        Each delta is one ``data: {"token_ids": [...]}`` event; the last
        event carries the full non-streaming reply body plus
        ``"event": "done"`` — concatenating the deltas' token_ids is
        bitwise identical to the final body's token_ids. The connection
        closes after the done event (HTTP/1.0 framing: close delimits
        the body, no chunked encoding needed). Submission errors raise
        BEFORE any header is written, so they surface as ordinary JSON
        error replies."""
        q: "queue.Queue" = queue.Queue()
        sess = None
        if path == "/chat":
            req, sess, trace = self._submit_chat(payload, request_id, stream_q=q)
        else:
            ids = self._encode_prompt(payload)
            unsupported = set(payload) - {
                "prompt", "prompt_ids", "max_new_tokens", "deadline_s", "n",
                "adapter_id", "trace_id", "stop", "stream",
            }
            if unsupported:
                raise ValueError(
                    f"unsupported request keys {sorted(unsupported)}; sampling "
                    "knobs are fixed at server start (inference.gen_kwargs)"
                )
            if int(payload.get("n", 1)) != 1:
                raise ValueError("streaming supports n=1 only")
            trace = None
            if self.tracer is not None:
                trace = self.tracer.new_trace(
                    trace_id=payload.get("trace_id"), request_id=request_id
                )
            req = self.scheduler.submit(
                ids,
                max_new_tokens=payload.get("max_new_tokens"),
                deadline_s=payload.get("deadline_s"),
                adapter_id=payload.get("adapter_id"),
                request_id=request_id,
                trace=trace,
                stop_sequences=self._parse_stop(payload),
                stream=q,
            )
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.end_headers()
        broken = False
        while True:
            item = q.get()
            if item is None:
                break
            if broken:
                continue  # client went away: keep draining to the sentinel
            try:
                handler.wfile.write(b"data: " + json.dumps(item).encode() + b"\n\n")
                handler.wfile.flush()
            except OSError:
                broken = True
        req.wait()
        if sess is not None:
            final = self._chat_reply(req, sess, trace, request_id)
        else:
            final = {
                "id": req.id,
                "token_ids": req.token_ids,
                "token_logprobs": req.token_logprobs,
                "finish_reason": req.finish_reason,
                "latency_s": req.latency_s,
                "ttft_s": req.ttft_s,
                "checkpoint_step": self._effective_checkpoint_step(),
            }
            if request_id is not None:
                final["request_id"] = request_id
            if req.finish_reason not in ("eos", "length", "stop"):
                final["stage"] = req.stage
            if self.tokenizer is not None:
                final["text"] = self.tokenizer.decode(req.token_ids)
            if trace is not None:
                t0 = req.finish_time if req.finish_time is not None else time.monotonic()
                trace.add("serialize", t0, time.monotonic())
                final["trace_id"] = trace.trace_id
                final["trace"] = trace.to_dict()["spans"]
        final["event"] = "done"
        if not broken:
            try:
                handler.wfile.write(b"data: " + json.dumps(final).encode() + b"\n\n")
                handler.wfile.flush()
            except OSError:
                pass
        handler.close_connection = True

    # ------------------------------------------------------------------
    # Admin surface (fleet supervisor orchestration)
    # ------------------------------------------------------------------

    def _handle_admin(self, path: str, payload: Dict) -> Dict:
        """``POST /admin/drain|undrain|reload``: the replica-side half of
        a supervisor-orchestrated rolling weight sync. Drain flips the
        scheduler into reject-new/finish-inflight mode (readiness goes
        off so routers stop dispatching); reload performs the watcher's
        drain-swap on an explicit checkpoint path (or a watch_dir scan
        when no path is given); undrain reopens admission."""
        if path == "/admin/drain":
            self.scheduler.reject_new()
            wait_s = payload.get("wait_s")
            idle = self.scheduler.wait_idle(float(wait_s)) if wait_s else None
            return {"draining": True, "idle": idle}
        if path == "/admin/undrain":
            self.scheduler.accept_new()
            return {"draining": False}
        if path == "/admin/reload":
            ckpt = payload.get("path")
            if ckpt is not None:
                reloaded = self.watcher.load_path(str(ckpt))
            elif self.watcher.watch_dir:
                reloaded = self.watcher.poll_once()
            else:
                raise ValueError("reload needs 'path' (server has no watch_dir)")
            return {
                "reloaded": bool(reloaded),
                "checkpoint_step": self._effective_checkpoint_step(),
                "reloads": self.watcher.reloads,
            }
        if path == "/admin/adapters":
            store = self._adapter_store(required=True)
            actions = [k for k in ("load", "evict", "reload") if k in payload]
            if len(actions) != 1:
                raise ValueError(
                    "POST /admin/adapters takes exactly one of "
                    '{"load": name} / {"evict": name} / {"reload": name}'
                )
            action, name = actions[0], str(payload[actions[0]])
            out: Dict[str, Any] = {"action": action, "adapter": name}
            if action == "load":
                out["slot"] = store.load(name)
            elif action == "evict":
                store.evict(name)
                self.engine.flush_adapter_prefixes(name)
            else:  # reload
                out["reloaded"] = self.watcher.reload_adapter(name)
            out.update(self._adapter_snapshot())
            return out
        raise ValueError(f"unknown admin endpoint {path}")

    def _adapter_store(self, required: bool = False):
        store = getattr(self.engine, "adapter_store", None)
        if store is None and required:
            raise ValueError(
                "server is not multi-tenant (start with inference.multi_tenant "
                "and an adapter_dir)"
            )
        return store

    def _adapter_snapshot(self) -> Dict:
        store = self._adapter_store(required=True)
        return {
            "resident": store.resident(),
            "available": store.scan(),
            "stats": store.stats(),
        }

    def _make_handler(self):
        server = self  # live reference: tests can swap fault_injector mid-run

        class Handler(BaseHTTPRequestHandler):
            def _reply(self, code: int, body: bytes, content_type: str = "application/json",
                       headers: Optional[Dict[str, str]] = None):
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _reply_json(self, code: int, obj: Dict, headers=None):
                self._reply(code, json.dumps(obj).encode(), headers=headers)

            def do_POST(self):  # noqa: N802
                path = self.path.rstrip("/")
                if path.startswith("/admin/"):
                    # the control plane is exempt from injected data-path
                    # faults: a supervisor must be able to drain/reload a
                    # replica whose request path is misbehaving
                    try:
                        length = int(self.headers.get("Content-Length", 0))
                        payload = json.loads(self.rfile.read(length) or b"{}")
                        self._reply_json(200, server._handle_admin(path, payload))
                    except (ValueError, TypeError, AdapterError) as e:
                        self._reply_json(400, {"error": str(e)})
                    except Exception as e:  # pragma: no cover - defensive
                        self._reply_json(500, {"error": repr(e)})
                    return
                if path not in ("", "/generate", "/chat"):
                    self.send_error(404)
                    return
                # every request gets an id at ingress (client-supplied or
                # fresh) — echoed in the reply, every error body, and the
                # request log line, tracing on or off
                rid = self.headers.get("X-Request-Id") or new_id()
                self._rid = rid
                # correlate this request's log lines (JSON log format
                # emits these as trace_id/request_id fields)
                logging.set_trace_context(request_id=rid)
                injector = server.fault_injector
                slow_through = False
                if injector is not None and injector.should_fail():
                    mode = injector.mode
                    if mode == "mixed":
                        mode = "drop" if injector.injected % 2 else "http_500"
                    if mode == "drop":
                        self.close_connection = True
                        try:
                            self.connection.close()
                        except OSError:
                            pass
                        return
                    if mode == "hang":
                        # unresponsive replica: hold the socket without
                        # answering, then drop it — clients only escape
                        # via their own timeout / hedge
                        time.sleep(injector.hang_s)
                        self.close_connection = True
                        try:
                            self.connection.close()
                        except OSError:
                            pass
                        return
                    if mode == "slow":
                        # slow decode: delayed but CORRECT answer —
                        # exercises hedging, not failover
                        time.sleep(injector.slow_s)
                        slow_through = True
                    if not slow_through:
                        self._reply_json(503, {
                            "error": "injected transient failure",
                            "request_id": rid,
                        })
                        return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(length) or b"{}")
                    if "trace_id" not in payload:
                        hdr_tid = self.headers.get("X-Trace-Id")
                        if hdr_tid:
                            payload["trace_id"] = hdr_tid
                    if payload.get("trace_id"):
                        logging.set_trace_context(
                            trace_id=payload["trace_id"], request_id=rid
                        )
                    if payload.get("stream"):
                        # SSE path writes its own headers + events; any
                        # submission error raises before headers go out
                        # and falls into the handlers below
                        server._handle_stream(
                            self, path or "/generate", payload, request_id=rid
                        )
                        return
                    if path == "/chat":
                        result = server._handle_chat(payload, request_id=rid)
                    else:
                        result = server._handle_generate(payload, request_id=rid)
                except SessionResetError as e:
                    # the retained state is gone (weights swap, TTL, or
                    # unknown id): the client re-creates the session by
                    # resending its full history — NEVER served stale KV
                    self._reply_json(409, {
                        "error": str(e), "session_reset": True,
                        "session_id": e.session_id, "reason": e.reason,
                        "request_id": rid,
                    })
                    return
                except SessionBusyError as e:
                    self._reply_json(409, {
                        "error": str(e), "session_busy": True,
                        "session_id": e.session_id, "request_id": rid,
                    })
                    return
                except SessionLimitError as e:
                    self._reply_json(
                        503,
                        {"error": str(e), "request_id": rid},
                        headers={"Retry-After": "1"},
                    )
                    return
                except QueueFullError as e:
                    self._reply_json(
                        503,
                        {"error": "queue full, retry later", "queue_depth": e.depth,
                         "request_id": rid},
                        headers={"Retry-After": str(max(1, int(e.retry_after)))},
                    )
                    return
                except DrainingError as e:
                    # reject-new drain mode (graceful shutdown / admin
                    # drain): transient — routers fail over elsewhere
                    self._reply_json(
                        503,
                        {"error": "server draining, retry elsewhere",
                         "request_id": rid},
                        headers={"Retry-After": str(max(1, int(e.retry_after)))},
                    )
                    return
                except (ValueError, TypeError) as e:
                    self._reply_json(400, {"error": str(e), "request_id": rid})
                    return
                except Exception as e:  # surface engine errors to the client
                    self._reply_json(500, {"error": repr(e), "request_id": rid})
                    return
                if result["finish_reason"] == "deadline":
                    # result carries "stage": which pipeline stage the
                    # request died in (queued / admitted / prefill / decode)
                    self._reply_json(504, {"error": "deadline exceeded", **result})
                elif result["finish_reason"] == "shutdown":
                    self._reply_json(503, {
                        "error": "server shutting down", "request_id": rid,
                    })
                else:
                    self._reply_json(200, result)

            def do_GET(self):  # noqa: N802
                path = self.path.rstrip("/")
                if path.split("?")[0] == "/debug/trace":
                    if server.tracer is None:
                        self._reply_json(404, {
                            "error": "tracing is off (set inference.tracing)",
                        })
                        return
                    query = urllib.parse.parse_qs(
                        urllib.parse.urlparse(self.path).query
                    )
                    try:
                        last = int(query.get("last", ["32"])[0])
                    except ValueError:
                        last = 32
                    self._reply_json(200, {
                        "traces": server.tracer.recent(last),
                    })
                    return
                if path == "/admin/adapters":
                    try:
                        self._reply_json(200, server._adapter_snapshot())
                    except (ValueError, AdapterError) as e:
                        self._reply_json(400, {"error": str(e)})
                    return
                if path == "/debug/slo":
                    server.slo.ingest_registry(server.metrics)
                    self._reply_json(200, server.slo.evaluate())
                    return
                if path == "/metrics":
                    server.slo.ingest_registry(server.metrics)
                    ledger = getattr(server.engine, "compile_ledger", None)
                    hbm = getattr(server.engine, "hbm", None)
                    text = dedupe_metadata(
                        server.metrics.render()
                        + server.slo.render_prometheus(ns="trlx_tpu_inference")
                        + build_account().render_prometheus()
                        + (ledger.render_prometheus() if ledger is not None else "")
                        + (hbm.render_prometheus() if hbm is not None else "")
                    )
                    self._reply(
                        200, text.encode(),
                        content_type="text/plain; version=0.0.4",
                    )
                    return
                if path in ("", "/healthz"):
                    injector = server.fault_injector
                    if injector is not None and getattr(injector, "healthz_hang_s", 0):
                        # wedged replica: the process is up but its
                        # health endpoint never answers — supervisors
                        # must detect this via probe timeouts and
                        # kill/respawn, not wait forever
                        time.sleep(injector.healthz_hang_s)
                        self.close_connection = True
                        try:
                            self.connection.close()
                        except OSError:
                            pass
                        return
                    watcher = server.watcher
                    ready = server.ready
                    kv = (
                        server.engine.kv_stats()
                        if hasattr(server.engine, "kv_stats") else {}
                    )
                    store = server._adapter_store()
                    self._reply_json(200, {
                        # liveness ("process is up") vs readiness ("can
                        # take traffic now") — a reload in flight is live
                        # but not ready; status keeps its legacy meaning
                        "status": "ok" if ready else "degraded",
                        "live": True,
                        "ready": ready,
                        "reloading": bool(watcher.reloading),
                        "draining": not server.scheduler.accepting,
                        "slots_total": server.engine.num_slots,
                        "slots_active": server.engine.active_slots,
                        "queue_depth": int(server.metrics.get("queue_depth")),
                        "param_version": server.engine.param_version,
                        "checkpoint_step": server._effective_checkpoint_step(),
                        "reloads": watcher.reloads,
                        # the decode read path the engine resolved to:
                        # "pallas" | "interpret" | "xla"
                        "decode_kernel": getattr(
                            server.engine, "decode_path", None),
                        # paged-pool occupancy (empty dict when paging is
                        # off) — supervisors surface these per-replica
                        **({"kv": kv} if kv else {}),
                        # session-store occupancy (sessions on only)
                        **(
                            {"sessions": server.engine.session_store.stats()}
                            if getattr(server.engine, "session_store", None)
                            is not None else {}
                        ),
                        # resident adapters (multi-tenant only) — fleet
                        # routers prefer replicas already holding the
                        # request's adapter (no load on the hot path)
                        **(
                            {"adapters": {
                                "resident": store.resident(),
                                "capacity": store.capacity,
                            }}
                            if store is not None else {}
                        ),
                        # compile/HBM forensics (tracing on only) — per-fn
                        # recompile counts and device-memory watermarks so
                        # supervisors can spot retrace storms and memory
                        # drift without scraping Prometheus
                        **(
                            {"compile": server.engine.compile_ledger.snapshot()}
                            if getattr(server.engine, "compile_ledger", None)
                            is not None else {}
                        ),
                        **(
                            {"hbm": server.engine.hbm.snapshot()}
                            if getattr(server.engine, "hbm", None)
                            is not None else {}
                        ),
                    })
                    return
                self.send_error(404)

            def log_message(self, fmt, *args):
                msg = fmt % args
                rid = getattr(self, "_rid", None)
                if rid is not None:
                    msg = f"{msg} request_id={rid}"
                logger.debug("inference-server: " + msg)

        return Handler

    # ------------------------------------------------------------------

    def _bind(self) -> None:
        self._httpd = ThreadingHTTPServer((self.host, self.port), self._make_handler())
        self.port = self._httpd.server_address[1]  # resolve port 0
        self._shutdown_done = False
        self.scheduler.start()
        store = self._adapter_store()
        if self.watcher.watch_dir or (store is not None and store.adapter_dir):
            # the poll thread also drives per-adapter hot-reload, so a
            # multi-tenant server needs it even without a trunk watch_dir
            self.watcher.start()

    @property
    def url(self) -> str:
        host = "127.0.0.1" if self.host == "0.0.0.0" else self.host
        return f"http://{host}:{self.port}"

    def start_background(self) -> str:
        """Start serving on a daemon thread; returns the base URL."""
        self._bind()
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        logger.info(f"Inference server listening on {self.url}")
        return self.url

    def serve(self) -> None:
        """Blocking serve (the standalone policy-server process).

        SIGTERM/SIGINT trigger a graceful drain-then-exit: the scheduler
        flips to reject-new (new requests answer 503 + Retry-After, so a
        fleet router fails them over), in-flight decodes run to
        completion and their replies go out over the still-open listener,
        and only then does the process exit — a preempted replica never
        turns completed work into client connection resets."""
        import signal as _signal

        self._bind()
        logger.info(f"Inference server listening on :{self.port}")

        def _graceful(signum):
            logger.warning(
                f"signal {signum}: draining scheduler (reject-new) before exit"
            )
            self.scheduler.reject_new()
            self.scheduler.wait_idle(self.drain_on_term_s)
            self._httpd.shutdown()  # unblocks serve_forever below

        def _on_term(signum, frame):
            threading.Thread(
                target=_graceful, args=(signum,),
                name="trlx-tpu-server-drain", daemon=True,
            ).start()

        previous = {}
        try:  # signal handlers only install from the main thread
            for sig in (_signal.SIGTERM, _signal.SIGINT):
                previous[sig] = _signal.signal(sig, _on_term)
        except ValueError:
            previous = {}
        try:
            self._httpd.serve_forever()
        finally:
            for sig, handler in previous.items():
                _signal.signal(sig, handler)
            self.shutdown(drain_s=self.drain_on_term_s)

    def shutdown(self, drain_s: float = 0.0) -> None:
        """Stop serving. With `drain_s > 0` the scheduler is drained
        FIRST (reject-new, finish-inflight) so in-flight requests
        complete and reply before the listener closes — the ordering a
        graceful SIGTERM needs. `drain_s == 0` keeps the original abrupt
        semantics (in-flight requests finish as "shutdown"), which is
        what replica-kill fault injection wants."""
        if self._shutdown_done:
            return
        self._shutdown_done = True
        self.watcher.stop()
        if drain_s > 0:
            self.scheduler.reject_new()
            if not self.scheduler.wait_idle(drain_s):
                logger.warning(
                    f"shutdown: drain timed out after {drain_s}s; "
                    "remaining requests will finish as 'shutdown'"
                )
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self.scheduler.stop()
