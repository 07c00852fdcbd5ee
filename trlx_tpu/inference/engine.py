"""Continuous-batching inference engine over a slot-based KV-cache pool.

The training sampler (`trlx_tpu/ops/sampling.py`) is one compiled
`lax.while_loop`: the whole batch prefills together and the program runs
until EVERY row finishes — fine for rollouts, fatal for serving, where a
40-token reply would wait on a 400-token neighbor. This engine refactors
that monolith into the two Orca/vLLM-style primitives:

- ``prefill``: jitted per (rows, prompt-width) bucket — run the model's
  cached prefill over a left-padded prompt batch against a full-length
  cache, returning the per-row KV cache rows + last-position logits;
- ``decode_step``: jitted once — sample one token for every ACTIVE slot
  of the pool and advance each slot's own cache column
  (`TransformerLM.decode_step` on a `row_index` cache; rows sit at
  different depths).

Slots are freed the step their request finishes (eos / length budget /
cancel) and newly prefilled requests are scattered into free slots
mid-flight, so the decode batch stays full under mixed lengths. `step`
keeps one decode program in flight: it dispatches the next step before it
waits for the tokens of the one dispatched a call earlier, so whatever the
host does between two calls runs while the device works (a freed slot is
refilled one step later for it). Prompt
widths are bucketed to multiples of 32 and prefill rows to powers of two
(the `_bucket_prompts` idiom from base_trainer.py) to bound
recompilation.

Numerics: masked cache columns carry a -1e9 attention bias whose exp
underflows to exactly 0.0 in f32, so a row's logits depend only on its
own valid columns — greedy decode through the slot pool is bit-identical
to a fresh-batch `trainer.generate` run regardless of pool composition,
padding width, or which slot the request lands in (pinned by
tests/test_inference_engine.py).

Thread safety: all device-touching methods are expected to be called
from ONE driver thread (the scheduler loop); `set_params` may be called
from any thread (checkpoint hot-reload) and swaps atomically under a
lock read at each dispatch.
"""

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.inference.adapters import adapter_salt
from trlx_tpu.inference.paging import BlockPool, KVPoolExhaustedError, prefix_keys
from trlx_tpu.models.policy import refuse_over_looped_stack
from trlx_tpu.models.transformer import (
    exit_early_from_state,
    init_kv_cache,
    init_paged_kv_arena,
    moe_stats_from_state,
    prefill_fuses,
    slot_state_of,
)
from trlx_tpu.observability import tracing
from trlx_tpu.ops.quant import dequantize_tree
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    process_logits,
    sampled_token_logprob,
    select_token,
)
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _refuse_over_latent_cache(model_cfg, what: str) -> None:
    """What no test holds over a latent cache (the layers of a kind
    `cfg.latent_of` knows: planes a token a layer, read absorbed, banded or
    by an index's choice) is refused by name."""
    if getattr(model_cfg, "has_latent_layers", False):
        kinds = " / ".join(k for k in dict.fromkeys(model_cfg.layer_types) if model_cfg.latent_of(k) is not None)
        raise NotImplementedError(f"{what} over a latent cache ({kinds} layers) is not supported")


def _refuse_over_slot_state(model_cfg, what: str) -> None:
    """A layer that keeps a state a slot (`LayerKeeps.slot`: a convolution's
    last inputs, a recurrent matrix) has nothing a block table can share and
    nothing a mask bit can roll back, whatever planes a token it keeps beside
    it: what would need either is refused by name."""
    if getattr(model_cfg, "has_slot_state", False):
        raise NotImplementedError(
            f"{what} over slot state ({slot_state_of(model_cfg)}, which no block table shares and no mask "
            "bit rolls back) is not supported")


def _refuse_over_attention_kinds(model_cfg, what: str) -> None:
    _refuse_over_slot_state(model_cfg, what)
    _refuse_over_latent_cache(model_cfg, what)
    kinds = getattr(model_cfg, "attention_kinds", ())
    if kinds:
        raise NotImplementedError(
            f"{what} over attention layers of several kinds ({', '.join(kinds)}) is not supported")


def _gather_rows(stack, idx):
    """Per-row adapter factors from the store's stacked tree: every leaf
    [n_slots, ...] -> [rows, ...] gathered by each row's adapter index.
    Shapes the `lora_rows` collection `lora_dense` reads (one factor pair
    per batch row), traced inside the prefill/decode programs so a
    heterogeneous batch is one program."""
    return jax.tree_util.tree_map(lambda s: s[idx], stack)


_KV_DTYPES = {
    "auto": None,
    "f32": jnp.float32,
    "float32": jnp.float32,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
}


class _InFlight(NamedTuple):
    """A decode program that was dispatched and whose outputs the host has
    not fetched. `rows[slot]` says whether the slot's output still belongs
    to the request that held the slot at dispatch: release, reclaim and
    insert clear it (in place), and the fetch hands out nothing for a row
    that lost it."""

    out: tuple  # device arrays: token, logprob, emitted, finished[, moe stats]
    rows: np.ndarray  # [num_slots] bool
    seq: int  # the engine's count of decode dispatches when this one was queued


class InferenceEngine:
    """Generation over a fixed pool of `num_slots` KV-cache slots.

    :param model: a flax module exposing `decode_step` (prefill on a
        scalar-`index` cache, per-slot decode and paged insert on a
        `row_index` one) — `CausalLMWithValueHead` and friends.
    :param gen_cfg: engine-wide sampling knobs. Per-request overrides are
        limited to `max_new_tokens` (≤ the engine's, which sizes the
        cache); everything else is fixed at engine build time so the
        decode program compiles once.
    """

    def __init__(
        self,
        model,
        model_cfg,
        params,
        gen_cfg: GenerationConfig,
        num_slots: int = 8,
        max_prompt_len: int = 256,
        max_prefill_batch: int = 8,
        prompt_bucket: int = 32,
        seed: int = 0,
        kv_paging: bool = False,
        kv_block_size: int = 32,
        kv_pool_blocks: int = 0,
        kv_cache_dtype: str = "auto",
        prefix_cache: bool = False,
        prefix_cache_capacity: int = 0,
        multi_tenant: bool = False,
        adapter_store=None,
        decode_kernel: str = "auto",
        compile_ledger=None,
        hbm_ledger=None,
    ):
        # observability context objects (inference.tracing): the compile
        # ledger wraps every engine jit below (decode budget 1 — the "no
        # recompile" invariant, finally enforced); the HBM ledger gets
        # the KV arena's analytic bytes and is sampled at dispatch
        # boundaries. Both None by default: off = plain jax.jit, bitwise
        # identical programs.
        self.compile_ledger = compile_ledger
        self.hbm = hbm_ledger
        self._step_n = 0
        # the decode program dispatched and not yet fetched (`_InFlight`),
        # or None; `_live` is the host's own book of the slots whose request
        # still decodes (insert sets a slot, a fetched `finished` and
        # release / reclaim clear it)
        self._ahead: Optional[_InFlight] = None
        self._live = np.zeros((int(num_slots),), bool)
        self._dispatches = 0  # decode programs queued: a step's `seq`, dispatch to fetch
        self._steps_ahead = 0
        self._outputs_masked = 0
        if getattr(model_cfg, "is_seq2seq", False):
            raise NotImplementedError(
                "the continuous-batching engine serves causal LMs only"
            )
        if multi_tenant:
            if adapter_store is None:
                raise ValueError("multi_tenant serving needs an AdapterStore")
            if getattr(model_cfg, "lora_rank", 0) <= 0:
                raise ValueError(
                    "multi_tenant serving needs a LoRA-enabled policy "
                    "(cfg.lora_rank > 0)"
                )
        if getattr(model_cfg, "prompt_tokens", 0) > 0 or getattr(model_cfg, "prefix_tokens", 0) > 0:
            raise NotImplementedError(
                "slot-pool decode under prompt/prefix tuning is unsupported"
            )
        # untested over sliding layers, so refused by name: a cached or
        # retained prefix resumes a prefill behind blocks it did not write
        for on, what in ((prefix_cache, "prefix_cache"),
                         (not kv_paging, "the dense slot pool (kv_paging=False)")):
            if on:
                _refuse_over_attention_kinds(model_cfg, what)
                refuse_over_looped_stack(model_cfg, what)
        if _KV_DTYPES.get(kv_cache_dtype) == jnp.int8:
            _refuse_over_slot_state(model_cfg, "an int8 arena (kv_cache_dtype='int8')")
            _refuse_over_latent_cache(model_cfg, "an int8 arena (kv_cache_dtype='int8')")
        if gen_cfg.num_beams > 1:
            raise NotImplementedError("beam search is not servable slot-wise")
        if gen_cfg.repetition_penalty != 1.0:
            raise NotImplementedError(
                "repetition_penalty requires per-slot seen-token tracking; "
                "not supported by the inference engine yet"
            )
        self.model = model
        self.model_cfg = model_cfg
        self.gen_cfg = gen_cfg
        self.num_slots = int(num_slots)
        self.prompt_bucket = int(prompt_bucket)
        self.max_prompt_len = _round_up(int(max_prompt_len), self.prompt_bucket)
        self.max_prefill_batch = int(max_prefill_batch)
        self.max_len = self.max_prompt_len + gen_cfg.max_new_tokens
        self.kv_paging = bool(kv_paging)
        self.kv_block_size = int(kv_block_size)
        self.prefix_cache = bool(prefix_cache) and self.kv_paging
        self.multi_tenant = bool(multi_tenant)
        self.adapter_store = adapter_store if self.multi_tenant else None
        if self.adapter_store is not None:
            # store-internal LRU eviction can later re-load an adapter
            # whose checkpoint moved while it was out — the store calls
            # back so that adapter's salted prefix blocks flush on load
            self.adapter_store.flush_prefixes = self.flush_adapter_prefixes
        # slot -> adapter name for requests in flight (store ref held)
        self._slot_adapter: Dict[int, Optional[str]] = {}
        if kv_cache_dtype not in _KV_DTYPES:
            raise ValueError(
                f"kv_cache_dtype {kv_cache_dtype!r} not in {sorted(_KV_DTYPES)}"
            )
        self.kv_cache_dtype = _KV_DTYPES[kv_cache_dtype] or getattr(
            model_cfg, "dtype", jnp.float32
        )
        if self.kv_cache_dtype == jnp.int8 and not self.kv_paging:
            raise NotImplementedError("int8 KV cache requires kv_paging")
        if prefix_cache and not kv_paging:
            raise ValueError("prefix_cache requires kv_paging")
        self._cache_len = self.max_len
        if self.kv_paging:
            if self.kv_block_size < 1:
                raise ValueError("kv_block_size must be >= 1")
            # every slot's logical view spans n_tbl blocks; cache_len
            # rounds up to a whole number of blocks
            self._cache_len = _round_up(self._cache_len, self.kv_block_size)
            self._n_tbl = self._cache_len // self.kv_block_size
            # auto-size to fixed-pool capacity parity: every slot can hold
            # a worst-case request (plus the reserved zero block)
            self._n_blocks = int(kv_pool_blocks) or (
                self.num_slots * self._n_tbl + 1
            )
            self._block_pool = BlockPool(
                self._n_blocks, self.kv_block_size,
                prefix_cache=self.prefix_cache,
                idle_capacity=int(prefix_cache_capacity),
            )
            self._slot_blocks: Dict[int, List[int]] = {}
            # columns each slot's request holds in the arena (prompt +
            # emitted), kept on the host for `kv_live_entry_share`
            self._slot_cols = np.zeros((self.num_slots,), np.int64)
            # BlockPool is plain Python touched by the driver thread
            # (insert/reclaim) AND the hot-reload thread (flush_cached).
            # Re-entrant: the session store shares this lock and the
            # insert path calls back into it while already holding it
            # (evict-under-pressure, retained-block acquisition).
            self._kv_lock = threading.RLock()
        else:
            self._block_pool = None
        # multi-turn chat: retained-block registry (enable_sessions)
        self.session_store = None

        # scheduler-owned trace buffer: while a traced batch inserts, the
        # scheduler sets this to a list and the insert path appends
        # (name, t0, t1, attrs) tuples (adapter loads, block allocation,
        # per-bucket prefill dispatches). None = tracing off: the guards
        # below keep the hot path allocation-free.
        self.trace_buf: Optional[List] = None

        # what each layer keeps (`LayerKeeps`): `token` planes are read
        # through a block table, `slot` arrays lie one row a slot beside the
        # arena. A seq2seq config names no layer kinds and is never paged.
        keeps = getattr(model_cfg, "layer_keeps", None)
        self._layer_keeps = [keeps(i) for i in range(model_cfg.n_layers)] if keeps else []
        self._slot_state_layers = sum(1 for k in self._layer_keeps if k.slot)
        # a looped stack runs its layers `_loop_passes` times a token, and a layer keeps planes a pass
        self._loop_passes = int(getattr(model_cfg, "loop_steps", 1))
        self._loop_exit_early = 0.0
        # the layers whose prefill runs a chunked recurrence from the slot's `state` (a KDA
        # layer's, an SSM mixer's), whatever planes a token they keep beside it
        self._recurrent_layers = sum(1 for k in self._layer_keeps if "state" in k.slot_names)
        # bytes of slot state a row holds over all layers (0 for K/V and latent layers)
        self._slot_state_bytes_per_slot = (
            model_cfg.slot_state_bytes_per_slot(self.kv_cache_dtype) if self._slot_state_layers else 0)
        self._params = params
        self._param_lock = threading.Lock()
        self._param_version = 0

        V = model_cfg.vocab_size
        P = self.num_slots
        self._suppress = None
        if gen_cfg.suppress_tokens:
            m = np.zeros((V,), np.float32)
            m[np.asarray(gen_cfg.suppress_tokens, np.int64)] = -np.inf
            self._suppress = jnp.asarray(m)

        if self.kv_paging:
            # paged mode: per-layer arenas shared by every slot + one
            # block table per slot; mask/pos/row_index stay dense per-slot
            # (they are tiny). Table entries default to the zero block. A
            # layer's slot state (`cfg.layer_keeps(i).slot`) lies beside its
            # arena, one row a slot: an insert overwrites the row, the decode
            # program moves it on in place, nobody ever clears it.
            layers = init_paged_kv_arena(
                model_cfg, self._n_blocks, self.kv_block_size,
                dtype=self.kv_cache_dtype, num_slots=P,
            )
            cache = {
                "layers": layers,
                "mask": jnp.zeros((P, self._cache_len), jnp.int32),
                "pos": jnp.zeros((P,), jnp.int32),
            }
        else:
            # "auto" resolves to cfg.dtype, so the flag-off pool is
            # byte-identical to before; f32/bf16 overrides re-type the
            # fixed rows in place
            cache = init_kv_cache(
                model_cfg, P, self._cache_len, dtype=self.kv_cache_dtype
            )
        # Fused sampling: the pool carries each slot's PRE-SAMPLED next
        # token + its policy logprob instead of a [P, V] f32 logits bank —
        # suppress/warping/categorical draw happen inside the same jitted
        # program that produced the logits (insert or decode), so no
        # [P, vocab] array round-trips through the pool per token and the
        # sampling work no longer sits outside the fused decode step.
        self._pool: Dict[str, Any] = {
            "layers": cache["layers"],
            "mask": cache["mask"],
            "pos": cache["pos"],
            "row_index": jnp.zeros((P,), jnp.int32),
            "step": jnp.zeros((P,), jnp.int32),
            "active": jnp.zeros((P,), jnp.int32),
            "max_new": jnp.full((P,), gen_cfg.max_new_tokens, jnp.int32),
            "next_token": jnp.full((P,), gen_cfg.pad_token_id, jnp.int32),
            "next_logprob": jnp.zeros((P,), jnp.float32),
            "rng": jax.random.PRNGKey(seed),
        }
        if self.kv_paging:
            self._pool["table"] = jnp.zeros((P, self._n_tbl), jnp.int32)
        if self.multi_tenant:
            # per-slot adapter stack index (0 = base). Gathered by the
            # decode program each step; stale indices on inactive rows
            # stay in-bounds (the store never shrinks its stack), so they
            # only feed rows whose outputs are already ignored.
            self._pool["adapter"] = jnp.zeros((P,), jnp.int32)
        # Paged decode kernel (ops/paged_attention.py) behind the
        # inference.decode_kernel knob: "xla" pins the gather read path;
        # "auto" selects the compiled kernel when the engine's params live
        # on a TPU and the gather path elsewhere; "pallas" demands the
        # compiled kernel and raises where it cannot be had; "interpret"
        # runs the same kernel through the Pallas interpreter (CPU tests
        # and smokes). The TRLX_TPU_KERNELS env switch overrides "auto"
        # and its "off" overrides everything (_resolve_attn_kernel).
        # Per-dispatch fallbacks to the gather path are counted with a
        # reason (kv_stats -> scheduler -> /metrics + healthz).
        if decode_kernel not in ("auto", "pallas", "interpret", "xla"):
            raise ValueError(
                f"decode_kernel {decode_kernel!r} not in "
                "('auto', 'pallas', 'interpret', 'xla')"
            )
        self.decode_kernel = decode_kernel
        self._kernel_unsupported = self._kernel_unsupported_reason()
        self._attn_kernel = self._resolve_attn_kernel()
        devices = self._param_devices()
        logger.info(
            f"decode_kernel={decode_kernel!r} resolved to {self.decode_path!r} "
            f"(params on {len(devices)} {devices[0].platform} device(s)"
            + (f"; kernel unsupported: {self._kernel_unsupported})"
               if self._attn_kernel and self._kernel_unsupported else ")")
        )
        self._kv_kernel_dispatches = 0
        self._kv_kernel_fallbacks: Dict[str, int] = {}
        self._moe_stats: Dict[str, float] = {}
        self._prefill_fns: Dict[Tuple[int, int], Callable] = {}
        self._insert_fns: Dict[int, Callable] = {}
        self._paged_insert_fns: Dict[Tuple[int, int, bool], Callable] = {}
        self._decode_fn = self._make_decode()
        if self.hbm is not None and self.kv_paging:
            stats = self.kv_stats()
            self.hbm.set_component(
                "kv_arena", stats["kv_pool_bytes"],
                n_blocks=self._n_blocks, block_size=self.kv_block_size,
                dtype=str(jnp.dtype(self.kv_cache_dtype)),
            )

    def _resolve_attn_kernel(self) -> Optional[str]:
        """Map the decode_kernel knob onto the per-dispatch attn_kernel
        value threaded into the per-row decode_step: None (gather path),
        "pallas" (compiled Mosaic kernel) or "interpret" (same kernel
        through the Pallas interpreter — CPU-executable). The devices are
        those the params live on, i.e. where the decode program runs (with
        no params yet, the default device): the compiled kernel needs
        exactly one TPU device, so an engine over a trainer's multi-chip
        mesh takes the gather path and a one-chip replica the kernel."""
        from trlx_tpu.ops.attention import kernels_env, require_tpu

        env = kernels_env()
        if self.decode_kernel == "xla" or env == "off":
            return None
        if self._interprets_kernels():
            return "interpret"
        devices = self._param_devices()
        demanded = ("inference.decode_kernel='pallas'" if self.decode_kernel == "pallas"
                    else "TRLX_TPU_KERNELS=pallas" if env == "pallas" else None)
        if demanded:
            require_tpu(devices, demanded)
            if len(devices) > 1:
                # a Mosaic kernel cannot be partitioned automatically, and
                # the engine has no shard_map wrapper for it
                raise RuntimeError(
                    f"{demanded} cannot be honoured: the params span "
                    f"{len(devices)} devices; serve one replica per chip"
                )
        if self.decode_kernel == "pallas" and self._kernel_unsupported is not None:
            raise ValueError(
                "inference.decode_kernel='pallas' cannot be honoured: "
                f"{self._kernel_unsupported} (use 'auto' for a counted "
                "fallback to the gather path)"
            )
        return "pallas" if devices[0].platform == "tpu" and len(devices) == 1 else None

    def _param_devices(self) -> List:
        leaves = jax.tree_util.tree_leaves(self._params)
        return list(leaves[0].devices()) if leaves else jax.devices()[:1]

    @property
    def decode_path(self) -> str:
        """The read path decode programs are built with: "pallas",
        "interpret" or "xla" (shown in /healthz)."""
        if self._attn_kernel is None or self._kernel_unsupported is not None:
            return "xla"
        return self._attn_kernel

    def _kernel_unsupported_reason(self) -> Optional[str]:
        """Engine-static reason the paged decode kernel cannot serve this
        config (counted once per decode dispatch), or None."""
        cfg = self.model_cfg
        if not self.kv_paging:
            return "kv_paging_off"
        if getattr(cfg, "alibi", False):
            return "alibi"
        if self._interprets_kernels():  # the interpreter takes any shape
            return None
        # the compiled `kda_decode` and `ssd_decode` tile whole groups of heads
        if getattr(cfg, "has_linear_layers", False):
            from trlx_tpu.ops.linear_attention import decode_kernel_takes

            if not decode_kernel_takes(cfg.n_heads, cfg.head_dim, cfg.head_dim):
                return "kda_decode_tiling"
        if getattr(cfg, "has_ssm_layers", False):
            from trlx_tpu.ops.ssd import decode_kernel_takes

            if not decode_kernel_takes(cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim):
                return "ssd_decode_tiling"
        return None

    def _interprets_kernels(self) -> bool:
        from trlx_tpu.ops.attention import kernels_env

        return "interpret" in (self.decode_kernel, kernels_env())

    def _ljit(self, fn, name: str, budget: int = 1, **jit_kwargs):
        """Engine jit entry point — plain jax.jit when no compile ledger
        is attached (identical programs), ledgered otherwise."""
        from trlx_tpu.observability.compile_ledger import ledgered_jit

        return ledgered_jit(fn, name=name, budget=budget,
                            ledger=self.compile_ledger, **jit_kwargs)

    # ------------------------------------------------------------------
    # Params (checkpoint hot-reload)
    # ------------------------------------------------------------------

    def set_params(self, params) -> int:
        """Atomically swap the served params. In-flight requests continue
        on the new weights from the next decode step to be dispatched: the
        step already in flight (`step`) ends on the old ones — the KV cache
        keeps the old prefix's keys/values, exactly like serving a live
        policy mid-update. Returns the new param version."""
        if self.prefix_cache:
            # cached prefixes hold K/V computed under the OLD weights:
            # in-flight requests may finish on their stale prefix (same
            # contract as the fixed pool), but new requests must not
            # silently mix old-prefix K/V with new-weight decode
            with self._kv_lock:
                self._block_pool.flush_cached()
        if self.session_store is not None:
            # same staleness contract for session-retained blocks: pins
            # release now, every session answers its next turn with a
            # 409 session_reset instead of silently serving old KV
            self.session_store.invalidate_all("weights_updated")
        with self._param_lock:
            self._params = params
            self._param_version += 1
            return self._param_version

    @property
    def param_version(self) -> int:
        return self._param_version

    @property
    def has_params(self) -> bool:
        """Whether the engine holds weights at all (readiness: a server
        started ahead of its first checkpoint must report not-ready)."""
        with self._param_lock:
            return self._params is not None

    def _current_params(self):
        with self._param_lock:
            return self._params

    # ------------------------------------------------------------------
    # Fused sampling (traced inside the insert / decode programs)
    # ------------------------------------------------------------------

    def _sample_fused(self, raw_logits, key, step):
        """Shared warp + draw: suppress -> process_logits -> select_token
        over the RAW f32 logits, returning (token int32, policy logprob
        f32). Identical op order to the while-loop sampler's per-step
        block, so greedy decode through the pool stays bit-identical to
        `trainer.generate`; the logprob reads the raw (pre-warp) logits —
        the true policy probability, like the rollout fast path."""
        scores = raw_logits
        if self._suppress is not None:
            scores = scores + self._suppress
        scores = process_logits(scores, self.gen_cfg, step)
        token = select_token(scores, key, self.gen_cfg).astype(jnp.int32)
        return token, sampled_token_logprob(raw_logits, token)

    # ------------------------------------------------------------------
    # Prefill + insert
    # ------------------------------------------------------------------

    def _get_prefill(self, pb: int, plen: int) -> Callable:
        key = (pb, plen)
        if key not in self._prefill_fns:
            model, cfg, S = self.model, self.model_cfg, self._cache_len
            mt = self.multi_tenant

            def prefill(params, ids, mask, stack=None, aidx=None):
                # no-op for dense trees; reconstructs the int8 frozen-trunk
                # view in-graph (ops/quant.py)
                params = dequantize_tree(params)
                variables = {"params": params}
                if mt:
                    # the prompt's K/V must carry each row's own adapter
                    variables["lora_rows"] = _gather_rows(stack, aidx)
                cache = init_kv_cache(cfg, ids.shape[0], S)
                # left padding: every row reads the last column, so the head runs over that one
                out = model.apply(
                    variables, ids, cache, mask, True,
                    method=type(model).decode_step, head_at=jnp.full((pb,), plen - 1, jnp.int32),
                )
                logits, new_cache = out[0], out[-1]
                return logits[:, 0].astype(jnp.float32), new_cache

            self._prefill_fns[key] = self._ljit(
                prefill, f"engine.prefill[b{pb},p{plen}]")
        return self._prefill_fns[key]

    def _get_insert(self, pb: int) -> Callable:
        if pb not in self._insert_fns:
            sample_fused = self._sample_fused
            mt = self.multi_tenant

            def insert(pool, cache, last_logits, slot_ids, max_new, aidx=None):
                # slot_ids >= num_slots mark padding rows: out-of-bounds
                # scatter updates are dropped, so they never land
                layers = [
                    {
                        "k": pl["k"].at[slot_ids].set(cl["k"]),
                        "v": pl["v"].at[slot_ids].set(cl["v"]),
                    }
                    for pl, cl in zip(pool["layers"], cache["layers"])
                ]
                row_index = jnp.full(
                    (last_logits.shape[0],), cache["index"], jnp.int32
                )
                # each fresh request's FIRST token samples here, fused with
                # the scatter (step 0 = the while-loop sampler's first
                # iteration); padding rows draw garbage that the OOB
                # scatter drops
                rng, key = jax.random.split(pool["rng"])
                token, lp = sample_fused(last_logits, key, 0)
                new_pool = {
                    **pool,
                    "layers": layers,
                    "mask": pool["mask"].at[slot_ids].set(cache["mask"]),
                    "pos": pool["pos"].at[slot_ids].set(cache["pos"]),
                    "row_index": pool["row_index"].at[slot_ids].set(row_index),
                    "step": pool["step"].at[slot_ids].set(0),
                    "active": pool["active"].at[slot_ids].set(1),
                    "max_new": pool["max_new"].at[slot_ids].set(max_new),
                    "next_token": pool["next_token"].at[slot_ids].set(token),
                    "next_logprob": pool["next_logprob"].at[slot_ids].set(lp),
                    "rng": rng,
                }
                if mt:
                    new_pool["adapter"] = pool["adapter"].at[slot_ids].set(aidx)
                return new_pool

            # donate the old pool (the scatter aliases it); the prefill
            # cache can't alias (different leading dim), so it isn't listed
            self._insert_fns[pb] = self._ljit(
                insert, f"engine.insert[b{pb}]", donate_argnums=(0,))
        return self._insert_fns[pb]

    def _get_paged_insert(self, pb: int, plen: int, fresh: bool = False) -> Callable:
        """Paged-mode prefill+insert, jitted per (rows, suffix-width)
        bucket: one per-row `decode_step` of t > 1 writes each row's RIGHT-padded
        prompt suffix straight into the shared arena through its fresh
        block table (no per-request cache copy to scatter afterwards —
        the arena IS the pool), seeds rows behind a cached prefix at
        column `shared_len`, and fuses the first-token draw. `fresh`
        (`_flush_paged`: no row of the call sits behind a cached prefix, and
        the model's `flash_prefill` covers the width) is a program of its
        own, in which the prompt attends within itself through the fused
        kernel instead of being scored against the row's whole table."""
        key = (pb, plen, fresh)
        if key not in self._paged_insert_fns:
            model, S, P = self.model, self._cache_len, self.num_slots
            sample_fused = self._sample_fused
            mt = self.multi_tenant
            layer_keeps = self._layer_keeps

            def insert(pool, params, ids, tmask, tables, slot_ids, max_new,
                       shared_len, stack=None, aidx=None):
                params = dequantize_tree(params)
                variables = {"params": params}
                if mt:
                    variables["lora_rows"] = _gather_rows(stack, aidx)
                # temp per-request cache rows backed by the SHARED arena;
                # a cached prefix is already resident in blocks
                # tables[:, : shared_len // block], so only its mask bits
                # need seeding — prefill resumes at column shared_len
                # a layer's slot state starts from nothing (a fresh prompt:
                # nothing is shared over slot state), one row a request
                layers = [
                    {**{k2: v2 for k2, v2 in al.items() if k2 not in keeps.slot_names},
                     **{k2: jnp.zeros((pb, *al[k2].shape[1:]), al[k2].dtype) for k2 in keeps.slot_names},
                     **({"table": tables} if keeps.token else {})}
                    for al, keeps in zip(pool["layers"], layer_keeps)]
                seed_mask = (
                    jnp.arange(S)[None, :] < shared_len[:, None]
                ).astype(jnp.int32)
                cache = {
                    "layers": layers,
                    "mask": seed_mask,
                    "pos": shared_len,
                    "row_index": shared_len,
                }
                # the head runs over each row's LAST valid position (right padding), the one read
                lens = tmask.sum(-1).astype(jnp.int32)
                out = model.apply(
                    variables, ids, cache, tmask,
                    method=type(model).decode_step,
                    attn_kernel="prefill" if fresh else None,
                    head_at=jnp.clip(lens - 1, 0, plen - 1),
                )
                logits, new_cache = out[0], out[-1]
                last = logits[:, 0].astype(jnp.float32)
                rng, key_ = jax.random.split(pool["rng"])
                token, lp = sample_fused(last, key_, 0)
                # the arena is the pool's; a row's final slot state goes into
                # its slot, over whatever a finished request left there
                arena = [
                    {k2: (al[k2].at[slot_ids].set(v2) if k2 in keeps.slot_names else v2)
                     for k2, v2 in layer.items() if k2 != "table"}
                    for layer, al, keeps in zip(new_cache["layers"], pool["layers"], layer_keeps)
                ]
                # padding rows carry slot_id == num_slots and all-OOB
                # tables: both their arena writes (inside the model's step)
                # and these pool scatters are dropped
                new_pool = {
                    **pool,
                    "layers": arena,
                    "table": pool["table"].at[slot_ids].set(tables),
                    "mask": pool["mask"].at[slot_ids].set(new_cache["mask"]),
                    "pos": pool["pos"].at[slot_ids].set(new_cache["pos"]),
                    "row_index": pool["row_index"].at[slot_ids].set(
                        new_cache["row_index"]
                    ),
                    "step": pool["step"].at[slot_ids].set(0),
                    "active": pool["active"].at[slot_ids].set(1),
                    "max_new": pool["max_new"].at[slot_ids].set(max_new),
                    "next_token": pool["next_token"].at[slot_ids].set(token),
                    "next_logprob": pool["next_logprob"].at[slot_ids].set(lp),
                    "rng": rng,
                }
                if mt:
                    new_pool["adapter"] = pool["adapter"].at[slot_ids].set(aidx)
                return new_pool

            self._paged_insert_fns[key] = self._ljit(
                insert, f"engine.paged_insert[b{pb},p{plen}{',fresh' if fresh else ''}]",
                donate_argnums=(0,))
        return self._paged_insert_fns[key]

    @staticmethod
    def _split_row(row) -> Tuple[np.ndarray, int, Optional[str]]:
        """Normalize an insert row to (ids, max_new, adapter_name) —
        callers without multi-tenancy keep passing 2-tuples."""
        if len(row) == 3:
            return row[0], row[1], row[2]
        ids, max_new = row
        return ids, max_new, None

    def _insert_requests_impl(
        self,
        rows: Sequence[Tuple],  # (unpadded prompt ids, max_new[, adapter_id])
        slot_ids: Sequence[int],
        sessions: Optional[Sequence] = None,  # per-row Session or None
    ) -> Tuple[int, int, int, int]:
        """Prefill `rows` (length-bucketed, left-padded) and scatter them
        into the given free slots. Requests are grouped by prompt-width
        bucket; each group prefills as one jitted call. Paged mode routes
        to `_insert_paged` (block allocation + prefix-store probing +
        right-padded suffix prefill). Multi-tenant rows carry an adapter
        id as a third element; the engine pins each row's adapter in the
        store for the request's lifetime (released in `reclaim_slots`)
        and the prefill program applies per-row factors. `sessions`
        (paged only) attaches a row to a chat session: its retained
        blocks seed the shared prefix, so only the conversation's delta
        tokens prefill. Returns what was prefilled (`_count_admission`)."""
        assert len(rows) == len(slot_ids)
        if sessions is not None and any(s is not None for s in sessions):
            if not self.kv_paging:
                raise ValueError("sessions require kv_paging")
        else:
            sessions = None
        norm = [self._split_row(r) for r in rows]
        aslots: Optional[List[int]] = None
        if self.multi_tenant:
            aslots = self._acquire_adapters(norm, slot_ids)
        try:
            if self.kv_paging:
                return self._insert_paged(norm, slot_ids, aslots, sessions)
            return self._insert_dense(norm, slot_ids, aslots)
        except Exception:
            if self.multi_tenant:
                self._release_adapters(slot_ids)
            raise

    def _acquire_adapters(self, norm, slot_ids) -> List[int]:
        """Pin every row's adapter (loading on demand) and return their
        stack indices. All-or-nothing: a capacity failure releases the
        pins already taken so the scheduler can retry with a smaller
        batch (it sheds distinct-adapter groups until the rest fit)."""
        aslots: List[int] = []
        acquired: List[Tuple[int, Optional[str]]] = []
        try:
            for (ids, max_new, name), slot in zip(norm, slot_ids):
                if self.trace_buf is not None:
                    t0 = time.monotonic()
                    aslots.append(self.adapter_store.acquire(name))
                    self.trace_buf.append((
                        "adapter_load", t0, time.monotonic(),
                        {"adapter": name or "base"},
                    ))
                else:
                    aslots.append(self.adapter_store.acquire(name))
                acquired.append((int(slot), name))
        except Exception:
            for _, name in acquired:
                self.adapter_store.release(name)
            raise
        for slot, name in acquired:
            self._slot_adapter[slot] = name
        return aslots

    def _release_adapters(self, slots) -> None:
        for slot in slots:
            if int(slot) in self._slot_adapter:
                self.adapter_store.release(self._slot_adapter.pop(int(slot)))

    def _insert_dense(self, norm, slot_ids, aslots: Optional[List[int]]) -> Tuple[int, int, int, int]:
        pad_id = self.gen_cfg.pad_token_id
        mt = self.multi_tenant
        programs = self._prefill_programs([
            (self._check_row(ids, max_new), int(max_new), int(slot), aslots[i] if mt else 0)
            for i, ((ids, max_new, _name), slot) in enumerate(zip(norm, slot_ids))
        ])
        params = self._current_params()
        stack = self.adapter_store.stacked() if mt else None
        counts = self._count_admission(programs)
        for plen, chunk, pb in programs:
            ids_arr = np.full((pb, plen), pad_id, np.int32)
            mask_arr = np.zeros((pb, plen), np.int32)
            # padding rows repeat row 0 (a real prompt; fully-masked
            # rows are avoided) and scatter out of bounds
            slots_arr = np.full((pb,), self.num_slots, np.int32)
            max_new_arr = np.full((pb,), self.gen_cfg.max_new_tokens, np.int32)
            aidx_arr = np.zeros((pb,), np.int32)  # padding rows gather base
            for j, (ids, max_new, slot, aslot) in enumerate(chunk):
                ids_arr[j, plen - ids.size :] = ids  # left-padded (decode convention)
                mask_arr[j, plen - ids.size :] = 1
                slots_arr[j] = slot
                max_new_arr[j] = max_new
                aidx_arr[j] = aslot
            ids_arr[len(chunk) :] = ids_arr[0]
            mask_arr[len(chunk) :] = mask_arr[0]

            with self._insert_span(len(chunk), plen):
                if mt:
                    aidx = jnp.asarray(aidx_arr)
                    last_logits, cache = self._get_prefill(pb, plen)(
                        params, jnp.asarray(ids_arr), jnp.asarray(mask_arr),
                        stack, aidx,
                    )
                    self._pool = self._get_insert(pb)(
                        self._pool, cache, last_logits,
                        jnp.asarray(slots_arr), jnp.asarray(max_new_arr), aidx,
                    )
                else:
                    last_logits, cache = self._get_prefill(pb, plen)(
                        params, jnp.asarray(ids_arr), jnp.asarray(mask_arr)
                    )
                    self._pool = self._get_insert(pb)(
                        self._pool, cache, last_logits,
                        jnp.asarray(slots_arr), jnp.asarray(max_new_arr),
                    )
        return counts

    def _prefill_programs(self, members: Sequence[Tuple]) -> List[Tuple[int, List[Tuple], int]]:
        """The prefill programs that dispatch `members` (rows whose first
        element is the tokens to prefill), as (width bucket, rows, row-count
        bucket): the rows grouped by the bucket their width is padded to, a
        group in chunks of `max_prefill_batch`."""
        groups: Dict[int, List[Tuple]] = {}
        for member in members:
            groups.setdefault(_round_up(len(member[0]), self.prompt_bucket), []).append(member)
        step = self.max_prefill_batch
        return [(plen, rows[i : i + step], _pow2_bucket(len(rows[i : i + step]), step))
                for plen, rows in groups.items() for i in range(0, len(rows), step)]

    def _count_admission(self, programs: Sequence[Tuple[int, List[Tuple], int]]) -> Tuple[int, int, int, int]:
        """What one call of `insert_requests` is about to prefill, from the programs it will
        dispatch: the rows, the prompt tokens they compute (a prompt's, less what it shares from
        the prefix store or its session), the positions the programs are dispatched at (rows
        padded to their bucket x the width bucket, summed over the programs) and the positions
        they run the head over (a padded row's one: `decode_step`'s `head_at`). The scheduler adds
        them to its counters; while a tracing session listens they stand on the trace too, one
        counter span an admission, in front of its first program's dispatch."""
        rows = sum(len(chunk) for _, chunk, _ in programs)
        tokens = sum(len(member[0]) for _, chunk, _ in programs for member in chunk)
        padded = sum(pb * plen for plen, _, pb in programs)
        head_positions = sum(pb for _, _, pb in programs)
        if tracing.active():
            tracing.counters("sched.insert", calls=1, rows=rows, prompt_tokens=tokens, padded_tokens=padded,
                             pad_tokens=padded - tokens, head_positions=head_positions)
            if self._recurrent_layers:
                # what the chunked recurrence is about to run, a layer: every padded position, in chunks, in which form
                chunks = sum(pb * -(-plen // self.model_cfg.state_chunk) for plen, _, pb in programs)
                tracing.counters("engine.prefill_state", tokens=tokens, padded_tokens=padded,
                                 linear_layers=self._recurrent_layers, chunks=chunks,
                                 form=_prefill_state_form(self.model_cfg))
        return rows, tokens, padded, head_positions

    @contextlib.contextmanager
    def _insert_span(self, rows: int, width: int):
        """Round the dispatch of one prefill program: the `trlx:engine.insert`
        span, and from the same clock reads the `prefill_bucket` entry of a
        traced request's buffer."""
        with tracing.timed_span("engine.insert") as sp:
            yield
        if self.trace_buf is not None:
            self.trace_buf.append((
                "prefill_bucket", sp.t0, sp.t1, {"bucket": width, "rows": rows},
            ))

    def _check_row(self, ids, max_new: int) -> np.ndarray:
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size == 0 or ids.size > self.max_prompt_len:
            raise ValueError(
                f"prompt length {ids.size} outside (0, {self.max_prompt_len}]"
            )
        if not 0 < max_new <= self.gen_cfg.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {max_new} outside (0, "
                f"{self.gen_cfg.max_new_tokens}]"
            )
        return ids

    def _alloc_evicting_sessions(self, n: int) -> List[int]:
        """pool.alloc with one retry after un-pinning idle sessions'
        retained blocks LRU-first (block pressure evicts conversations'
        KV before refusing new work). Lock already held (re-entrant)."""
        try:
            return self._block_pool.alloc(n)
        except KVPoolExhaustedError:
            if self.session_store is None:
                raise
            self.session_store.evict_for_blocks(n)
            return self._block_pool.alloc(n)

    def _insert_paged(
        self, rows, slot_ids, aslots: Optional[List[int]] = None,
        sessions: Optional[Sequence] = None,
    ) -> Tuple[int, int, int, int]:
        """Paged insert: allocate each request's blocks up front
        (prompt + max_new — no mid-decode OOM, no preemption),
        probing the prefix store for resident leading blocks first. In
        multi-tenant mode prefix keys are salted with the row's adapter
        identity, so paged prefix blocks never cross tenants.

        Requests whose probe would hit keys REGISTERED EARLIER IN THIS
        CALL are deferred one placement round: the registering request's
        prefill has not been dispatched yet, and a same-program gather of
        its blocks would read zeros. Each round places at least the first
        pending request, so this terminates; GRPO's n-way fan-out of one
        prompt resolves as 1 full prefill + (n-1) suffix prefills batched
        together in round two.

        Session rows bypass the prefix store entirely: their shared
        prefix is the conversation's own retained block chain (taken via
        per-request references, so the normal slot-reclaim release works
        unchanged) and their blocks are never published under keys —
        retained KV stays private to its conversation."""
        bs, pool = self.kv_block_size, self._block_pool
        mt = self.multi_tenant
        store = self.session_store
        pending: List[Tuple] = []
        for i, ((ids, max_new, name), slot) in enumerate(zip(rows, slot_ids)):
            salt = adapter_salt(name) if mt else b""
            pending.append((
                self._check_row(ids, max_new), int(max_new), int(slot),
                salt, aslots[i] if mt else 0,
                sessions[i] if sessions is not None else None,
            ))
        params = self._current_params()
        # place every round before dispatching anything, journalling each
        # placement — on pool exhaustion the whole call rolls back (no
        # partial prefills, no dangling store keys) so the scheduler can
        # requeue the batch and retry once blocks free
        rounds: List[List] = []
        journal: List[Tuple[int, List[int], List[bytes]]] = []
        t_alloc0 = time.monotonic() if self.trace_buf is not None else 0.0
        with self._kv_lock:
            try:
                while pending:
                    placed, deferred = [], []
                    round_keys: set = set()
                    for ids, max_new, slot, salt, aslot, sess in pending:
                        if sess is not None:
                            keys = []
                            shared = store.acquire_blocks(sess, ids)
                            sess.last_reused_blocks = len(shared)
                            sess.last_prefill_tokens = ids.size - len(shared) * bs
                            if shared:
                                store.retained_hits += 1
                                store.retained_blocks_reused += len(shared)
                        else:
                            keys = prefix_keys(ids, bs, salt) if self.prefix_cache else []
                            if any(k in round_keys for k in keys):
                                deferred.append((ids, max_new, slot, salt, aslot, sess))
                                continue
                            shared = []
                            for key in keys:
                                blk = pool.acquire_cached(key)
                                if blk is None:
                                    break
                                shared.append(blk)
                            if keys:
                                if shared:
                                    pool.hits += 1
                                else:
                                    pool.misses += 1
                        n_cap = -(-(ids.size + max_new) // bs)
                        try:
                            owned = self._alloc_evicting_sessions(n_cap - len(shared))
                        except KVPoolExhaustedError:
                            pool.release(shared)
                            raise
                        blocks = shared + owned
                        # publish the full-prompt blocks this prefill will
                        # write (keys cover [0, (L-1)//bs) — at least one
                        # suffix token always prefills on a future hit)
                        registered: List[bytes] = []
                        for j in range(len(shared), len(keys)):
                            pool.register(keys[j], blocks[j])
                            round_keys.add(keys[j])
                            registered.append(keys[j])
                        self._slot_blocks[slot] = blocks
                        self._slot_cols[slot] = ids.size
                        journal.append((slot, blocks, registered))
                        T = len(shared) * bs
                        placed.append((ids[T:], T, blocks, max_new, slot, aslot))
                    rounds.append(placed)
                    pending = deferred
            except KVPoolExhaustedError:
                for slot, blocks, registered in journal:
                    for key in registered:
                        pool.unregister(key)
                    pool.release(blocks)
                    self._slot_blocks.pop(slot, None)
                raise
        if self.trace_buf is not None:
            self.trace_buf.append((
                "block_alloc", t_alloc0, time.monotonic(),
                {"rounds": len(rounds), "requests": len(slot_ids)},
            ))
        # dispatch order between rounds is what makes same-call sharing
        # sound: a round-2 suffix prefill gathers blocks the round-1
        # program has already written by the time it runs
        programs = [p for placed in rounds for p in self._prefill_programs(placed)]
        counts = self._count_admission(programs)
        self._flush_paged(programs, params)
        return counts

    def _flush_paged(self, programs, params) -> None:
        """Dispatch the placement rounds' prefills, round by round, each
        round grouped by suffix width bucket and chunked to
        `max_prefill_batch` (`_prefill_programs`)."""
        pad_id = self.gen_cfg.pad_token_id
        mt = self.multi_tenant
        stack = self.adapter_store.stacked() if mt else None
        for plen, chunk, pb in programs:
            ids_arr = np.full((pb, plen), pad_id, np.int32)
            tmask = np.zeros((pb, plen), np.int32)
            tables = np.full((pb, self._n_tbl), self._n_blocks, np.int32)
            slots_arr = np.full((pb,), self.num_slots, np.int32)
            max_new_arr = np.full((pb,), self.gen_cfg.max_new_tokens, np.int32)
            shared_arr = np.zeros((pb,), np.int32)
            aidx_arr = np.zeros((pb,), np.int32)  # padding rows gather base
            for j, (suffix, T, blocks, max_new, slot, aslot) in enumerate(chunk):
                ids_arr[j, : len(suffix)] = suffix  # RIGHT-padded
                tmask[j, : len(suffix)] = 1
                tables[j, : len(blocks)] = blocks
                tables[j, len(blocks) :] = 0  # zero-block padding
                slots_arr[j] = slot
                max_new_arr[j] = max_new
                shared_arr[j] = T
                aidx_arr[j] = aslot
            # padding rows repeat row 0's tokens but keep all-OOB
            # tables and OOB slot ids — every write they make drops
            ids_arr[len(chunk) :] = ids_arr[0]
            tmask[len(chunk) :] = tmask[0]
            args = [
                self._pool, params, jnp.asarray(ids_arr), jnp.asarray(tmask),
                jnp.asarray(tables), jnp.asarray(slots_arr),
                jnp.asarray(max_new_arr), jnp.asarray(shared_arr),
            ]
            if mt:
                args += [stack, jnp.asarray(aidx_arr)]
            fresh = not shared_arr.any() and prefill_fuses(self.model_cfg, plen)
            with self._insert_span(len(chunk), plen):
                self._pool = self._get_paged_insert(pb, plen, fresh)(*args)

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------

    def _make_decode(self) -> Callable:
        model, gen_cfg = self.model, self.gen_cfg
        pad, eos = gen_cfg.pad_token_id, gen_cfg.eos_token_id
        sample_fused = self._sample_fused
        paged = self.kv_paging
        mt = self.multi_tenant
        # closure constant: the fused paged read path, or None for the
        # pinned gather path (unsupported configs fall back here and are
        # counted per dispatch in _step_impl)
        ak = self._attn_kernel if self._kernel_unsupported is None else None
        sown = getattr(self.model_cfg, "has_sparse_moe", False)
        looped = self._loop_passes > 1  # its step sows the exit gate's one scalar (never both: no looped experts)

        def decode(params, pool, stack=None):
            params = dequantize_tree(params)
            active = pool["active"].astype(bool)
            # emit the token the PREVIOUS program (insert or decode)
            # already sampled — no warping work on this side of the model
            # call, and no [P, V] logits carried between programs
            token = jnp.where(active, pool["next_token"], pad)
            logprob = pool["next_logprob"]
            valid = active
            finished = active & (
                (token == eos) | (pool["step"] + 1 >= pool["max_new"])
            )
            cache = {k: pool[k] for k in ("layers", "mask", "pos", "row_index")}
            if paged:
                # route every layer through the slot block tables; decode
                # never remaps blocks, so the tables pass through
                cache["layers"] = [
                    dict(al, table=pool["table"]) if keeps.token else al
                    for al, keeps in zip(cache["layers"], self._layer_keeps)
                ]
            variables = {"params": params}
            if mt:
                # one heterogeneous step: each row applies its own
                # adapter's factors, gathered by the slot's stack index
                # (Punica-style batched LoRA; slot 0 zeros = base policy)
                variables["lora_rows"] = _gather_rows(stack, pool["adapter"])
            out = model.apply(
                variables, token[:, None], cache,
                valid.astype(jnp.int32)[:, None],
                method=type(model).decode_step,
                attn_kernel=ak,
                mutable=["moe_stats"] if sown else ["loop_stats"] if looped else False,
            )
            if sown or looped:
                out, state = out
            logits, new_cache = out[0], out[-1]
            if paged:
                new_cache = dict(new_cache, layers=[
                    {k2: v2 for k2, v2 in layer.items() if k2 != "table"}
                    for layer in new_cache["layers"]
                ])
            # fused draw of each row's NEXT token from the fresh logits;
            # new_step is per-row, exactly the loop counter each row would
            # see in the while-loop sampler (finished/inactive rows draw
            # garbage that is never emitted — insert overwrites the slot)
            rng, key = jax.random.split(pool["rng"])
            new_step = pool["step"] + active.astype(jnp.int32)
            nxt, nxt_lp = sample_fused(logits[:, -1].astype(jnp.float32), key, new_step)
            new_pool = {
                **pool,
                **new_cache,
                "next_token": nxt,
                "next_logprob": nxt_lp,
                "step": new_step,
                "active": pool["active"] * (1 - finished.astype(jnp.int32)),
                "rng": rng,
            }
            if sown:
                # the step's dispatch counters (a few scalars), fetched with
                # its tokens: no further transfer
                return new_pool, token, logprob, valid, finished, moe_stats_from_state(state)
            if looped:  # the same way: one more scalar among the step's outputs
                return new_pool, token, logprob, valid, finished, exit_early_from_state(state)
            return new_pool, token, logprob, valid, finished

        # distinct ledger site per read path (budget 1 either way): a
        # kernel-enabled engine retracing into the gather program — or
        # vice versa — must show up as a budget violation, not hide
        # under the other site's compile
        site = "engine.decode" if ak is None else f"engine.decode[{ak}]"
        return self._ljit(decode, site, donate_argnums=(1,))

    def _maybe_oom_postmortem(self, site: str, exc: BaseException) -> None:
        """OOM forensics at the engine-dispatch boundary: RESOURCE_EXHAUSTED
        escaping a prefill/insert/decode dispatch dumps a memory postmortem
        (KV occupancy, sessions, resident adapters, compile history,
        largest live buffers) once per site before re-raising."""
        from trlx_tpu.observability.hbm import is_oom_error, oom_postmortem

        if not is_oom_error(exc):
            return
        oom_postmortem(
            site, exc, hbm=self.hbm, compile_ledger=self.compile_ledger,
            context={
                "kv_stats": self.kv_stats,
                "session_stats": self.session_stats,
                "adapter_stats": self.adapter_stats,
                "active_slots": lambda: self.active_slots,
                "num_slots": self.num_slots,
            },
        )

    def insert_requests(self, rows, slot_ids, **kwargs) -> Tuple[int, int, int, int]:
        """OOM-guarded wrapper over `_insert_requests_impl` (see there for
        the contract); samples the HBM ledger at the prefill boundary.
        Returns the admission's (rows, prompt tokens prefilled, positions
        dispatched with the padding, positions unembedded): `_count_admission`."""
        # the step in flight was dispatched before these rows: whatever it
        # holds for their slots is not theirs
        self._disown(slot_ids)
        try:
            counts = self._insert_requests_impl(rows, slot_ids, **kwargs)
        except Exception as e:
            self._maybe_oom_postmortem("engine.insert", e)
            raise
        self._live[np.asarray(slot_ids, np.int64)] = True
        if self.hbm is not None:
            self.hbm.sample("engine.insert")
        return counts

    def step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """One decode step's outputs, one step a call, in order (`_step_impl`
        has the return contract and what runs ahead). OOM-guarded; samples
        the HBM ledger every 64th decode step — often enough to catch the
        arena high-water mark, rare enough to stay off the hot path. An
        error of a step surfaces at the call that fetches it, and costs the
        requests that step's token and no other: the step dispatched behind
        it stays in flight and is what the next call returns (a dispatch
        that fails raises at once and leaves the step in flight where it
        was)."""
        self._step_n += 1
        try:
            with tracing.span("engine.step"):
                out = self._step_impl()
        except Exception as e:
            self._maybe_oom_postmortem("engine.step", e)
            raise
        if self.hbm is not None and self._step_n % 64 == 1:
            self.hbm.sample("engine.decode")
        return out

    def _step_impl(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Advance every active slot. Returns host arrays (tokens [P],
        logprobs [P] f32, emitted [P] bool, finished [P] bool): a slot
        emits one token a call where `emitted`. Finished slots are already
        deactivated in the pool. The logprob is the policy's raw-logit
        log-probability of the emitted token (see `_sample_fused`),
        meaningful only where `emitted`.

        One decode program stays in flight: a call dispatches the NEXT step
        first and only then waits for the step dispatched a call earlier,
        which it returns. The program takes its input token from the pool
        on the device, deactivates finished rows itself and never remaps a
        block, so it needs nothing the host learns from the step before it;
        what the caller does between two calls (emit, reclaim, insert,
        release, `set_params`) runs while the device works and queues
        behind the step in flight. With nothing in flight (the first call,
        or every row of the step in flight released, reclaimed or refilled
        since) the call dispatches its own step, then the one after, and
        waits for its own. A slot's output belongs to the request that held
        the slot when the step was dispatched: for a slot released,
        reclaimed or inserted into since, `emitted` and `finished` come
        back false."""
        if self._ahead is not None and not self._ahead.rows.any():
            self._ahead = None  # nobody waits for it: the call's own step comes first
        if self._ahead is None:
            self._ahead = self._dispatch_decode()
        # still `_ahead` while the next step is dispatched, which counts the
        # columns this one writes as in flight
        due = self._ahead
        self._ahead = self._dispatch_decode()
        # the span ends when the host has the step's outputs, and covers
        # nothing else: its end is what a reader sets against the device's
        with tracing.span("engine.fetch"):
            token, logprob, valid, finished, *stats = jax.device_get(due.out)
        traced = tracing.active()
        if traced:  # which step that fetch waited for: the `seq` it was queued under
            tracing.counters("engine.fetched", seq=due.seq)
        if stats and self._loop_passes > 1:  # a looped stack: what the step fetched ran, and its exit gate's scalar
            self._loop_exit_early = float(stats[0])
            if traced:
                tracing.counters("engine.loop", steps=1, passes=self._loop_passes,
                                 layer_calls=self._loop_passes * self.model_cfg.n_layers,
                                 exit_early=round(self._loop_exit_early, 6))
        elif stats:  # a model with `SparseMoE` layers: the step's dispatch counters
            self._moe_stats = {k: float(v) for k, v in stats[0].items()}
            if self.kv_paging and traced:
                tracing.counters("engine.moe", **self._moe_stats)
        self._outputs_masked += int((valid & ~due.rows).sum())
        valid = valid & due.rows
        finished = finished & due.rows
        # the device deactivated these rows itself: the step in flight
        # holds nothing for them
        self._live[finished] = False
        self._ahead.rows[finished] = False
        if self.kv_paging:
            self._slot_cols += valid
        # kernel dispatch accounting (driver thread; read under _kv_lock
        # by kv_stats), after the step has run: a decode dispatch either
        # rode the fused kernel or fell back to the gather path for a
        # counted reason.
        if self._attn_kernel is not None:
            if self._kernel_unsupported is not None:
                r = self._kernel_unsupported
                self._kv_kernel_fallbacks[r] = self._kv_kernel_fallbacks.get(r, 0) + 1
            else:
                self._kv_kernel_dispatches += 1
        return np.asarray(token), np.asarray(logprob, np.float32), valid, finished

    def _dispatch_decode(self) -> _InFlight:
        """Queue one decode program on the device and start its outputs'
        copy to the host; wait for nothing."""
        ahead = self._ahead is not None
        self._steps_ahead += int(ahead)
        self._dispatches += 1
        seq = self._dispatches
        # said only while a tracing session listens, as counter spans (a
        # reader keeps names, not attributes): how ragged the rows are, which
        # the paged kernel's time follows, and directly in front of the
        # dispatch's span who the step is, so that a reader joins this
        # dispatch, the device's program and the fetch that waits for it
        if tracing.active():
            if self.kv_paging:
                tracing.counters("engine.kv_walk", **self._kv_walk(), kv_write=self._kv_write_form())
            if self._slot_state_layers:
                tracing.counters("engine.slot_state", **self._slot_state_step())
            tracing.counters("engine.queued", seq=seq, ahead=int(ahead), rows=int(self._live.sum()))
        with tracing.span("engine.dispatch"):
            params = self._current_params()
            if self.multi_tenant:
                self._pool, *out = self._decode_fn(params, self._pool, self.adapter_store.stacked())
            else:
                self._pool, *out = self._decode_fn(params, self._pool)
            # or the copy starts only when the fetch asks for it, and the
            # device stands still meanwhile (PERF.md section 5)
            for leaf in jax.tree_util.tree_leaves(out):
                leaf.copy_to_host_async()
        return _InFlight(tuple(out), self._live.copy(), seq)

    def _disown(self, slots: Sequence[int]) -> None:
        """The step in flight no longer speaks for these slots."""
        if self._ahead is not None:
            self._ahead.rows[np.asarray(slots, np.int64)] = False

    def release_slots(self, slots: Sequence[int]) -> None:
        """Deactivate slots host-side (stop sequence / deadline cancel /
        shutdown). The deactivation queues behind the step in flight, which
        still decodes a token for each of them: `step` drops it."""
        if not len(slots):
            return
        idx = jnp.asarray(np.asarray(slots, np.int32))
        self._pool = {**self._pool, "active": self._pool["active"].at[idx].set(0)}
        self.reclaim_slots(slots)

    def reclaim_slots(self, slots: Sequence[int]) -> None:
        """Return a finished slot's blocks to the pool and drop its
        adapter pin (host bookkeeping only — no device op; a freed slot's
        stale table is harmless because inactive rows' arena writes are
        gated out). What the step in flight still holds for the slot
        reaches nobody. Idempotent; the scheduler calls this for natural
        finishes; `release_slots` folds it into cancels."""
        self._disown(slots)
        self._live[np.asarray(slots, np.int64)] = False
        if self.multi_tenant:
            self._release_adapters(slots)
        if not self.kv_paging:
            return
        with self._kv_lock:
            for slot in slots:
                blocks = self._slot_blocks.pop(int(slot), None)
                if blocks:
                    self._block_pool.release(blocks)

    # ------------------------------------------------------------------
    # Paged-pool accounting (admission + metrics)
    # ------------------------------------------------------------------

    def projected_blocks(
        self, prompt_ids, max_new_tokens: int, ignore_cache: bool = False,
        adapter_id: Optional[str] = None, session=None,
    ) -> int:
        """Blocks this request would claim if admitted now:
        ceil((prompt + max_new) / block_size) minus the leading
        blocks a read-only prefix-store probe says are resident (probed
        in the request's own adapter key space), or minus the session's
        retained blocks when the request rides one. 0 when paging is
        off."""
        if not self.kv_paging:
            return 0
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        n_cap = -(-(ids.size + int(max_new_tokens)) // self.kv_block_size)
        if session is not None:
            # session rows never touch the prefix store; their only
            # reuse is the conversation's own retained prefix
            if ignore_cache:
                return max(1, n_cap)
            with self._kv_lock:
                cov = session.covered_tokens(self.kv_block_size)
                shared = (
                    len(session.blocks)
                    if session.reset_reason is None
                    and ids.size > cov
                    and np.array_equal(ids[:cov], session.tokens[:cov])
                    else 0
                )
            return max(1, n_cap - shared)
        salt = adapter_salt(adapter_id) if self.multi_tenant else b""
        with self._kv_lock:
            shared = 0 if ignore_cache else self._block_pool.lookup_chain(ids, salt)
        return max(1, n_cap - shared)

    def blocks_available(self) -> int:
        """Blocks a new request can claim: free + evictable idle
        (prefix-cache idle blocks, plus idle sessions' retained pins —
        the insert path evicts those under pressure)."""
        if not self.kv_paging:
            return 0
        with self._kv_lock:
            n = self._block_pool.available()
            if self.session_store is not None:
                n += self.session_store.evictable_blocks()
            return n

    @property
    def total_blocks(self) -> int:
        """Allocatable blocks (zero block excluded); 0 when paging is off."""
        return self._block_pool.total if self.kv_paging else 0

    def _live_entries(self) -> int:
        """Block-table entries that hold a column the next decode step
        attends to, over the slots with a request: what the paged kernel
        fetches (ops/paged_attention.py, `n_live`). From the host's own
        count of each slot's columns; the step adds its one."""
        return int((-(-self._next_columns() // self.kv_block_size)).sum())

    def _next_columns(self) -> np.ndarray:
        """Columns the next decode step to be dispatched attends over, for
        each slot with a request: what the host has counted for the slot,
        the one column that the step in flight writes for a row it still
        speaks for, and the step's own one."""
        with self._kv_lock:
            slots = list(self._slot_blocks)
        cols = self._slot_cols[slots] + 1
        ahead = self._ahead
        if ahead is not None:
            cols += ahead.rows[slots]
        return np.minimum(cols, self._cache_len)

    def _slot_state_step(self) -> Dict[str, int]:
        """What the decode step being dispatched does to slot state: every
        live row's arrays a slot are read whole and written whole (`bytes` is
        both, over the live rows and the layers that keep such state; a row
        with no request is left as it lies)."""
        live = int(self._live.sum())
        return {"steps": 1, "slots": self.num_slots, "live": live, "layers": self._slot_state_layers,
                "bytes": 2 * live * self._slot_state_bytes_per_slot}

    def _kv_walk(self) -> Dict[str, int]:
        """Key positions the next decode step reads against the positions
        resident, over the slots with a request, summed over the layers that
        keep planes a token: `resident` (every layer could read all of a
        row's columns), `walked_full` and `walked_window` (what the K/V
        layers without and with a window do read: the paged kernel walks
        whole table entries, from the one that holds a row's first column
        inside the window to the one that holds its last; the gather path
        reads a row's whole table), `walked_latent` (the same for the latent
        layers that read by a walk, banded or not), and for the latent layers
        whose index chooses (`LatentSpec.index_topk`): `index_scored` (index
        keys walked: every live entry), `index_attendable` (positions the
        index chose among) and `index_chosen` (latents the step reads:
        index_topk a row at most; on the gather path every column is read and
        `walked_latent` counts it). `bytes` is what all of that reads from
        the arena, positions x what a position holds in the plane read,
        `bytes_full` the part of it in the layers that choose (their index
        keys and chosen latents) and `bytes_dense_full` what those layers
        would read were every attendable latent read; `layers`; and, of the
        K/V layers' calls of the paged kernel, `calls_copied` (the kernel
        copies a tile's blocks itself) and `calls_operands` (each block an
        operand the pipeline fetches), which the arena's shapes decide
        (`ops.paged_attention.copies_blocks`). From the host's own count of
        each slot's columns; the step adds its one."""
        from trlx_tpu.ops.paged_attention import copies_blocks

        cfg, blk = self.model_cfg, self.kv_block_size
        cols = self._next_columns()
        # one entry a call of the read side: a layer of a looped stack is read once a pass, from that pass's planes
        kinds = [cfg.layer_op(i) for i in range(cfg.n_layers) if self._layer_keeps[i].token] * self._loop_passes
        last = -(-cols // blk)
        kernels = self.decode_path != "xla"

        def walked(window) -> int:  # by one layer
            if not kernels:
                return len(cols) * self._n_tbl * blk
            first = 0 if window is None else np.maximum(cols - window, 0) // blk
            return int(((last - first) * blk).sum())

        walk = dict.fromkeys(("walked_full", "walked_window", "walked_latent", "index_scored", "index_attendable",
                              "index_chosen", "bytes", "bytes_full", "bytes_dense_full", "calls_copied",
                              "calls_operands"), 0)
        walk.update(resident=int(cols.sum()) * len(kinds), layers=len(kinds))
        itemsize = jnp.dtype(self.kv_cache_dtype).itemsize
        form = "calls_copied" if copies_blocks(cfg.kv_heads, blk, cfg.head_dim, self.kv_cache_dtype) else "calls_operands"
        for kind in kinds:
            latent, n = cfg.latent_of(kind), walked(cfg.window_of(kind))
            if latent is None:
                walk["walked_full" if cfg.window_of(kind) is None else "walked_window"] += n
                walk["bytes"] += itemsize * n * 2 * cfg.kv_heads * cfg.head_dim
                walk[form] += int(kernels)
            elif not latent.index_topk:
                walk["walked_latent"] += n
                walk["bytes"] += itemsize * n * latent.width
            else:
                chosen = int(np.minimum(cols, latent.index_topk).sum())
                walk["index_scored"] += n
                walk["index_attendable"] += int(cols.sum())
                walk["index_chosen"] += chosen
                if not kernels:
                    walk["walked_latent"] += n
                read = itemsize * (n * latent.index_head_dim + (chosen if kernels else n) * latent.width)
                walk["bytes"] += read
                walk["bytes_full"] += read
                walk["bytes_dense_full"] += itemsize * n * latent.width
        if not getattr(cfg, "has_index_layers", False):  # no layer chooses: the counters of a choice say nothing
            walk = {k: v for k, v in walk.items() if not k.startswith(("index_", "bytes_"))}
        return walk

    def _kv_write_form(self) -> str:
        """How a decode step's K and V reach the arena: `kernel`, from the paged
        kernel itself (`ops.paged_attention.writes_in_kernel`: the engine runs
        the kernel and every K/V layer's arena is one it copies blocks of, no
        int8 arena), or `xla`, `paged_kv_write` in front of the read (and what a
        model with no K/V layer says)."""
        from trlx_tpu.ops.paged_attention import writes_in_kernel

        arenas = [layer["k"] for layer in self._pool["layers"] if "k" in layer]
        runs_kernel = self._attn_kernel is not None and self._kernel_unsupported is None
        return "kernel" if runs_kernel and arenas and all(map(writes_in_kernel, arenas)) else "xla"

    def kv_stats(self) -> Dict[str, Any]:
        """Host-side paged-pool counters for metrics/healthz; {} when
        paging is off. `kv_kernel_fallbacks` is a {reason: count} dict,
        `kv_live_entry_share` a float (live table entries over slots x
        table entries: the part of the table walk the paged kernel does
        not skip); everything else is an int."""
        if not self.kv_paging:
            return {}
        # single source of truth for arena bytes (incl. int8 scale
        # planes): observability/hbm.py — the same function the offline
        # budget checker and the live HBM ledger price the arena with
        from trlx_tpu.observability.hbm import paged_arena_bytes

        cfg = self.model_cfg
        kv_bytes = paged_arena_bytes(
            cfg, self._n_blocks, self.kv_block_size, dtype=jnp.dtype(self.kv_cache_dtype))
        walk = self._kv_walk()
        per_slot = self._slot_state_bytes_per_slot
        with self._kv_lock:
            pool = self._block_pool
            return {
                # the expert layers' dispatch counters of the last decode step
                **{f"moe_{k}": v for k, v in self._moe_stats.items()},
                "kv_walked_share": (walk["walked_full"] + walk["walked_window"] + walk["walked_latent"])
                / max(walk["resident"], 1),
                # what one token holds in the arena, over all layers (scale planes aside)
                "kv_bytes_per_token": cfg.cached_values_per_token * jnp.dtype(self.kv_cache_dtype).itemsize,
                "kv_blocks_total": pool.total,
                "kv_blocks_free": pool.available(),
                "kv_blocks_used": pool.in_use(),
                "kv_pool_bytes": int(kv_bytes),
                # what the layers keep a slot beside the arena (0 for K/V and latent layers)
                "slot_state_bytes_per_slot": per_slot,
                "slot_state_bytes": per_slot * self.num_slots,
                "prefix_cache_hits": pool.hits,
                "prefix_cache_misses": pool.misses,
                "prefix_cache_evictions": pool.evictions,
                "prefix_cache_idle_blocks": pool.cached_idle(),
                "kv_kernel_dispatches": self._kv_kernel_dispatches,
                # of those, the dispatches whose K/V layers wrote the step's keys and values
                # from the kernel itself (all of them or none: the arena's shapes decide)
                "kv_kernel_writes": self._kv_kernel_dispatches * (self._kv_write_form() == "kernel"),
                "kv_kernel_fallbacks": dict(self._kv_kernel_fallbacks),
                "kv_live_entry_share": self._live_entries() / (self.num_slots * self._n_tbl),
                # decode steps dispatched while their predecessor's outputs
                # were unfetched, of `decode_steps_total` calls of `step`;
                # slot outputs a fetch dropped because the slot had been
                # released, reclaimed or refilled since the dispatch
                "decode_steps_total": self._step_n,
                "decode_steps_ahead_total": self._steps_ahead,
                "decode_outputs_masked_total": self._outputs_masked,
            }

    # ------------------------------------------------------------------
    # Sessions (multi-turn chat: retained KV between requests)
    # ------------------------------------------------------------------

    def enable_sessions(
        self,
        ttl_s: float = 600.0,
        max_sessions: int = 256,
        bytes_budget_mb: float = 0.0,
    ):
        """Attach a `SessionStore` sharing this engine's block pool and
        KV lock. Requires kv_paging (retention IS block pinning).
        Returns the store (also kept as `self.session_store`)."""
        from trlx_tpu.inference.sessions import SessionStore

        if not self.kv_paging:
            raise ValueError("sessions require kv_paging (retained KV blocks)")
        _refuse_over_attention_kinds(self.model_cfg, "sessions (a retained prefix)")
        block_bytes = self.kv_stats()["kv_pool_bytes"] // self._n_blocks
        self.session_store = SessionStore(
            self._block_pool, self.kv_block_size, lock=self._kv_lock,
            ttl_s=ttl_s, max_sessions=max_sessions,
            bytes_budget=int(bytes_budget_mb * 1024 * 1024),
            block_bytes=block_bytes,
        )
        return self.session_store

    def retain_session(self, slot: int, session, full_ids) -> int:
        """Pin a finishing turn's leading blocks into its session.
        Driver thread only, BEFORE `reclaim_slots` — the slot's blocks
        must still hold the request's references. Returns the retained
        block count."""
        if not self.kv_paging or self.session_store is None:
            return 0
        with self._kv_lock:
            blocks = self._slot_blocks.get(int(slot))
            if not blocks:
                return 0
            return self.session_store.retain_turn(session, blocks, full_ids)

    def session_stats(self) -> Dict[str, float]:
        """Session-store counters for metrics/healthz; {} when off."""
        return self.session_store.stats() if self.session_store is not None else {}

    # ------------------------------------------------------------------
    # Multi-tenant adapter plumbing
    # ------------------------------------------------------------------

    def flush_adapter_prefixes(self, name: Optional[str]) -> int:
        """Drop one adapter's cached prefix blocks (per-adapter
        hot-reload: its K/V went stale, everyone else's is still good).
        Returns the number of keys flushed; 0 when prefix caching is off.
        The adapter's sessions reset for the same reason — their retained
        KV was written under the replaced adapter weights."""
        if self.session_store is not None:
            self.session_store.invalidate_adapter(name)
        if not self.prefix_cache:
            return 0
        with self._kv_lock:
            return self._block_pool.flush_prefix(adapter_salt(name))

    def adapter_stats(self) -> Dict[str, Any]:
        """Store counters for metrics/healthz; {} when single-tenant."""
        return self.adapter_store.stats() if self.multi_tenant else {}

    def slots_for_adapter(self, name: Optional[str]) -> List[int]:
        """Slots currently pinned to `name` (per-adapter drain)."""
        return [s for s, n in self._slot_adapter.items() if n == name]

    @property
    def active_slots(self) -> int:
        """Slots whose request still decodes, by the host's own book: a
        step behind the device, and never a wait for it (any thread may
        ask while a step is in flight)."""
        return int(self._live.sum())


def _prefill_state_form(cfg) -> str:
    """Which form a prompt's chunked recurrence runs in an insert program:
    "kernel" (`kda_chunk_fwd` a span; `kda_chunked` asks the same function
    when the program is traced) or "xla" (an SSM mixer's always)."""
    from trlx_tpu.ops.linear_attention import chunk_kernel_mode

    by_kernel = cfg.has_linear_layers and chunk_kernel_mode(cfg.n_heads, cfg.head_dim, cfg.head_dim)
    return "kernel" if by_kernel else "xla"
