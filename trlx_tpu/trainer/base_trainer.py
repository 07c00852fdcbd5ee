"""Abstract TPU trainer: one trainer family for every mesh layout.

Parity: trlx/trainer/accelerate_base_trainer.py (AccelerateRLTrainer).
Where the reference needs two backends (Accelerate for DDP/ZeRO, NeMo for
TP/PP), this single trainer covers all of DP/FSDP/TP/SP by constructing a
GSPMD mesh from config.parallel and jit-compiling one train step:

- model params live sharded on the mesh (rule table in
  trlx_tpu/parallel/sharding.py);
- frozen params (num_layers_unfrozen) are *partitioned out* of the
  optimizer: loss_fn takes (train_params, frozen_params) and grads are
  taken w.r.t. the trainable tree only — backprop below the freeze point
  is dead code XLA eliminates (the reference instead sets requires_grad
  False, utils/modeling.py:22-38);
- gradient accumulation over microbatches is two jitted fns (accumulate /
  apply) — the functional analogue of accelerate's no_sync context
  (accelerate_base_trainer.py:502-516).
"""

import json
import os
import pickle
import shutil
import time
from abc import abstractmethod
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import traverse_util

from trlx_tpu import resilience
from trlx_tpu.observability import PhaseTimeline, tracing
from trlx_tpu.sentinel import LAST_GOOD_NAME, HealthSentinel, SentinelRewind, StepWatchdog
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models import resolve_split, trainable_mask
from trlx_tpu.parallel import MeshRuntime, infer_param_shardings
from trlx_tpu.pipeline import MiniBatchIterator
from trlx_tpu.tokenizers import get_tokenizer
from trlx_tpu.trainer import BaseRLTrainer, register_trainer
from trlx_tpu.utils import Clock, get_optimizer, get_scheduler, set_seed, significant
from trlx_tpu.utils import logging
from trlx_tpu.utils.tracking import get_tracker

logger = logging.get_logger(__name__)


def partition_params(params: Dict, mask_tree: Dict) -> Tuple[Dict, Dict]:
    """Split a param tree into (trainable, frozen) flat dicts by mask."""
    flat = traverse_util.flatten_dict(params)
    flat_mask = traverse_util.flatten_dict(mask_tree)
    train = {k: v for k, v in flat.items() if flat_mask[k]}
    frozen = {k: v for k, v in flat.items() if not flat_mask[k]}
    return train, frozen


def merge_params(train: Dict, frozen: Dict) -> Dict:
    """Inverse of partition_params -> nested param tree."""
    return traverse_util.unflatten_dict({**train, **frozen})


@register_trainer
class TPUTrainer(BaseRLTrainer):
    # the family of the trainer's spans in a profiler session:
    # `trlx:<span_family>.train_minibatch` (PPOTrainer's is `ppo`)
    span_family = "train"

    def __init__(
        self,
        config: TRLConfig,
        reward_fn=None,
        metric_fn=None,
        logit_mask=None,
        stop_sequences=None,
        devices=None,
        **kwargs,
    ):
        super().__init__(
            config,
            reward_fn=reward_fn,
            metric_fn=metric_fn,
            logit_mask=logit_mask,
            stop_sequences=stop_sequences,
        )
        # Multi-host bootstrap must precede the first backend use (the
        # PRNGKey below); no-op on single-process setups.
        if devices is None:
            from trlx_tpu.parallel import initialize_distributed

            initialize_distributed()
        set_seed(config.train.seed)
        self.rng = jax.random.PRNGKey(config.train.seed)
        self.tokenizer = get_tokenizer(config.tokenizer)
        self.runtime = MeshRuntime.from_config(config.parallel, devices=devices)
        self.max_length = config.train.seq_length

        # Model + params (sharded onto the mesh by the rule table)
        self.model, self.model_cfg, params = self.get_arch(config)
        P = getattr(self.model_cfg, "prompt_tokens", 0)
        if (
            P > 0
            and getattr(self.model_cfg, "pos_embed", None) == "learned"
            and config.train.seq_length + P > self.model_cfg.max_seq_len
        ):
            # the soft prompt shifts real-token positions by P; past the
            # learned-position table the embedding gather would clamp
            # silently, so fail loudly up front
            raise ValueError(
                f"prompt_tokens={P} + train.seq_length="
                f"{config.train.seq_length} exceeds the learned-position "
                f"table ({self.model_cfg.max_seq_len}); lower seq_length by "
                "the prompt length"
            )
        self.split = resolve_split(self.model_cfg, config.model.num_layers_unfrozen)
        params = self.place_params(params)

        # Trainable/frozen partition + optimizer over the trainable tree only
        mask_tree = self.make_trainable_mask(params)
        self.train_params, self.frozen_params = partition_params(params, mask_tree)
        n_train = sum(int(np.prod(np.shape(x))) for x in self.train_params.values())
        n_total = n_train + sum(int(np.prod(np.shape(x))) for x in self.frozen_params.values())
        logger.info(f"Trainable params: {n_train:,} / {n_total:,}")

        base_lr = float(config.optimizer.kwargs.get("lr", 1e-4))
        self.lr_schedule = get_scheduler(config.scheduler.name, base_lr, config.scheduler.kwargs)
        self.optimizer = get_optimizer(config.optimizer.name, self.lr_schedule, config.optimizer.kwargs)
        self.opt_state = self.optimizer.init(self.train_params)
        # Commit every opt-state leaf: eagerly-created scalars (e.g. the
        # Adam step count) are otherwise uncommitted, and the first jitted
        # call's cache key (UnspecifiedValue) then differs from every
        # later call's (committed) — one silent full retrace of each train
        # program after its first execution.
        self.opt_state = jax.tree_util.tree_map(
            lambda x: x if getattr(x, "committed", True)
            else jax.device_put(x, self.runtime.replicated),
            self.opt_state,
        )

        # Batch/microbatch bookkeeping (reference accelerate_base_trainer.py:77-83)
        self.mb_size = config.train.minibatch_size or config.train.batch_size
        assert config.train.batch_size % self.mb_size == 0, "Minibatch size must divide batch size"
        self.num_mb = config.train.batch_size // self.mb_size

        run_name = config.train.run_name or f"{config.train.trainer}/{config.model.model_path}"
        self.tracker = get_tracker(
            config.train.tracker,
            config.to_dict(),
            run_name,
            config.train.logging_dir,
        )

        self.generate_kwargs = dict(config.method.gen_kwargs or {})
        self.generate_experience_kwargs = getattr(config.method, "gen_experience_kwargs", None)

        # A single list-valued gen kwarg becomes an eval-time sweep
        # (reference generate_sweep_kwarg, accelerate_base_trainer.py:139-146):
        # evaluate() runs once per value and logs metrics with @k=v suffixes.
        # Kwargs whose VALUE is inherently a list (HF GenerationConfig
        # list-typed fields) are exempt from sweep detection.
        LIST_TYPED = {"suppress_tokens", "begin_suppress_tokens", "bad_words_ids"}
        self.generate_sweep_kwarg = None
        for k, v in list(self.generate_kwargs.items()):
            if k in LIST_TYPED:
                continue
            if isinstance(v, list):
                if self.generate_sweep_kwarg is not None:
                    logger.info(f"Only a single sweep is allowed, {k} is going to be set to {v[0]}")
                    self.generate_kwargs[k] = v[0]
                else:
                    self.generate_sweep_kwarg = (k, v)
                    # rollout generation (non-eval) uses the first value
                    self.generate_kwargs[k] = v[0]

        self._train_step_fn = None
        self._accum_fns = None
        self._generate_cache: Dict[Any, Callable] = {}
        self.iter_count = 0
        self.nth_evaluation = 0

        # Preemption-safe resume state (trlx_tpu/resilience.py):
        # _loop_pos tracks where training would continue if restarted now
        # (epoch / inner epoch / the iter_count the current dataloader was
        # seeded at); it is saved into every checkpoint and restored into
        # _resume_pos by load() so a resumed run replays the exact same
        # shuffles and minibatch order.
        self._nan_streak = 0
        # Health sentinel (trlx_tpu/sentinel.py): built only when
        # train.sentinel is on — with it off, every code path below is
        # textually identical to the pre-sentinel trainer.
        self._sentinel = HealthSentinel.from_train_config(config.train) if config.train.sentinel else None
        self._watchdog: Optional[StepWatchdog] = None
        # injectable for tests (the default on timeout is os._exit(75))
        self._watchdog_on_timeout = None
        self._sentinel_skip_chunk = False
        # Deterministic train-side fault injection (tests/CI chaos runs):
        # assign a resilience.FaultInjector with nan_grad_steps /
        # loss_spike_steps / hang_steps before learn().
        self.fault_injector: Optional[resilience.FaultInjector] = None
        # Observability (train.tracing, default off): the phase timeline
        # collects generate/score/train-minibatch spans with first-call
        # (jit compile) time split from steady state; drained into
        # `timing/*` stats every step and written as a Chrome trace at
        # the end of learn(). _last_stats keeps the latest host-side
        # stats dict for postmortem bundles.
        self._timeline = PhaseTimeline() if config.train.tracing else None
        # Goodput ledger (rides the timeline's phase hooks): attributes
        # every wall second of learn() to a cause and computes live MFU
        # with observability/flops.py. Only exists when tracing is on.
        self._goodput = None
        # Compile ledger + HBM ledger (ISSUE 18): per-function recompile
        # accounting with retrace-storm postmortems, and device-memory
        # watermarks sampled at the same phase boundaries. Explicit
        # context objects like the tracer — None when tracing is off, and
        # every jit site then routes through plain jax.jit (bitwise
        # identical programs, pinned by tests/test_compile_hbm.py).
        self._compile_ledger = None
        self._hbm = None
        if self._timeline is not None:
            from trlx_tpu.observability.compile_ledger import CompileLedger
            from trlx_tpu.observability.goodput import GoodputLedger
            from trlx_tpu.observability.hbm import HBMLedger

            self._goodput = GoodputLedger()
            self._timeline.ledger = self._goodput
            self._compile_ledger = CompileLedger(
                postmortem_dir=config.train.postmortem_dir,
                config=config.to_dict() if hasattr(config, "to_dict") else None,
            )
            for fn_name, budget in (config.train.compile_budgets or {}).items():
                self._compile_ledger.declare_budget(fn_name, budget)
            self._hbm = HBMLedger()
            self._timeline.hbm = self._hbm
        # Opt-in persistent compilation cache: programs compiled by this
        # (and any later) run of the same config are reloaded instead of
        # recompiled; hits/misses show up in the compile ledger.
        if config.train.compilation_cache_dir:
            jax.config.update("jax_compilation_cache_dir",
                              config.train.compilation_cache_dir)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
        self._last_stats: Dict[str, Any] = {}
        self._loop_pos: Optional[Dict[str, int]] = None
        self._resume_pos: Optional[Dict[str, int]] = None
        self._resume_dir: Optional[str] = None
        self._resumed = False
        self._preemption_guard: Optional[resilience.PreemptionGuard] = None
        self._best_reward = -float("inf")

    # ------------------------------------------------------------------
    # Abstract surface (same contract as the reference's AccelerateRLTrainer)
    # ------------------------------------------------------------------

    @abstractmethod
    def get_arch(self, config: TRLConfig):
        """Returns (flax module, TransformerConfig, initialized params)."""

    @abstractmethod
    def make_loss_fn(self) -> Callable:
        """Returns a pure fn(train_params, frozen_params, batch) ->
        (loss, stats) suitable for jit."""

    @abstractmethod
    def prepare_learning(self):
        """Set self.train_dataloader, self.eval_dataloader,
        self.n_inner_epochs, self.total_steps."""

    @abstractmethod
    def create_train_dataloader(self, seed_offset: int = 0):
        """Fresh (re-shuffled) loader over the training store; the fused
        epoch paths pass seed_offset to distinguish epochs created up
        front."""

    def place_params(self, params) -> Dict:
        """Device-place the initialized params (rule-table GSPMD sharding;
        pipelined trainers override with their stacked layout)."""
        from trlx_tpu.parallel.mesh import PipeMeshRuntime

        if isinstance(self.runtime, PipeMeshRuntime):
            raise NotImplementedError(
                f"parallel.pipeline > 1 requires a pipeline-aware trainer "
                f"(train.trainer: PipelinedSFTTrainer), not "
                f"{type(self).__name__}; or use data/fsdp/tensor/sequence "
                "axes with this trainer"
            )
        self.param_shardings = infer_param_shardings(self.runtime.mesh, params)
        return jax.tree_util.tree_map(jax.device_put, params, self.param_shardings)

    def make_trainable_mask(self, params) -> Dict:
        return trainable_mask(params, self.model_cfg, self.config.model.num_layers_unfrozen)

    def make_update_mask(self) -> Optional[Dict]:
        """Optional {flat_key: 0/1 array} multiplied onto optimizer UPDATES
        for train_params leaves that are only partially trainable (a freeze
        boundary cutting through a stacked-layer leaf — pipelined trainers).
        Grads through such layers are already cut in-graph; this stops
        grad-independent optimizer terms (AdamW weight decay) from moving
        the frozen slices. None = no masking (every plain layout)."""
        return None

    def post_backward_callback(self):
        pass

    def post_epoch_callback(self):
        pass

    # ------------------------------------------------------------------
    # Params / generation / decode helpers
    # ------------------------------------------------------------------

    @property
    def params(self) -> Dict:
        """Full (merged) param tree."""
        return merge_params(self.train_params, self.frozen_params)

    def serving_params(self) -> Dict:
        """Param tree safe to hand to a long-lived consumer (an inference
        engine held by an in-process replica): the jitted train step
        DONATES train_params on every optimizer step, so anything that
        keeps aliases to those buffers reads deleted arrays one update
        later. Trainable leaves are copied; the frozen trunk is never
        donated and stays shared live."""
        train_copy = jax.tree_util.tree_map(jnp.copy, self.train_params)
        return merge_params(train_copy, self.frozen_params)

    def next_rng(self) -> jax.Array:
        self.rng, key = jax.random.split(self.rng)
        # IDENTICAL across hosts, deliberately: every host runs the same
        # global SPMD program over one global batch, so the key must agree
        # (differing per-host args to a multi-host jit are undefined).
        # Sampling diversity across data-parallel shards comes from batch
        # POSITION inside the jitted sampler, not from per-rank keys — the
        # reference's per-DP-rank fold (modeling_nemo_ppo.py:384-393)
        # exists because its ranks run separate per-rank sampling loops,
        # which this design doesn't have.
        return key

    def _ljit(self, fn, name: str, budget: int = 1, **jit_kwargs):
        """The trainer's jit entry point: plain `jax.jit` when the
        compile ledger is off (`train.tracing` unset — identical
        programs), ledgered otherwise. Every jit site below routes
        through here so each compiled function has a name and a declared
        recompile budget (docs/observability.md lists them)."""
        from trlx_tpu.observability.compile_ledger import ledgered_jit

        return ledgered_jit(fn, name=name, budget=budget,
                            ledger=self._compile_ledger, **jit_kwargs)

    def get_generate_fn(self, batch_size: int, prompt_len: int, gen_kwargs: Dict, mode: str = "lm",
                        capture: bool = False):
        """Jit-cached generate fn per (shape, kwargs) bucket. `capture`
        builds the rollout fast-path sampler, which additionally emits
        per-token logprobs/values and the hydra-split activations (see
        ops/sampling.py)."""
        from trlx_tpu.ops.sampling import make_generate_fn

        # repr-normalize values: gen_kwargs may carry unhashable HF-style
        # knobs (lists/dicts) from configs written against the reference
        block = self._prefill_block()
        key = (batch_size, prompt_len, repr(sorted(gen_kwargs.items())), mode, bool(capture), block)
        if key not in self._generate_cache:
            gen_cfg = self._generation_config(gen_kwargs)
            two_qs = bool(getattr(self.config.method, "two_qs", True))
            fn = make_generate_fn(
                self.model, self.model_cfg, gen_cfg, mode=mode,
                logit_mask=self.logit_mask, two_qs=two_qs,
                capture=capture, capture_split=self.split if capture else 0,
                prefill_block=block,
            )
            # each (shape, kwargs) bucket is its own compiled program by
            # design — name it as such so each gets a budget of 1 and a
            # retrace WITHIN a bucket (the actual invariant) still fires
            import hashlib

            kw_tag = hashlib.md5(key[2].encode()).hexdigest()[:6]
            fn_name = (
                f"generate[b{batch_size},p{prompt_len},{mode}"
                + (",cap" if capture else "")
                + f",kw{kw_tag}]"
            )
            self._generate_cache[key] = self._ljit(fn, fn_name)
        return self._generate_cache[key]

    def _decode_params(self) -> Dict:
        """Param view fed to the sampler. The base view is the merged
        train+frozen tree; trainers that enable
        method.quantize_frozen_trunk override this with the int8
        frozen-trunk view (ppo_trainer). Train/score paths never call
        this."""
        return self.params

    def _bucket_shape(self, rows: int, width: int) -> Tuple[int, int]:
        """The (rows, prompt width) `generate` runs a batch of that shape at."""
        if not getattr(self.config.train, "bucket_generation", True):
            return rows, width
        return -(-rows // 8) * 8, -(-width // 32) * 32

    def _bucket_prompts(self, input_ids, attention_mask):
        """Round the generate batch up to a multiple of 8 rows and the
        prompt width up to a multiple of 32 columns, so ragged eval tails
        and RFT chunks reuse one compiled program per BUCKET instead of
        triggering a multi-second compile per exact shape (VERDICT r1
        weak #5). Row padding repeats row 0 (a real prompt — fully-masked
        rows are avoided); column padding adds masked pad tokens on the
        tokenizer's padding side, which attention ignores. Returns
        (ids, mask, (true_rows, left_col_pad)); `_unbucket_output` undoes
        both. Disable with train.bucket_generation = False."""
        b, t = input_ids.shape
        bb, tb = self._bucket_shape(b, t)
        if (bb, tb) == (b, t):
            return input_ids, attention_mask, (b, 0)
        pad_id = self.tokenizer.pad_token_id
        left = self.config.tokenizer.padding_side == "left"
        ids = np.full((bb, tb), pad_id, dtype=np.asarray(input_ids).dtype)
        mask = np.zeros((bb, tb), dtype=np.asarray(attention_mask).dtype)
        col = slice(tb - t, tb) if left else slice(0, t)
        ids[:b, col] = input_ids
        mask[:b, col] = attention_mask
        ids[b:] = ids[0]
        mask[b:] = mask[0]
        return ids, mask, (b, tb - t if left else 0)

    def _unbucket_output(self, out: Dict, orig) -> Dict:
        b, col_pad = orig
        trimmed = {}
        for k, v in out.items():
            if hasattr(v, "ndim") and v.ndim >= 1 and v.shape[0] >= b:
                v = v[:b]
                if col_pad and k in ("samples", "samples_mask", "h_split"):
                    v = v[:, col_pad:]
            trimmed[k] = v
        return trimmed

    def generate(self, input_ids, attention_mask, gen_kwargs: Optional[Dict] = None, mode: str = "lm",
                 capture: bool = False):
        """Sample continuations for a (host) prompt batch; returns the
        sampling dict (device arrays)."""
        gen_kwargs = gen_kwargs if gen_kwargs is not None else self.generate_kwargs
        input_ids = np.asarray(input_ids)
        attention_mask = np.asarray(attention_mask)
        if getattr(self.config.train, "bucket_generation", True):
            input_ids, attention_mask, orig = self._bucket_prompts(input_ids, attention_mask)
            if self.config.model.model_arch_type == "seq2seq":
                # seq2seq samples are decoder-side only — never trim the
                # encoder's column padding off them
                orig = (orig[0], 0)
        else:
            orig = (input_ids.shape[0], 0)
        fn = self.get_generate_fn(input_ids.shape[0], input_ids.shape[1], gen_kwargs, mode,
                                  capture=capture)
        out = fn(self._decode_params(), jnp.asarray(input_ids), jnp.asarray(attention_mask),
                 self.next_rng())
        return self._unbucket_output(out, orig)

    def decode(
        self,
        prompts,
        samples,
        prompt_sizes=None,
        append_eos_token: bool = False,
    ) -> Tuple[List[str], List[str], List[str]]:
        """Token->string decode with stop-sequence trimming and eos
        restoration (reference accelerate_base_trainer.py:203-254)."""
        prompts = np.asarray(prompts)
        samples = np.asarray(samples)
        if prompt_sizes is None:
            prompt_sizes = [prompts.shape[1]] * len(prompts)

        str_samples, str_prompts, str_outputs = [], [], []
        for prompt, sample, prompt_size in zip(prompts, samples, prompt_sizes):
            output_start_ix = 0 if self.config.model.model_arch_type == "seq2seq" else prompt_size
            str_prompt = self.tokenizer.decode(prompt[:prompt_size], skip_special_tokens=True)
            str_output = self.tokenizer.decode(sample[output_start_ix:], skip_special_tokens=True)

            trimmed = False
            if self.stop_sequences:
                for stop in self.stop_sequences:
                    stop_ix = str_output.find(stop)
                    if stop_ix >= 0:
                        str_output = str_output[:stop_ix].rstrip()
                        trimmed = True

            # Restore the trailing eos unless generation ran out of budget
            if append_eos_token and (
                trimmed
                or sample[-1] == self.tokenizer.eos_token_id
                or sample[-1] == self.tokenizer.pad_token_id
            ):
                str_output += self.tokenizer.eos_token

            str_prompts.append(str_prompt)
            str_outputs.append(str_output)
            if self.config.model.model_arch_type == "seq2seq":
                sep = getattr(self.tokenizer, "sep_token", "") or ""
                str_samples.append(str_prompt + sep + str_output)
            else:
                str_samples.append(str_prompt + str_output)

        return str_samples, str_prompts, str_outputs

    # ------------------------------------------------------------------
    # Serving (trlx_tpu/inference/): expose the policy as a service
    # ------------------------------------------------------------------

    def serve(self, host: Optional[str] = None, port: Optional[int] = None,
              watch_dir: Optional[str] = None, background: bool = False):
        """Serve the current policy through the continuous-batching
        inference server (config section: `inference`). Generation knobs
        come from the method's gen_kwargs overlaid with
        `inference.gen_kwargs`; `inference.max_new_tokens` caps the
        per-request budget and sizes the KV slot pool.

        With `watch_dir` (or `inference.watch_dir`) the server hot-reloads
        the newest manifest-complete checkpoint from a live training run.
        `background=True` starts a daemon thread and returns the
        `InferenceServer` (its `.url` is the base endpoint); otherwise
        this blocks serving forever."""
        from trlx_tpu.inference import (
            AdapterStore,
            InferenceEngine,
            InferenceServer,
            Scheduler,
        )
        from trlx_tpu.ops.sampling import GenerationConfig

        icfg = self.config.inference
        gen_kwargs = {**self.generate_kwargs, **(icfg.gen_kwargs or {})}
        gen_kwargs.setdefault("max_new_tokens", icfg.max_new_tokens)
        gen_kwargs["max_new_tokens"] = min(
            int(gen_kwargs["max_new_tokens"]), icfg.max_new_tokens
        )
        gen_cfg = GenerationConfig.from_gen_kwargs(
            gen_kwargs, self.tokenizer.eos_token_id, self.tokenizer.pad_token_id
        )
        adapter_store = None
        if icfg.multi_tenant:
            # the serving params only donate LoRA leaf paths/shapes to the
            # store; multi-tenant programs read factors from the stack
            # (slot 0 = zeros = base policy), never from the param leaves
            adapter_store = AdapterStore(
                self.serving_params(),
                adapter_dir=icfg.adapter_dir,
                max_resident=icfg.max_resident_adapters,
                hbm_budget_bytes=int(icfg.adapter_hbm_budget_mb * 1024 * 1024),
            )
        serve_compile_ledger = serve_hbm = None
        if icfg.tracing:
            from trlx_tpu.observability.compile_ledger import CompileLedger
            from trlx_tpu.observability.hbm import HBMLedger

            serve_compile_ledger = CompileLedger()
            serve_hbm = HBMLedger()
        engine = InferenceEngine(
            self.model, self.model_cfg, self.serving_params(), gen_cfg,
            num_slots=icfg.num_slots,
            max_prompt_len=icfg.max_prompt_len,
            max_prefill_batch=icfg.max_prefill_batch,
            prompt_bucket=icfg.prompt_bucket,
            seed=self.config.train.seed,
            kv_paging=icfg.kv_paging,
            kv_block_size=icfg.kv_block_size,
            kv_pool_blocks=icfg.kv_pool_blocks,
            kv_cache_dtype=icfg.kv_cache_dtype,
            prefix_cache=icfg.prefix_cache,
            prefix_cache_capacity=icfg.prefix_cache_capacity,
            multi_tenant=icfg.multi_tenant,
            adapter_store=adapter_store,
            decode_kernel=icfg.decode_kernel,
            compile_ledger=serve_compile_ledger,
            hbm_ledger=serve_hbm,
        )
        if icfg.sessions:
            engine.enable_sessions(
                ttl_s=icfg.session_ttl_s,
                max_sessions=icfg.session_max,
                bytes_budget_mb=icfg.session_bytes_budget_mb,
            )
        tracer = recorder = None
        if icfg.tracing:
            from trlx_tpu.observability import FlightRecorder, Tracer

            tracer = Tracer(
                max_traces=icfg.trace_ring,
                sample_rate=icfg.trace_sample_rate,
            )
            recorder = FlightRecorder("scheduler", icfg.flight_recorder_events)
        scheduler = Scheduler(
            engine,
            max_queue_depth=icfg.max_queue_depth,
            max_wait_s=icfg.max_wait_s,
            default_deadline_s=icfg.default_deadline_s,
            fair_share=icfg.fair_share and icfg.multi_tenant,
            tenant_weights=icfg.tenant_weights,
            tenant_queue_depth=icfg.tenant_queue_depth,
            tracer=tracer,
            recorder=recorder,
        )
        server = InferenceServer(
            scheduler,
            tokenizer=self.tokenizer,
            host=host if host is not None else icfg.host,
            port=port if port is not None else icfg.port,
            watch_dir=watch_dir if watch_dir is not None else icfg.watch_dir,
            reload_interval_s=icfg.reload_interval_s,
        )
        if background:
            server.start_background()
            return server
        server.serve()
        return server

    # ------------------------------------------------------------------
    # Train step (jit) with gradient accumulation
    # ------------------------------------------------------------------

    def make_grad_fn(self):
        """(train_params, frozen_params, batch) -> (loss, stats, grads).
        Default: autodiff of make_loss_fn. Trainers with a hand-written
        backward (the 1F1B pipeline schedule) override this instead of
        make_loss_fn."""
        loss_fn = self.make_loss_fn()

        def grad_fn(train_params, frozen_params, batch):
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                train_params, frozen_params, batch
            )
            return loss, stats, grads

        return grad_fn

    def _build_steps(self):
        grad_fn = self.make_grad_fn()
        optimizer = self.optimizer
        update_mask = self.make_update_mask()

        def masked(updates):
            if update_mask is None:
                return updates
            return {
                k: (u * update_mask[k] if k in update_mask else u)
                for k, u in updates.items()
            }

        # Pin param/opt-state outputs to their current (input) shardings:
        # otherwise the compiler may hand donated outputs back with
        # different layouts, and the NEXT call retraces — one silent extra
        # multi-second compile per program.
        train_sh = jax.tree_util.tree_map(lambda x: x.sharding, self.train_params)
        opt_sh = jax.tree_util.tree_map(lambda x: x.sharding, self.opt_state)
        self._state_shardings = (train_sh, opt_sh)

        def pin(train_params, opt_state):
            return (
                jax.lax.with_sharding_constraint(train_params, train_sh),
                jax.lax.with_sharding_constraint(opt_state, opt_sh),
            )

        def train_step(train_params, frozen_params, opt_state, batch):
            _, stats, grads = grad_fn(train_params, frozen_params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, train_params)
            train_params = optax.apply_updates(train_params, masked(updates))
            train_params, opt_state = pin(train_params, opt_state)
            return train_params, opt_state, stats

        def accum_step(train_params, frozen_params, acc_grads, batch):
            _, stats, grads = grad_fn(train_params, frozen_params, batch)
            acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
            return acc_grads, stats

        def apply_step(train_params, opt_state, acc_grads):
            grads = jax.tree_util.tree_map(lambda g: g / self.num_mb, acc_grads)
            updates, opt_state = optimizer.update(grads, opt_state, train_params)
            train_params = optax.apply_updates(train_params, masked(updates))
            train_params, opt_state = pin(train_params, opt_state)
            return train_params, opt_state

        def train_scan(train_params, frozen_params, opt_state, stacked_batches):
            """N optimizer steps in one compiled program: lax.scan over the
            stacked minibatches (one dispatch per inner epoch instead of
            one per step; the functional analogue has no reference
            equivalent — torch must step the optimizer from Python)."""

            stacked_batches, rejoin = self._split_shared(stacked_batches)

            def body(carry, batch):
                train_params, opt_state = carry
                _, stats, grads = grad_fn(train_params, frozen_params, rejoin(batch))
                updates, opt_state = optimizer.update(grads, opt_state, train_params)
                train_params = optax.apply_updates(train_params, masked(updates))
                return (train_params, opt_state), stats

            (train_params, opt_state), stats = jax.lax.scan(
                body, (train_params, opt_state), stacked_batches
            )
            mean_stats = jax.tree_util.tree_map(lambda s: s.mean(0), stats)
            train_params, opt_state = pin(train_params, opt_state)
            return train_params, opt_state, mean_stats

        if self._sentinel is not None:
            # In-jit gradient guard (sentinel layer 1): the global grad
            # norm is computed inside the compiled step and a non-finite
            # (or over-threshold) step is masked with jnp.where — params
            # and opt state pass through unchanged, with no recompile and
            # no host round trip. `lr_scale` is a traced weak-typed scalar
            # (cooldown damping after a rewind), so changing its value
            # never retraces; on a clean step with lr_scale=1.0 both
            # `u * 1.0` and `where(True, new, old)` are bitwise exact, so
            # sentinel-on-but-clean training matches sentinel-off bit for
            # bit. The guarded fns replace the plain ones wholesale — with
            # the flag off the graphs above compile exactly as before.
            threshold = self.config.train.grad_skip_threshold

            def guarded_update(grads, opt_state, train_params, lr_scale):
                gnorm = optax.global_norm(grads)
                ok = jnp.isfinite(gnorm)
                if threshold is not None:
                    ok = ok & (gnorm <= threshold)
                updates, new_opt = optimizer.update(grads, opt_state, train_params)
                updates = jax.tree_util.tree_map(
                    lambda u: jnp.where(ok, u * lr_scale, jnp.zeros_like(u)),
                    masked(updates),
                )
                # a skipped step must not advance Adam moments/count either
                new_opt = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), new_opt, opt_state
                )
                train_params = optax.apply_updates(train_params, updates)
                guard_stats = {
                    "grad_global_norm": gnorm,
                    "skipped_updates": 1.0 - ok.astype(jnp.float32),
                }
                return train_params, new_opt, guard_stats

            def train_step(train_params, frozen_params, opt_state, batch, lr_scale):
                _, stats, grads = grad_fn(train_params, frozen_params, batch)
                train_params, opt_state, guard_stats = guarded_update(
                    grads, opt_state, train_params, lr_scale
                )
                train_params, opt_state = pin(train_params, opt_state)
                stats = dict(stats)
                stats["train"] = guard_stats
                return train_params, opt_state, stats

            def apply_step(train_params, opt_state, acc_grads, lr_scale):
                grads = jax.tree_util.tree_map(lambda g: g / self.num_mb, acc_grads)
                train_params, opt_state, guard_stats = guarded_update(
                    grads, opt_state, train_params, lr_scale
                )
                train_params, opt_state = pin(train_params, opt_state)
                return train_params, opt_state, guard_stats

            def train_scan(train_params, frozen_params, opt_state, stacked_batches, lr_scale):
                stacked_batches, rejoin = self._split_shared(stacked_batches)

                def body(carry, batch):
                    train_params, opt_state = carry
                    _, stats, grads = grad_fn(train_params, frozen_params, rejoin(batch))
                    train_params, opt_state, guard_stats = guarded_update(
                        grads, opt_state, train_params, lr_scale
                    )
                    stats = dict(stats)
                    stats["train"] = guard_stats
                    return (train_params, opt_state), stats

                (train_params, opt_state), stats = jax.lax.scan(
                    body, (train_params, opt_state), stacked_batches
                )
                mean_stats = jax.tree_util.tree_map(lambda s: s.mean(0), stats)
                train_params, opt_state = pin(train_params, opt_state)
                return train_params, opt_state, mean_stats

        self._train_step_fn = self._ljit(
            train_step, "train_step", donate_argnums=(0, 2))
        self._train_scan_fn = self._ljit(
            train_scan, "train_scan", donate_argnums=(0, 2))
        self._accum_fns = (
            self._ljit(accum_step, "accum_step", donate_argnums=(2,)),
            self._ljit(apply_step, "apply_step", donate_argnums=(0, 1, 2)),
        )

    def batch_to_device(self, batch):
        """Place a host batch onto the mesh, batch-dim sharded over DP axes."""
        return self._bind_shared(self.runtime.shard_batch(batch))

    def _bind_shared(self, batch):
        """A placed batch (one step's, or a [n_steps, batch, ...] stack) with
        what every step of the cycle shares and no collator carries: nothing
        here; the PPO trainer's cycle-wide trunk cache."""
        return batch

    def _split_shared(self, stacked_batches):
        """(what `train_scan` walks step by step, how a step's slice gets
        back what `_bind_shared` put on the stack)."""
        return stacked_batches, lambda batch: batch

    def _normalize_state_shardings(self):
        """Re-commit train state to the canonical sharding objects. Jitted
        outputs can come back with equivalent-but-differently-expressed
        NamedShardings; since jit caches key on the sharding OBJECTS, the
        next call would silently retrace (a multi-second compile per
        train program). device_put to an equivalent sharding is free."""
        train_sh, opt_sh = self._state_shardings
        self.train_params = jax.device_put(self.train_params, train_sh)
        self.opt_state = jax.device_put(self.opt_state, opt_sh)

    def _sentinel_args(self) -> Tuple:
        """Extra traced args for the guarded train fns: the cooldown LR
        scale (a plain Python float — weak-typed, so value changes never
        retrace and bf16 updates stay bf16). Empty with the sentinel off,
        so every call site can splat it unconditionally."""
        if self._sentinel is None:
            return ()
        return (float(self._sentinel.lr_scale(self.iter_count)),)

    def _maybe_inject_train_fault(self, minibatch: List[Any]) -> List[Any]:
        """Apply a scheduled train-side fault (resilience.FaultInjector)
        to this step's microbatches; no-op without an injector."""
        if self.fault_injector is None:
            return minibatch
        fault = self.fault_injector.train_fault(self.iter_count)
        if fault is None:
            return minibatch
        logger.warning(f"FaultInjector: injecting '{fault}' at step {self.iter_count}")
        self.fault_injector.maybe_hang(fault)
        if fault == "hang":
            return minibatch
        return [self.fault_injector.poison_batch(mb, fault) for mb in minibatch]

    def _observability_extra(self) -> Dict[str, Any]:
        """Compile/HBM ledger snapshots riding goodput.json ({} with the
        ledgers off)."""
        extra: Dict[str, Any] = {}
        if self._compile_ledger is not None:
            extra["compile"] = self._compile_ledger.snapshot()
        if self._hbm is not None:
            extra["hbm"] = self._hbm.snapshot()
        return extra

    def _maybe_oom_postmortem(self, site: str, exc: BaseException) -> None:
        """OOM forensics at the train-step boundary: a RESOURCE_EXHAUSTED
        escaping a train dispatch dumps a memory postmortem (ledger
        snapshot, compile history, largest live buffers) once per site
        before re-raising. Non-OOM errors pass through untouched; the
        probe is one string match, so the happy path pays nothing."""
        from trlx_tpu.observability.hbm import is_oom_error, oom_postmortem

        if not is_oom_error(exc):
            return
        oom_postmortem(
            site, exc, hbm=self._hbm, compile_ledger=self._compile_ledger,
            context={"iter_count": self.iter_count,
                     "last_stats_keys": sorted(self._last_stats)[:64]},
            config=self.config.to_dict(),
            out_dir=self.config.train.postmortem_dir,
        )

    def train_minibatch(self, minibatch: List[Any]) -> Dict[str, float]:
        """One optimizer step over `num_mb` microbatches (the dispatch: the
        stats come back as device arrays). OOM-guarded: a RESOURCE_EXHAUSTED
        here leaves a memory postmortem bundle."""
        try:
            with self._span(f"{self.span_family}.train_minibatch", phase="train_minibatch",
                            step=self.iter_count):
                return self._train_minibatch_impl(minibatch)
        except Exception as e:
            self._maybe_oom_postmortem("train_step", e)
            raise

    def _span(self, name: str, phase: Optional[str] = None, **attrs):
        """One site, every sink: the `trlx:<name>` span of a profiler session
        (observability/tracing.py), the site's seconds (`.seconds` after the
        block), and with `train.tracing` on a phase `phase` on the timeline
        (and through it the goodput and HBM ledgers)."""
        return tracing.timed_span(
            name, timeline=self._timeline if phase else None, phase=phase, **attrs)

    def _train_minibatch_impl(self, minibatch: List[Any]) -> Dict[str, float]:
        if self._train_step_fn is None:
            self._build_steps()
        minibatch = self._maybe_inject_train_fault(minibatch)
        if len(minibatch) == 1:
            self.train_params, self.opt_state, stats = self._train_step_fn(
                self.train_params, self.frozen_params, self.opt_state,
                self.batch_to_device(minibatch[0]), *self._sentinel_args(),
            )
            self._normalize_state_shardings()
            return stats
        accum, apply = self._accum_fns
        acc = jax.tree_util.tree_map(jnp.zeros_like, self.train_params)
        stats_list = []
        for mb in minibatch:
            acc, stats = accum(self.train_params, self.frozen_params, acc, self.batch_to_device(mb))
            stats_list.append(stats)
        guard_stats = None
        if self._sentinel is not None:
            self.train_params, self.opt_state, guard_stats = apply(
                self.train_params, self.opt_state, acc, *self._sentinel_args()
            )
        else:
            self.train_params, self.opt_state = apply(self.train_params, self.opt_state, acc)
        self._normalize_state_shardings()
        # average stats across microbatches (reference
        # accelerate_base_trainer.py:580-583)
        stats = jax.tree_util.tree_map(lambda *xs: sum(xs) / len(xs), *stats_list)
        if guard_stats is not None:
            stats = dict(stats)
            stats["train"] = guard_stats
        return stats

    def train_inner_epoch_fused(self, train_dataloader) -> Tuple[Dict[str, float], int]:
        """Run one inner epoch's optimizer steps as a single jitted
        lax.scan dispatch. Returns (epoch-mean stats, n_steps)."""
        batches = [b for mb in MiniBatchIterator(train_dataloader, self.mb_size, self.num_mb)
                   for b in mb]
        return self.train_batches_fused(batches)

    def train_inner_epochs_fused(self, dataloaders) -> Tuple[Dict[str, float], int]:
        """ALL inner epochs' optimizer steps in one lax.scan dispatch
        (config.train.fuse_all_inner_epochs): on dispatch-latency-bound
        runtimes every avoided dispatch is won wall-clock."""
        batches = [
            b
            for dl in dataloaders
            for mb in MiniBatchIterator(dl, self.mb_size, self.num_mb)
            for b in mb
        ]
        return self.train_batches_fused(batches)

    def train_batches_fused(self, batches) -> Tuple[Dict[str, float], int]:
        """Scan the train step over a homogeneous-shape batch prefix in one
        dispatch; a ragged tail falls back to per-step dispatch.
        OOM-guarded like `train_minibatch`."""
        try:
            return self._train_batches_fused_impl(batches)
        except Exception as e:
            self._maybe_oom_postmortem("train_step_fused", e)
            raise

    def _train_batches_fused_impl(self, batches) -> Tuple[Dict[str, float], int]:
        if self._train_step_fn is None:
            self._build_steps()
        if not batches:
            return {}, 0
        # Group maximal runs of same-shape batches: each multi-batch run is
        # one lax.scan dispatch; singletons (e.g. a ragged per-epoch tail
        # between full-size epochs) dispatch per step. A prefix-only split
        # would demote every batch after the first ragged one.
        runs: List[List[Any]] = []
        for b in batches:
            if runs and _batch_shapes(b) == _batch_shapes(runs[-1][0]):
                runs[-1].append(b)
            else:
                runs.append([b])

        all_stats = []  # (stats pytree, weight)
        for run in runs:
            if len(run) > 1:
                stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *run)
                stacked = self._bind_shared(self.runtime.shard_batch_stacked(stacked))
                self.train_params, self.opt_state, stats = self._train_scan_fn(
                    self.train_params, self.frozen_params, self.opt_state, stacked,
                    *self._sentinel_args(),
                )
                all_stats.append((stats, len(run)))
            else:
                self.train_params, self.opt_state, stats = self._train_step_fn(
                    self.train_params, self.frozen_params, self.opt_state,
                    self.batch_to_device(run[0]), *self._sentinel_args(),
                )
                all_stats.append((stats, 1))
        self._normalize_state_shardings()
        n_steps = len(batches)
        if len(all_stats) == 1:  # no ragged tail: scan stats are the epoch mean
            return all_stats[0][0], n_steps
        mean_stats = jax.tree_util.tree_map(
            lambda *xs: sum(x * w for x, (_, w) in zip(xs, all_stats)) / n_steps,
            *[s for s, _ in all_stats],
        )
        return mean_stats, n_steps

    # ------------------------------------------------------------------
    # Learn / evaluate / checkpoints
    # ------------------------------------------------------------------

    def _resolve_resume_checkpoint(self) -> Optional[str]:
        """Explicit `train.resume_from_checkpoint` wins; otherwise, with
        `train.auto_resume`, scan `checkpoint_dir` for the newest
        manifest-complete checkpoint (truncated ones are skipped in favor
        of the previous valid one)."""
        cfg = self.config.train
        if cfg.resume_from_checkpoint:
            if os.path.exists(cfg.resume_from_checkpoint):
                return os.path.abspath(cfg.resume_from_checkpoint)
            logger.warning(
                f"resume_from_checkpoint={cfg.resume_from_checkpoint} does "
                "not exist; starting fresh"
            )
        if cfg.auto_resume:
            found = resilience.find_latest_valid_checkpoint(cfg.checkpoint_dir)
            if found:
                logger.info(f"auto_resume: continuing from {found}")
            else:
                logger.info(
                    f"auto_resume: no valid checkpoint under "
                    f"'{cfg.checkpoint_dir}'; starting fresh"
                )
            return found
        return None

    def learn(self):
        """Outer loop (reference accelerate_base_trainer.py:518-652), with
        preemption handling: SIGTERM/SIGINT requests an emergency
        checkpoint at the next step boundary, after which the process
        exits with resilience.PREEMPTION_EXIT_CODE so schedulers can
        restart it (train.auto_resume picks the run back up)."""
        logger.info("Starting training")
        self.iter_count = 0
        self.nth_evaluation = 0
        self._loop_pos = None
        self._resume_pos = None
        self._best_reward = -float("inf")
        self._resumed = False
        self._resume_dir = self._resolve_resume_checkpoint()
        if self._resume_dir:
            # load() BEFORE prepare_learning so restored state (RNG, step,
            # rollout store) feeds experience collection and loader seeds
            self.load(self._resume_dir)
            self._resumed = True
        self.prepare_learning()

        if not self._resumed:
            results = self.evaluate()
            self.tracker.log(results, step=self.iter_count)
        # on resume the initial eval is skipped: it would consume PRNG
        # splits the uninterrupted run never drew, breaking bit-identical
        # continuation (it was already logged before the preemption)

        clock = Clock()
        guard = None
        if self.config.train.handle_preemption:
            guard = resilience.PreemptionGuard().install()
        self._preemption_guard = guard
        if self.config.train.step_timeout_s:
            # hang watchdog (sentinel layer 4): beats arrive at step
            # boundaries and per rollout chunk; a wedged step dumps all
            # thread stacks and exits 75 so auto_resume takes over
            self._watchdog = StepWatchdog(
                self.config.train.step_timeout_s,
                on_timeout=self._watchdog_on_timeout,
                on_fire=self._watchdog_postmortem,
            ).start()

        try:
            while True:
                try:
                    return self._learn_loop(self._best_reward, clock)
                except SentinelRewind as e:
                    # sentinel layer 3: restore the pinned last_good
                    # checkpoint and continue past the offending chunk
                    self._sentinel_rewind(e)
        except resilience.PreemptionInterrupt as e:
            logger.warning(
                f"Preempted (signal {e.signum}); emergency checkpoint at "
                f"step {self.iter_count} under "
                f"'{self.config.train.checkpoint_dir}'. Exiting with code "
                f"{resilience.PREEMPTION_EXIT_CODE}."
            )
            raise SystemExit(resilience.PREEMPTION_EXIT_CODE) from e
        finally:
            if guard is not None:
                guard.uninstall()
            self._preemption_guard = None
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            # a trainer-launched rollout fleet (rollout_fleet_supervised)
            # must not outlive learn(): stop supervision, kill replicas,
            # close the router
            shutdown_fleet = getattr(self, "shutdown_rollout_fleet", None)
            if shutdown_fleet is not None:
                shutdown_fleet()
            if getattr(self, "_profiling", False):
                tracing.stop()
                self._profiling = False
            if self._timeline is not None:
                trace_dir = self.config.train.trace_dir or "logs/traces"
                try:
                    path = self._timeline.write(
                        os.path.join(trace_dir, "train_timeline.json")
                    )
                    logger.info(f"Phase timeline (Perfetto) written to {path}")
                except Exception:
                    logger.exception("failed to write the phase timeline")
            if self._goodput is not None:
                try:
                    path = self._goodput.write(os.path.join(
                        self.config.train.trace_dir or "logs/traces",
                        "goodput.json"), extra=self._observability_extra())
                    logger.info(f"Goodput ledger written to {path}")
                except Exception:
                    logger.exception("failed to write the goodput ledger")

    def _next_pos(self, epoch_idx: int, inner_idx: int) -> Dict[str, int]:
        """Continuation position AFTER inner epoch (epoch_idx, inner_idx)
        completes, with the current iter_count as the next loader seed."""
        inner_idx += 1
        if inner_idx >= self.n_inner_epochs:
            return {"epoch": epoch_idx + 1, "inner": 0, "epoch_start_iter": self.iter_count}
        return {"epoch": epoch_idx, "inner": inner_idx, "epoch_start_iter": self.iter_count}

    def _learn_loop(self, best_reward, clock):
        results = {}
        fuse = self.config.train.fuse_inner_epoch and self.num_mb == 1
        fuse_all = self.config.train.fuse_all_inner_epochs and self.num_mb == 1
        # Exact resume: pos carries (epoch, inner epoch, and the iter_count
        # the interrupted inner epoch's dataloader was seeded at); already-
        # consumed minibatches = iter_count - epoch_start_iter are skipped
        # so the continuation replays the original shuffle and order.
        pos = self._resume_pos
        self._resume_pos = None
        start_epoch = pos["epoch"] if pos else 0
        if pos:
            logger.info(
                f"Resuming at epoch {pos['epoch']}, inner epoch "
                f"{pos['inner']}, step {self.iter_count}"
            )
            if fuse_all and (
                pos["inner"] or self.iter_count != pos["epoch_start_iter"]
            ):
                # fuse_all checkpoints are only taken at epoch boundaries;
                # a mid-epoch position means the checkpoint came from a
                # non-fused run — the fused dispatch cannot skip inside an
                # epoch, so the interrupted epoch restarts from its start
                logger.warning(
                    "Resuming a MID-EPOCH checkpoint with "
                    "fuse_all_inner_epochs=True: the interrupted epoch "
                    "restarts from its beginning (resume with the original "
                    "fusion setting for an exact continuation)"
                )
        for epoch_idx in range(start_epoch, self.config.train.epochs):
            if fuse_all:
                # every inner epoch in ONE dispatch; host precomputes the
                # per-epoch reshuffles
                self._maybe_profile_step()
                self._loop_pos = {
                    "epoch": epoch_idx, "inner": 0, "epoch_start_iter": self.iter_count
                }
                loaders = [
                    self.create_train_dataloader(seed_offset=i)
                    for i in range(self.n_inner_epochs)
                ]
                stats, n_steps = self.train_inner_epochs_fused(loaders)
                self.iter_count += n_steps
                # a checkpoint taken now must continue at the NEXT epoch
                self._loop_pos = {
                    "epoch": epoch_idx + 1, "inner": 0, "epoch_start_iter": self.iter_count
                }
                res, best_reward, done = self._post_step(
                    stats, clock, best_reward, n_steps=n_steps
                )
                results = res or results
                if done:
                    return results
                # Deferred callback replay is exactly equivalent to the
                # unfused interleaving: mean_kl is computed once per
                # experience collection (as in the reference,
                # accelerate_ppo_trainer.py:506-507) and kl_ctl.value is
                # only read at the NEXT collection, so n updates with the
                # same mean_kl commute with the epochs
                # (tests/test_kl_cadence.py pins this).
                for _ in range(self.n_inner_epochs):
                    self.post_backward_callback()
                self.post_epoch_callback()
                # fuse_all: the epoch already completed in one dispatch and
                # the next one collects fresh experience anyway — a pending
                # skip-chunk request is thereby satisfied
                self._sentinel_skip_chunk = False
                continue
            inner_start = pos["inner"] if pos and epoch_idx == start_epoch else 0
            for inner_idx in range(inner_start, self.n_inner_epochs):
                resuming_here = (
                    pos is not None and epoch_idx == start_epoch and inner_idx == inner_start
                )
                if resuming_here:
                    epoch_start_iter = pos["epoch_start_iter"]
                    pos = None  # consumed
                else:
                    epoch_start_iter = self.iter_count
                # seed_offset re-derives the interrupted epoch's loader
                # seed (config.seed + epoch_start_iter) from the restored
                # iter_count, reproducing the original shuffle
                train_dataloader = self.create_train_dataloader(
                    seed_offset=epoch_start_iter - self.iter_count
                )
                skip_steps = self.iter_count - epoch_start_iter
                self._loop_pos = {
                    "epoch": epoch_idx, "inner": inner_idx,
                    "epoch_start_iter": epoch_start_iter,
                }
                if fuse and skip_steps == 0:
                    # one jitted lax.scan dispatch for the whole inner epoch
                    self._maybe_profile_step()
                    stats, n_steps = self.train_inner_epoch_fused(train_dataloader)
                    self.iter_count += n_steps
                    self._loop_pos = self._next_pos(epoch_idx, inner_idx)
                    res, best_reward, done = self._post_step(
                        stats, clock, best_reward, n_steps=n_steps
                    )
                    results = res or results
                    if done:
                        return results
                    self.post_backward_callback()
                    if self._sentinel_skip_chunk:
                        # sentinel skip-chunk: drop the remaining inner
                        # epochs and collect fresh experience
                        self._sentinel_skip_chunk = False
                        break
                    continue
                if fuse and skip_steps:
                    logger.warning(
                        "Mid-epoch resume with fuse_inner_epoch: running "
                        "this inner epoch per-step to skip the "
                        f"{skip_steps} already-trained minibatches"
                    )
                for mb_idx, minibatch in enumerate(
                    MiniBatchIterator(train_dataloader, self.mb_size, self.num_mb)
                ):
                    if mb_idx < skip_steps:
                        continue  # already trained before the preemption
                    self._maybe_profile_step()
                    stats = self.train_minibatch(minibatch)
                    if self._goodput is not None:
                        self._goodput.note_train_rows(self.mb_size)
                    self.iter_count += 1
                    res, best_reward, done = self._post_step(stats, clock, best_reward)
                    results = res or results
                    if done:
                        return results
                    if self._sentinel_skip_chunk:
                        break

                self.post_backward_callback()
                if self._sentinel_skip_chunk:
                    # sentinel skip-chunk (escalation rung 2): abandon the
                    # remaining epochs over this suspect batch and collect
                    # fresh experience via post_epoch_callback
                    self._sentinel_skip_chunk = False
                    logger.warning(
                        f"Sentinel: skipping the rest of the current chunk at "
                        f"step {self.iter_count}; collecting fresh experience"
                    )
                    break
            self.post_epoch_callback()
        return results

    def _last_metrics_render(self) -> str:
        """The latest host-side stats, one `name value` per line — the
        "last metrics render" file of a postmortem bundle."""
        return "\n".join(
            f"{k} {v}" for k, v in self._last_stats.items() if np.ndim(v) == 0
        )

    def _watchdog_postmortem(self) -> None:
        """StepWatchdog on_fire hook: bundle flight-recorder events,
        thread stacks, the last stats snapshot, and the run config while
        the wedged threads still exist — before on_timeout/exit."""
        if not self.config.train.tracing:
            return
        from trlx_tpu.observability.postmortem import maybe_dump

        maybe_dump(
            f"watchdog-step{self.iter_count}",
            trigger="step-watchdog",
            out_dir=self.config.train.postmortem_dir,
            detail={
                "step": self.iter_count,
                "timeout_s": self.config.train.step_timeout_s,
            },
            metrics_render=self._last_metrics_render(),
            config=self.config.to_dict(),
        )

    def _sentinel_postmortem(self, action: str, verdict) -> None:
        """Bundle a postmortem when the sentinel rewinds or aborts (once
        per (action, step) — a rewound run that re-trips later still
        documents the second incident)."""
        if not self.config.train.tracing:
            return
        from trlx_tpu.observability.postmortem import maybe_dump

        maybe_dump(
            f"sentinel-{action}-step{self.iter_count}",
            trigger=f"sentinel-{action}",
            out_dir=self.config.train.postmortem_dir,
            detail={"step": self.iter_count, "reasons": list(verdict.reasons)},
            metrics_render=self._last_metrics_render(),
            config=self.config.to_dict(),
        )

    def _post_step(self, stats, clock, best_reward, n_steps: int = 1):
        """Checkpoint / stats fetch / eval / best-checkpoint / logging after
        an optimizer step (or a fused inner epoch of `n_steps` steps).
        Intervals use crossing semantics so strides > 1 still fire.
        Returns (eval results, best_reward, done)."""
        results = {}
        done = self.iter_count >= self.total_steps
        self._best_reward = best_reward

        def crossed(interval: int) -> bool:
            return self.iter_count // interval > (self.iter_count - n_steps) // interval

        # one batched device->host fetch for the whole stats dict (per-stat
        # np.asarray would block once per stat); divergence is
        # checked BEFORE any checkpoint write so a NaN-poisoned state never
        # overwrites the last good checkpoint
        stats = jax.device_get(_flatten_stats(stats))
        stats = {k: float(v) if np.ndim(v) == 0 else v for k, v in stats.items()}
        if self._timeline is not None:
            # timing/<phase>_ms (steady-state mean since the last drain)
            # + timing/<phase>_first_ms (compile+run, reported once)
            stats.update(self._timeline.drain_stats())
        if self._goodput is not None:
            # goodput/* (live MFU, throughput, wasted seconds by cause)
            # plus a crash-durable flush: the ledger artifact and the
            # phase timeline land on disk EVERY stats step, not only at
            # learn() shutdown, so a killed run still leaves both
            stats.update(self._goodput.drain_stats())
            if self._compile_ledger is not None:
                # compile/* (per-fn recompile counts, storms, backend
                # seconds, persistent-cache hits)
                stats.update(self._compile_ledger.drain_stats())
            if self._hbm is not None:
                # hbm/* (measured peak bytes, analytic account)
                stats.update(self._hbm.drain_stats())
            trace_dir = self.config.train.trace_dir or "logs/traces"
            try:
                self._goodput.write(
                    os.path.join(trace_dir, "goodput.json"),
                    extra=self._observability_extra())
                # the timeline artifact grows with the span count, so its
                # flush is throttled (the json above is O(1)-sized)
                now = time.monotonic()
                if now - getattr(self, "_timeline_flushed", 0.0) >= 30.0:
                    self._timeline_flushed = now
                    self._timeline.write(
                        os.path.join(trace_dir, "train_timeline.json"))
            except Exception:
                logger.exception("periodic goodput/timeline flush failed")
        self._last_stats = stats
        if self._watchdog is not None:
            self._watchdog.beat()
        verdict = None
        if self._sentinel is not None:
            # the in-jit guard reports the fraction of skipped steps; turn
            # it back into a count for the cumulative counter
            self._sentinel.record_skipped(
                stats.get("train/skipped_updates", 0.0) * n_steps
            )
            verdict = self._sentinel.observe_step(stats, self.iter_count)
            stats.update(self._sentinel.stats())
            if verdict.action != "ok":
                logger.warning(
                    f"Sentinel {verdict.action} at step {self.iter_count}: "
                    + "; ".join(verdict.reasons)
                )
            if verdict.action == "skip":
                self._sentinel_skip_chunk = True
            elif verdict.action == "rewind":
                # flush this step's stats first so the post-mortem trail
                # includes the anomaly that triggered the rewind
                self.tracker.log(stats, step=self.iter_count)
                self._sentinel_postmortem("rewind", verdict)
                raise SentinelRewind(self.iter_count, verdict.reasons)
            elif verdict.action == "abort":
                self.tracker.log(stats, step=self.iter_count)
                self._sentinel_postmortem("abort", verdict)
                raise FloatingPointError(
                    f"Health sentinel abort at step {self.iter_count}: "
                    + "; ".join(verdict.reasons)
                    + f". Resume from a checkpoint under "
                    f"'{self.config.train.checkpoint_dir}' with a lower "
                    "learning rate or tighter clipping "
                    "(train.resume_from_checkpoint)."
                )
        else:
            self._check_divergence(stats)

        guard = self._preemption_guard
        if guard is not None and guard.triggered:
            # preemption requested mid-epoch: write a manifest-complete
            # emergency checkpoint at this step boundary and exit with the
            # distinct code; auto_resume continues from here bit-identically
            self._emergency_save(guard.signum)
            raise resilience.PreemptionInterrupt(
                guard.signum, self.config.train.checkpoint_dir
            )

        if crossed(self.config.train.checkpoint_interval) or done:
            subfolder = f"checkpoint_{self.iter_count:0{len(str(self.total_steps))}d}"
            directory = os.path.join(self.config.train.checkpoint_dir, subfolder)
            self.save(directory)
            self.save_pretrained(os.path.join(directory, "hf_model"))
            if self.config.train.checkpoint_keep_n > 0 and jax.process_index() == 0:
                resilience.gc_checkpoints(
                    self.config.train.checkpoint_dir, self.config.train.checkpoint_keep_n
                )
        if (
            self._sentinel is not None
            and verdict is not None
            and verdict.action == "ok"
            and self._sentinel.should_pin(self.iter_count)
        ):
            # pin last_good (the rewind target) only after enough
            # consecutive clean steps; note_pinned BEFORE save so the
            # pin's own extra_state carries the pointer
            directory = os.path.join(self.config.train.checkpoint_dir, LAST_GOOD_NAME)
            self._sentinel.note_pinned(directory, self.iter_count)
            logger.info(f"Sentinel: pinning last_good checkpoint at step {self.iter_count}")
            self.save(directory)
        stats["time/step"] = clock.tick(self.config.train.batch_size * n_steps) / n_steps
        stats["learning_rate"] = float(np.asarray(self.lr_schedule(self.iter_count)))

        if crossed(self.config.train.eval_interval) or done:
            results = self.evaluate()
            stats.update(results)

            if self.config.train.save_best:
                current = stats.get(
                    "reward/mean", stats.get("metrics/reward", -float("inf"))
                )
                if jax.process_count() > 1:
                    # rewards exist only on process 0; broadcast so every
                    # host takes the same save branch (orbax save is a
                    # collective — skew would deadlock; reference
                    # all-reduces do_save the same way,
                    # accelerate_base_trainer.py:621-628)
                    from jax.experimental import multihost_utils

                    current = float(
                        multihost_utils.broadcast_one_to_all(np.float32(current))
                    )
                if current > best_reward:
                    best_reward = current
                    self._best_reward = current
                    directory = os.path.join(
                        self.config.train.checkpoint_dir, "best_checkpoint"
                    )
                    logger.info(f"Saving best checkpoint into {directory}")
                    self.save(directory)
                    self.save_pretrained(os.path.join(directory, "hf_model"))

        self.tracker.log(stats, step=self.iter_count)
        loss_desc = " | ".join(
            f"{k.split('/')[-1]}: {significant(v)}"
            for k, v in stats.items()
            if "loss" in k and np.ndim(v) == 0
        )
        logger.info(f"[step {self.iter_count}/{self.total_steps}] {loss_desc}")
        return results, best_reward, done

    def _check_divergence(self, stats: Dict[str, Any]):
        """Legacy failure detection, active when train.sentinel is off
        (with it on, HealthSentinel subsumes this as one rung of its
        escalation ladder): count consecutive steps with non-finite
        losses; abort with the last-good-checkpoint pointer once patience
        runs out."""
        if not self.config.train.nan_guard:
            return
        bad = any(
            np.ndim(v) == 0 and "loss" in k and not np.isfinite(v)
            for k, v in stats.items()
        )
        if not bad:
            self._nan_streak = 0
            return
        self._nan_streak += 1
        logger.warning(
            f"Non-finite loss at step {self.iter_count} "
            f"({self._nan_streak}/{self.config.train.nan_guard_patience})"
        )
        if self._nan_streak >= self.config.train.nan_guard_patience:
            # flush the fatal step's stats first — without this the
            # diverged step never reaches the tracker and post-mortems
            # are missing exactly the data point that killed the run
            self.tracker.log(stats, step=self.iter_count)
            raise FloatingPointError(
                f"Loss diverged (non-finite for {self._nan_streak} consecutive "
                f"steps). Resume from the last checkpoint under "
                f"'{self.config.train.checkpoint_dir}' with a lower learning "
                "rate or tighter clipping (train.resume_from_checkpoint)."
            )

    def _sentinel_rewind(self, e: SentinelRewind):
        """Sentinel layer 3: restore the pinned last_good checkpoint
        bit-exactly, carry the sentinel's own ladder state ACROSS the
        restore (the rewind budget must survive — reloading it from the
        pin would reset it and loop forever), advance the PRNG past the
        offending chunk so the same rollouts are not replayed, and open
        the cooldown window (LR damp / KL boost)."""
        sen = self._sentinel
        assert sen is not None and sen.last_good is not None
        path = sen.last_good["path"]
        logger.warning(
            f"Sentinel rewind #{sen.rewinds_used + 1}/{sen.max_rewinds}: "
            f"restoring last_good (step {sen.last_good['step']}) from "
            f"{path} after: " + "; ".join(e.reasons)
        )
        ladder_state = sen.state_dict()
        if self._goodput is not None:
            # the restore below plus every rollout phase until the first
            # post-rewind train step is repaid work — charge waste/rewind
            self._goodput.note_rewind()
        with self._span("train.sentinel_restore", phase="sentinel_restore", step=self.iter_count):
            self.load(path)  # restores params/opt_state/PRNG/loop-pos bit-exactly
        sen.load_state_dict(ladder_state)
        sen.note_rewind(self.iter_count)
        # diverge the PRNG stream from the pinned one: the chunk that bred
        # the anomaly must not be regenerated verbatim
        self.rng = jax.random.fold_in(self.rng, np.uint32(e.step))
        self._sentinel_skip_chunk = False
        self._post_rewind()

    def _post_rewind(self):
        """Trainer-specific cleanup after a sentinel rewind (the PPO
        trainer drops the restored rollout store and collects fresh
        experience under the post-rewind PRNG/cooldown)."""

    def _maybe_profile_step(self):
        """Capture a profiler trace over the configured step window
        (train.profile_dir / profile_start / profile_stop), through the one
        control in observability/tracing.py."""
        cfg = self.config.train
        if not cfg.profile_dir:
            return
        if cfg.profile_start <= self.iter_count < cfg.profile_stop and not getattr(self, "_profiling", False):
            logger.info(f"Starting profiler trace into {cfg.profile_dir}")
            tracing.start(cfg.profile_dir)
            self._profiling = True
        elif self.iter_count >= cfg.profile_stop and getattr(self, "_profiling", False):
            tracing.stop()
            self._profiling = False
            logger.info(f"Profiler trace written to {cfg.profile_dir}")

    def evaluate(self) -> Dict[str, Any]:
        """Generate on eval prompts, score with reward_fn/metric_fn
        (reference accelerate_base_trainer.py:339-500). With a list-valued
        gen kwarg the whole pass repeats per value, metrics suffixed
        @k=v (the reference's generation sweep).

        Multi-host: the reference shards its eval loader per rank and
        gathers generations (accelerate_base_trainer.py:391-402) because
        each rank runs its own model replica. Here the eval GENERATION is
        already sharded — one global jitted program over the mesh, batch
        split across all hosts' devices by GSPMD — so every host drives
        the same generate calls, while the host-side work (device->host
        copies, string decode, reward_fn/metric_fn — user code, possibly
        non-deterministic — and logging) runs on rank 0 only; non-zero
        ranks see empty sample lists. _post_step broadcasts the save_best
        verdict. Verified end-to-end by tests/test_multihost.py on a real
        2-process cluster."""
        logger.info("Evaluating model")
        clock = Clock()
        stats: Dict[str, Any] = {}

        if self.generate_sweep_kwarg is not None:
            sweep_arg, sweep_values = self.generate_sweep_kwarg
        else:
            sweep_arg, sweep_values = None, [None]

        for sweep_value in sweep_values:
            if sweep_value is not None:
                gen_kwargs = {**self.generate_kwargs, sweep_arg: sweep_value}
                suffix = f"@{sweep_arg}={sweep_value}"
            else:
                gen_kwargs = self.generate_kwargs
                suffix = ""

            all_samples, all_prompts, all_outputs = [], [], []
            all_metadata = []
            clock.tick()  # reset: exclude the previous value's scoring time
            for batch in self.eval_dataloader:
                out = self.generate(batch["input_ids"], batch["attention_mask"], gen_kwargs)
                if jax.process_index() == 0:
                    # every host drives the (mesh-sharded) generate calls,
                    # but only rank 0 scores/logs — skip the host copies
                    # and string decode elsewhere
                    samples = np.asarray(out["samples"])
                    prompts = np.asarray(batch["input_ids"])
                    str_samples, str_prompts, str_outputs = self.decode(prompts, samples)
                    all_samples += str_samples
                    all_prompts += str_prompts
                    all_outputs += str_outputs
                metadata = {
                    k: v for k, v in batch.items() if k not in ("input_ids", "attention_mask")
                }
                all_metadata.append(metadata)

            # accumulated over sweep values (one generation pass per value)
            stats["time/generate"] = stats.get("time/generate", 0.0) + clock.tick()

            metadata = {}
            for md in all_metadata:
                for k, v in md.items():
                    metadata.setdefault(k, []).extend(v)

            if jax.process_index() == 0:
                rows = list(zip(all_prompts, all_outputs))
                if self.reward_fn:
                    rewards = self.reward_fn(
                        samples=all_samples,
                        prompts=all_prompts,
                        outputs=all_outputs,
                        tokenizer=self.tokenizer,
                        **metadata,
                    )
                    rewards = [
                        float(np.sum(np.asarray(r))) if np.ndim(r) > 0 else float(r)
                        for r in rewards
                    ]
                    rows = [r + (reward,) for r, reward in zip(rows, rewards)]
                    stats[f"reward/mean{suffix}"] = float(np.mean(rewards))
                    # headline metric (save_best) = first sweep value's reward
                    stats.setdefault("reward/mean", stats[f"reward/mean{suffix}"])
                if self.metric_fn:
                    metrics = self.metric_fn(
                        samples=all_samples,
                        prompts=all_prompts,
                        outputs=all_outputs,
                        **metadata,
                    )
                    for k, v in metrics.items():
                        if np.ndim(v) > 0 and len(v):
                            stats[f"metrics/{k}{suffix}"] = float(np.mean(np.asarray(v, dtype=np.float64)))
                        else:
                            stats[f"metrics/{k}{suffix}"] = float(v)
                self._print_samples_table(rows, title_suffix=suffix)

        self.nth_evaluation += 1
        return stats

    def _print_samples_table(self, rows, max_rows: int = 8, title_suffix: str = ""):
        try:
            from rich.console import Console
            from rich.table import Table

            columns = ["prompt", "output"] + (["reward"] if rows and len(rows[0]) > 2 else [])
            table = Table(*columns, title=f"Evaluation #{self.nth_evaluation}{title_suffix}", show_lines=True)
            for row in rows[:max_rows]:
                table.add_row(*[str(significant(x)) if isinstance(x, float) else str(x) for x in row])
            Console().print(table)
        except ImportError:
            for row in rows[:max_rows]:
                logger.info(" | ".join(str(x) for x in row))

    # ------------------------------------------------------------------
    # Checkpointing (orbax) + HF export
    # ------------------------------------------------------------------

    def _sync_hosts(self, tag: str):
        """Barrier across hosts (no-op single-process): checkpoint staging
        and promotion must not race the collective orbax write."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"trlx_tpu_ckpt_{tag}")

    def _extra_resume_state(self) -> Dict[str, Any]:
        """Trainer-specific host state to include in checkpoints (e.g. the
        PPO rollout store and KL controller). Must be picklable.
        Subclasses extend the dict returned by super()."""
        extra: Dict[str, Any] = {}
        if self._sentinel is not None:
            extra["sentinel"] = self._sentinel.state_dict()
        return extra

    def _load_extra_resume_state(self, state: Dict[str, Any]) -> None:
        """Inverse of _extra_resume_state."""
        if self._sentinel is not None and "sentinel" in state:
            self._sentinel.load_state_dict(state["sentinel"])

    def _resume_state_dict(self) -> Dict[str, Any]:
        """Host-side trainer state beyond the param/optimizer trees: the
        step counter, PRNG key, nan-guard streak, loop position, and best
        reward — everything needed for a bit-identical continuation."""
        best = self._best_reward
        return {
            "iter_count": self.iter_count,
            "rng_key": np.asarray(self.rng).tolist(),
            "nan_streak": self._nan_streak,
            "loop_pos": self._loop_pos,
            "best_reward": best if np.isfinite(best) else None,
            "has_optimizer": bool(self.config.train.save_optimizer),
        }

    def save(self, directory: Optional[str] = None):
        """Save full trainer state with orbax (reference:
        accelerator.save_state, accelerate_base_trainer.py:309-317),
        atomically: everything is staged in a sibling `.tmp` directory,
        `manifest.json` is written last, and one `os.replace` promotes the
        stage — a preemption mid-save can never corrupt an existing
        checkpoint or leave a half-written one that auto_resume would
        pick up. Optimizer state is included iff `train.save_optimizer`.
        Saved state covers the PRNG key, loop position, and nan-guard
        counter so a resumed run is bit-identical to an uninterrupted one.
        """
        import orbax.checkpoint as ocp

        directory = os.path.abspath(directory or self.config.train.checkpoint_dir)
        tmp, old = directory + ".tmp", directory + ".old"
        is_primary = jax.process_index() == 0
        if is_primary:
            for stale in (tmp, old):
                if os.path.isdir(stale):
                    shutil.rmtree(stale, ignore_errors=True)
        self._sync_hosts("stage")

        state = {
            "train_params": self.train_params,
            "frozen_params": self.frozen_params,
        }
        if self.config.train.save_optimizer:
            state["opt_state"] = self.opt_state
        ocp.PyTreeCheckpointer().save(os.path.join(tmp, "state"), state, force=True)

        if is_primary:
            resilience.atomic_write_json(
                os.path.join(tmp, "trainer_state.json"), self._resume_state_dict()
            )
            extra = self._extra_resume_state()
            if extra:
                with open(os.path.join(tmp, "extra_state.pkl"), "wb") as f:
                    pickle.dump(extra, f)
        self._sync_hosts("commit")
        if is_primary:
            resilience.write_manifest(tmp, self.iter_count)
            if os.path.isdir(directory):
                # os.replace cannot overwrite a non-empty dir: swap the old
                # checkpoint aside, promote the stage, then drop the old
                os.replace(directory, old)
            os.replace(tmp, directory)
            shutil.rmtree(old, ignore_errors=True)
        self._sync_hosts("done")

    def load(self, directory: str):
        import orbax.checkpoint as ocp

        directory = os.path.abspath(directory)
        if not resilience.is_valid_checkpoint(directory):
            # explicit user-given path: load anyway (pre-manifest layouts),
            # but say the completeness guarantee does not apply
            logger.warning(
                f"Checkpoint {directory} has no manifest (pre-atomic layout "
                "or truncated save); loading without completeness guarantees"
            )

        meta: Dict[str, Any] = {"iter_count": 0}
        path = os.path.join(directory, "trainer_state.json")
        if os.path.exists(path):
            with open(path) as f:
                meta = json.load(f)

        has_opt = bool(meta.get("has_optimizer", True))
        target = {
            "train_params": self.train_params,
            "frozen_params": self.frozen_params,
        }
        if has_opt:
            target["opt_state"] = self.opt_state
        state = ocp.PyTreeCheckpointer().restore(os.path.join(directory, "state"), item=target)
        self.train_params = state["train_params"]
        self.frozen_params = state["frozen_params"]
        if has_opt:
            self.opt_state = state["opt_state"]
        else:
            logger.warning(
                "Checkpoint was saved with train.save_optimizer=False; "
                "optimizer state starts fresh (momentum/variance reset)"
            )

        self.iter_count = int(meta.get("iter_count", 0))
        if meta.get("rng_key") is not None:
            self.rng = jnp.asarray(np.asarray(meta["rng_key"], dtype=np.uint32))
        self._nan_streak = int(meta.get("nan_streak", 0))
        self._resume_pos = meta.get("loop_pos")
        self._loop_pos = meta.get("loop_pos")
        if meta.get("best_reward") is not None:
            self._best_reward = float(meta["best_reward"])

        extra_path = os.path.join(directory, "extra_state.pkl")
        if os.path.exists(extra_path):
            with open(extra_path, "rb") as f:
                self._load_extra_resume_state(pickle.load(f))
        logger.info(f"Restored checkpoint from {directory} at step {self.iter_count}")

    def _emergency_save(self, signum: Optional[int]):
        """Write the preemption checkpoint. Named after the step with a
        `_preempt` suffix; auto_resume finds it by manifest step, so the
        name only aids humans."""
        width = len(str(getattr(self, "total_steps", 0) or 0))
        subfolder = f"checkpoint_{self.iter_count:0{width}d}_preempt"
        directory = os.path.join(self.config.train.checkpoint_dir, subfolder)
        logger.warning(
            f"Writing emergency checkpoint (signal {signum}) to {directory}"
        )
        self.save(directory)

    def save_pretrained(self, directory: Optional[str] = None, **kwargs):
        """Portable export: HF-layout state dict for GPT2/Llama families
        plus tokenizer info (reference accelerate_base_trainer.py:284-307)."""
        if jax.process_index() != 0:
            return
        directory = directory or os.path.join(self.config.train.checkpoint_dir, "hf_model")
        os.makedirs(directory, exist_ok=True)
        try:
            import torch

            from trlx_tpu.models.hf_interop import params_to_hf_state_dict

            params = self.params
            if getattr(self.model_cfg, "lora_rank", 0) > 0:
                # fold adapters into the base kernels (peft merge_and_unload)
                from trlx_tpu.models.lora import merge_lora_into_params

                params = merge_lora_into_params(params, self.model_cfg)
            if getattr(self.model_cfg, "prompt_tokens", 0) > 0:
                # HF base checkpoints have no slot for the soft prompt (the
                # only trained LM params) — export it alongside, like peft's
                # adapter-only checkpoints, and say so loudly
                np.save(
                    os.path.join(directory, "soft_prompt.npy"),
                    np.asarray(params["lm"]["soft_prompt"], np.float32),
                )
                logger.warning(
                    "Prompt-tuning export: pytorch_model.bin holds the "
                    "UNMODIFIED base weights; the trained soft prompt is in "
                    "soft_prompt.npy (prepend its embeddings to use it)"
                )
            if getattr(self.model_cfg, "prefix_tokens", 0) > 0:
                np.savez(
                    os.path.join(directory, "prefix_kv.npz"),
                    **{
                        f"block_{i}.attn.{kv}": np.asarray(
                            params["lm"][f"block_{i}"]["attn"][kv], np.float32
                        )
                        for i in range(self.model_cfg.n_layers)
                        for kv in ("prefix_k", "prefix_v")
                    },
                )
                logger.warning(
                    "Prefix-tuning export: pytorch_model.bin holds the "
                    "UNMODIFIED base weights; the trained K/V prefixes are "
                    "in prefix_kv.npz"
                )
            sd = params_to_hf_state_dict(params, self.model_cfg)
            torch.save({k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in sd.items()},
                       os.path.join(directory, "pytorch_model.bin"))
            # a loadable HF config.json makes the export self-contained:
            # the dir can be passed straight back as model.model_path
            # (incl. models born from random: presets)
            from trlx_tpu.models.hf_interop import config_to_hf

            hf_cfg = config_to_hf(self.model_cfg)
            # stamp the ACTUAL tokenizer's special ids: generate() on the
            # reloaded export must stop/pad on this run's tokens, not on
            # the family's defaults
            for key in ("pad_token_id", "eos_token_id", "bos_token_id"):
                v = getattr(self.tokenizer, key, None)
                if v is not None:
                    hf_cfg[key] = int(v)
            with open(os.path.join(directory, "config.json"), "w") as f:
                json.dump(hf_cfg, f, indent=2)
            # tokenizer files too, when the tokenizer can express itself in
            # HF format (reference exports carry the tokenizer alongside,
            # accelerate_base_trainer.py:284-307) — the dir then loads in
            # plain transformers with AutoModel + AutoTokenizer
            if hasattr(self.tokenizer, "save_pretrained"):
                try:
                    self.tokenizer.save_pretrained(directory)
                except Exception as te:
                    logger.warning(f"Tokenizer export skipped: {te}")
        except Exception as e:  # model family without HF layout — save msgpack
            logger.warning(f"HF export unavailable ({e}); saving flax msgpack instead")
            from flax import serialization

            with open(os.path.join(directory, "params.msgpack"), "wb") as f:
                f.write(serialization.to_bytes(self.params))
        with open(os.path.join(directory, "trlx_tpu_config.json"), "w") as f:
            json.dump(self.config.to_dict(), f, indent=2, default=str)

    def _generation_config(self, gen_kwargs: Dict):
        from trlx_tpu.ops.sampling import GenerationConfig

        return GenerationConfig.from_gen_kwargs(
            gen_kwargs, self.tokenizer.eos_token_id, self.tokenizer.pad_token_id)

    def _prefill_block(self) -> int:
        """Columns of the sampler's prefill block (`ops.sampling.BlockPlan`);
        0, the one-shot prefill, where padding is on the right: a batch's
        padding then lies behind its prompts and no block in front is empty."""
        from trlx_tpu.ops import sampling

        return sampling.PREFILL_BLOCK if self.config.tokenizer.padding_side == "left" else 0

    def _block_plan(self, prompt_len: int, gen_kwargs: Dict):
        """The plan by which `get_generate_fn`'s program of that prompt width
        follows a batch's longest prompt, or None where it keeps the one-shot
        prefill: the sampler's own rule (`ops.sampling.block_plan`)."""
        from trlx_tpu.ops.sampling import block_plan

        return block_plan(self.model_cfg, self._generation_config(gen_kwargs), prompt_len,
                          self._prefill_block())


def _batch_shapes(batch) -> Tuple:
    return tuple(np.shape(x) for x in jax.tree_util.tree_leaves(batch))


def _flatten_stats(d: Dict, prefix: str = "") -> Dict:
    out = {}
    for k, v in d.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten_stats(v, key))
        else:
            out[key] = v
    return out
