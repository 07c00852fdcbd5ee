"""PPO trainer.

Parity: trlx/trainer/accelerate_ppo_trainer.py (AcceleratePPOTrainer) — the
same rollout->score->precompute->store->optimize cycle, restructured for
TPU: generation and logprob/value precompute are two jit-compiled programs
with static shapes (prompts padded to the pipeline max, responses to
max_new_tokens), the hydra reference branch runs fused with the policy
forward (ops in trlx_tpu/models/policy.py), and the user reward_fn stays on
host between the two.
"""

import dataclasses
import itertools
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import PPORLBatch, PPORLElement
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.method_configs import MethodConfig, register_method
from trlx_tpu.observability import tracing
from trlx_tpu.models import (
    build_model,
    forward_policy_and_ref,
    forward_seq2seq_policy_and_ref,
    position_ids,
    ref_param_subtree,
)
from trlx_tpu.ops.ppo import (
    AdaptiveKLController,
    FixedKLController,
    get_advantages_and_returns,
    ppo_loss,
)
from trlx_tpu.parallel import infer_param_shardings
from trlx_tpu.pipeline import LoaderStream
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base_trainer import TPUTrainer, merge_params
from trlx_tpu.utils import Clock
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import RunningMoments, logprobs_of_labels

logger = logging.get_logger(__name__)

# The share of one device's memory a cycle's trunk cache may take
# (`PPOTrainer._trunk_cache_available`): it is held through the train phase
# and while the next chunks are scored, beside the weights, the optimizer
# and the scorer's activations. 1.6% in the benchmark's PPO cells (268 MB).
TRUNK_CACHE_HBM_SHARE = 0.125


def _to_batch_columns(x, q_from: int, q_to: int, width: Optional[int],
                      left_queries: bool, fill=0):
    """Rows [b, q_from + r, ...] (token ids on the host, or cached states
    [.., d] inside a jit), whose queries are padded to `q_from` columns, in
    the columns of a batch that pads queries to `q_to` and is `width` wide
    (None: as wide as it comes out): the padding a wider query block adds
    sits in front of a left-padded query and between a right-padded query
    and its response. Added columns hold `fill`: a state of zeros there is,
    like the pad tokens' states in the others, attention-masked and
    loss-masked."""
    if q_to < q_from:
        raise ValueError(f"a batch pads queries to {q_to}, under the rows' {q_from}")
    xp = np if isinstance(x, np.ndarray) else jnp
    cols = lambda before, after: (
        ((0, 0), (before, after)) + ((0, 0),) * (x.ndim - 2))
    gap = q_to - q_from
    if gap and left_queries:
        x = xp.pad(x, cols(gap, 0), constant_values=fill)
    elif gap:
        x = xp.concatenate(
            [xp.pad(x[:, :q_from], cols(0, gap), constant_values=fill), x[:, q_from:]], axis=1)
    if width is None:
        return x
    if x.shape[1] < width:
        x = xp.pad(x, cols(0, width - x.shape[1]), constant_values=fill)
    return x[:, :width]


@dataclass
@register_method
class PPOConfig(MethodConfig):
    """PPO hyperparameters; field set identical to the reference
    (modeling_ppo.py:73-134) so configs carry over. The loss/GAE math these
    parameterize lives in trlx_tpu/ops/ppo.py."""

    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    init_kl_coef: float = 0.001
    target: Optional[float] = None
    horizon: int = 10000
    gamma: float = 1.0
    lam: float = 0.95
    cliprange: float = 0.2
    cliprange_value: float = 0.2
    vf_coef: float = 1.0
    scale_reward: Optional[str] = None
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    gen_kwargs: dict = field(default_factory=dict)
    gen_experience_kwargs: Optional[dict] = None
    num_value_layers_unfrozen: int = 0
    # Rollout fast path: the sampling loop itself captures per-token policy
    # logprobs/values and the hydra-split activations, shrinking the score
    # phase to the frozen-reference suffix and letting the cycle dispatch
    # the next rollout ahead of train (cross-cycle reward overlap). Default
    # off: the classic path stays bit-identical (tests/test_pipelined_cycle
    # pinning). Extra field vs the reference config set.
    capture_rollout_stats: bool = False
    # Whiten advantages over real response tokens only (GAE whitening
    # currently normalizes across padded positions too, biasing mean/std
    # for short responses). Default off to preserve reference-parity
    # curves (the reference whitens unmasked, utils/modeling.py whiten).
    whiten_with_mask: bool = False
    # Int8 weight-only view of the never-trained decode weights (blocks
    # below the hydra split + embeddings) swapped in for GENERATION only;
    # train/score always see the dense tree. Default off: flag off is
    # bit-identical. Extra field vs the reference config set.
    quantize_frozen_trunk: bool = False
    # Multi-turn rollouts (tool-use RL): name of a registered
    # trlx_tpu.environments Environment. When set, make_experience drives
    # whole episodes through fleet chat sessions (retained KV server-side,
    # so each policy turn prefills only its delta tokens), masks
    # environment-authored tokens out of the loss (PPORLElement.loss_mask)
    # and lands each turn's reward on the last token of that policy turn.
    # Requires train.rollout_backend="fleet". Default None: the
    # single-turn path stays bit-identical. Extra fields vs the reference
    # config set.
    multiturn_env: Optional[str] = None
    multiturn_max_turns: int = 4
    multiturn_env_kwargs: dict = field(default_factory=dict)


@register_trainer
class PPOTrainer(TPUTrainer):
    span_family = "ppo"

    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        self.seq2seq = config.model.model_arch_type == "seq2seq"

        self.store = PPORolloutStorage(
            self.tokenizer.pad_token_id, self.tokenizer.padding_side
        )

        # Frozen reference branch (hydra): a copy of the top-of-model params
        # at init (full copy when everything is trainable) — reference
        # AutoModelForCausalLMWithHydraValueHead (modeling_ppo.py:385-499).
        self.ref_params = self._build_ref_params()

        if config.method.target is not None:
            self.kl_ctl = AdaptiveKLController(
                config.method.init_kl_coef, config.method.target, config.method.horizon
            )
        else:
            self.kl_ctl = FixedKLController(config.method.init_kl_coef)

        self.running_moments = RunningMoments()
        self.ref_mean = config.method.ref_mean
        self.ref_std = config.method.ref_std
        self.mean_kl = 0.0

        self.log_rollouts = config.train.rollout_logging_dir is not None
        if self.log_rollouts:
            self.setup_rollout_logging(config)

        # the rollout loader's stream (`_rollout_stream`) and the collection's
        # count of generate dispatches: [calls, widths, positions, padding positions]
        self._prompt_stream = None
        self._prefill_tally = np.zeros(4, np.int64)
        self._score_fn = None
        # whether `_score_fn` hands out a sixth result on request, the
        # chunk's trunk state (`_score_hands_out_trunk_state`)
        self._score_with_trunk_state = False
        # the cycle's trunk cache (`_trunk_cache_available`): the fill's
        # program, the chunks of the collection under way, and the one
        # device array [rollouts, query + response, d] the store's rows index
        self._trunk_cache_fn = None
        self._trunk_concat_fn = None
        self._trunk_cache_budget = None
        self._trunk_chunks = None
        self._trunk_scored_rows = 0
        self._trunk_cache = None
        # Disaggregated rollouts (train.rollout_backend="fleet"): lazy
        # ReplicaRouter over the inference replicas; None under the
        # default "local" backend (bit-identical pre-fleet path). With
        # train.rollout_fleet_supervised the trainer also launches and
        # supervises the replicas themselves (FleetSupervisor).
        self._rollout_router = None
        self._rollout_supervisor = None
        # router-side request tracer (train.tracing): one ring shared by
        # every fleet dispatch, exported on fleet shutdown
        self._rollout_tracer = None
        # optimizer step the in-process replicas' engines last received
        # params for (see _push_params_to_thread_replicas)
        self._fleet_params_step = 0

    def _build_ref_params(self):
        """Extract + place the frozen reference subtree (overridden by the
        pipelined trainer, whose reference lives stacked on the pipe axis)."""
        ref = ref_param_subtree(self.params, self.model_cfg, self.split)
        ref_shardings = infer_param_shardings(self.runtime.mesh, ref)
        return jax.tree_util.tree_map(jax.device_put, ref, ref_shardings)

    def get_arch(self, config: TRLConfig):
        return build_model(
            config.model,
            vocab_size=self.tokenizer.vocab_size,
            rng=jax.random.PRNGKey(config.train.seed),
            num_value_layers=getattr(config.method, "num_value_layers_unfrozen", 0),
        )

    def setup_rollout_logging(self, config):
        import json as _json
        import os
        import uuid

        assert os.path.isdir(config.train.rollout_logging_dir)
        self.run_id = f"run-{uuid.uuid4()}"
        self.rollout_logging_dir = os.path.join(config.train.rollout_logging_dir, self.run_id)
        os.mkdir(self.rollout_logging_dir)
        with open(os.path.join(self.rollout_logging_dir, "config.json"), "w") as f:
            f.write(_json.dumps(config.to_dict(), indent=2, default=str))

    # ------------------------------------------------------------------
    # Loss
    # ------------------------------------------------------------------

    def _window_loss_ok(self) -> bool:
        """Whether the train loss can use the windowed head
        (`forward`'s `window`): needs the plain MLP value head and no soft
        prompt (the branch attends full-width; the prompt shifts
        positions)."""
        return (
            getattr(self.config.method, "num_value_layers_unfrozen", 0) == 0
            and getattr(self.model_cfg, "prompt_tokens", 0) == 0
        )

    def _goodput_configure(self, n_prompt: int, n_new: int) -> None:
        """Price the goodput ledger's per-sample FLOPs with the knobs
        an offline estimate passes to flops_per_cycle — live MFU and an
        offline MFU share one model by construction. Re-done every
        chunk (pure arithmetic)."""
        self._goodput.configure_unit_flops(
            self.model_cfg, n_prompt, n_new,
            unfrozen=self.model_cfg.n_layers - self.split,
            window_ok=(self._window_loss_ok()
                       and not getattr(self.model_cfg, "sows_moe_aux", False)),
            fast_path=False,  # make_experience scores with the full fwd
        )

    def make_loss_fn(self) -> Callable:
        model = self.model
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id

        if self.seq2seq:
            # Encoder input = query, decoder input = response (starting with
            # decoder_start); reference seq2seq loss path
            # accelerate_ppo_trainer.py:147-174.
            def seq2seq_loss_fn(train_params, frozen_params, batch: PPORLBatch):
                params = merge_params(train_params, frozen_params)
                query_tensors = batch.query_tensors
                response_tensors = batch.response_tensors
                old_logprobs = batch.logprobs
                old_values = batch.values
                old_rewards = batch.rewards
                response_length = old_rewards.shape[1]

                attention_mask = (query_tensors != pad_id).astype(jnp.int32)
                decoder_attention_mask = (response_tensors != pad_id).astype(jnp.int32)
                decoder_attention_mask = decoder_attention_mask.at[:, 0].set(1)
                gae_mask = decoder_attention_mask[:, 1:][:, :response_length]

                advantages, returns = get_advantages_and_returns(
                    old_values, old_rewards, method.gamma, method.lam,
                    mask=gae_mask if method.whiten_with_mask else None,
                )

                logits, values_pred, _, _ = model.apply(
                    {"params": params},
                    query_tensors, attention_mask,
                    response_tensors, decoder_attention_mask,
                )
                values_pred = values_pred[:, :-1]
                logprobs = logprobs_of_labels(logits[:, :-1, :], response_tensors[:, 1:])
                mask = decoder_attention_mask[:, 1:]

                logprobs = logprobs[:, :response_length]
                values_pred = values_pred[:, :response_length]
                mask = mask[:, :response_length]

                return ppo_loss(
                    logprobs=logprobs,
                    values=values_pred,
                    old_logprobs=old_logprobs,
                    old_values=old_values,
                    advantages=advantages,
                    returns=returns,
                    mask=mask,
                    cliprange=method.cliprange,
                    cliprange_value=method.cliprange_value,
                    vf_coef=method.vf_coef,
                )

            return seq2seq_loss_fn

        sparse_moe = getattr(self.model_cfg, "has_sparse_moe", False)

        def loss_fn(train_params, frozen_params, batch: PPORLBatch):
            params = merge_params(train_params, frozen_params)
            query_tensors = batch.query_tensors
            response_tensors = batch.response_tensors
            old_logprobs = batch.logprobs
            old_values = batch.values
            old_rewards = batch.rewards
            response_length = old_rewards.shape[1]

            tokens = jnp.concatenate([query_tensors, response_tensors], axis=1)
            attention_mask = (tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            start = query_tensors.shape[1] - 1
            end = start + response_length
            mask = attention_mask[:, start + 1 : end + 1]
            if batch.loss_masks is not None:
                # multi-turn rollouts: environment-authored tokens (tool
                # output, game state) are context, not actions — they
                # carry zero loss weight and drop out of masked whitening
                mask = mask * batch.loss_masks.astype(mask.dtype)

            advantages, returns = get_advantages_and_returns(
                old_values, old_rewards, method.gamma, method.lam,
                mask=mask if method.whiten_with_mask else None,
            )

            def window_from_full(logits, values_full):
                lp = logprobs_of_labels(logits[:, :-1, :], tokens[:, 1:])
                return lp[:, start:end], values_full[:, :-1][:, start:end]

            moe_aux, moe_stats = 0.0, {}
            x, first = tokens, 0
            if batch.trunk_cache is not None:
                # Trunk-cache train path: resume the trainable suffix from
                # this batch's rows of the cycle's cache, the state entering
                # block `split` as the fill's forward left it (its own
                # dtype: nothing is rounded that was not rounded before).
                # Exact: the trunk is entirely frozen (split > 0 implies
                # it), the columns a row does not own hold the state of pad
                # tokens or zeros and are attention-masked (exp(-1e9) ==
                # 0.0 in f32, so they contribute exactly nothing) and
                # loss-masked, and gradients already stopped at the first
                # trainable layer: backward is unchanged.
                h0 = _to_batch_columns(
                    jnp.take(batch.trunk_cache, batch.trunk_rows, axis=0),
                    batch.trunk_cache.shape[1] - self._trunk_response_width(),
                    query_tensors.shape[1], tokens.shape[1], self._left_queries(),
                )
                if isinstance(h0, jax.core.Tracer):
                    # inside jit this is a pure layout hint; in eager mode it
                    # would be a reshard (device_put) that perturbs backward
                    # reduction order and breaks the bitwise-equality contract
                    h0 = self._place_trunk_cache(h0)
                x, first = jax.lax.stop_gradient(h0), self.split
            if getattr(self.model_cfg, "sows_moe_aux", False):
                from trlx_tpu.utils.modeling import apply_with_moe_aux

                (logits, values_full, _), moe_aux = apply_with_moe_aux(
                    self.model_cfg, model, params,
                    tokens, attention_mask, positions,
                )
                logprobs, values_pred = window_from_full(logits, values_full)
            else:
                # window the head (r5) where it can be: the blocks run
                # full-width, the 50k-vocab unembed + fused CE + value head
                # over the response window only — the loss reads exactly
                # this slice, and the full-width head was the cycle's
                # largest wasted matmul (tests/test_trainers.py pins
                # equality with the full-forward loss)
                window = (start, response_length) if self._window_loss_ok() else None
                # (a step resumed from the trunk cache sows them for the
                # blocks it runs, [split, n_layers))
                sown = sparse_moe and window is not None
                out = model.apply(
                    {"params": params}, x, attention_mask, positions,
                    start=first, window=window, method=type(model).forward,
                    **({"mutable": ["moe_stats"]} if sown else {}),
                )
                if sown:  # SparseMoE layers sow their dispatch counters
                    from trlx_tpu.models.transformer import moe_stats_from_state

                    out, state = out
                    moe_stats = moe_stats_from_state(state)
                logits, values_pred, _ = out
                if window is not None:
                    logprobs = logprobs_of_labels(
                        logits, tokens[:, start + 1:end + 1]
                    )
                else:
                    logprobs, values_pred = window_from_full(logits, values_pred)

            loss, stats = ppo_loss(
                logprobs=logprobs,
                values=values_pred,
                old_logprobs=old_logprobs,
                old_values=old_values,
                advantages=advantages,
                returns=returns,
                mask=mask,
                cliprange=method.cliprange,
                cliprange_value=method.cliprange_value,
                vf_coef=method.vf_coef,
            )
            if moe_stats:
                # SparseMoE's dispatch counters (docs/observability.md)
                stats = {**stats, "moe": jax.lax.stop_gradient(moe_stats)}
            if getattr(self.model_cfg, "sows_moe_aux", False):
                # the logged total must be the optimized objective
                loss = loss + moe_aux
                stats = {
                    **stats, "moe_aux_loss": moe_aux,
                    "losses": {**stats["losses"], "total_loss": loss},
                }
            return loss, stats

        return loss_fn

    # ------------------------------------------------------------------
    # Experience collection
    # ------------------------------------------------------------------

    def _build_score_fn(self):
        """Jitted rollout scorer: policy logprobs + values + frozen-ref
        logprobs in one compiled program (the reference runs 2-3 torch
        forwards, accelerate_ppo_trainer.py:414-446). Where
        `_score_hands_out_trunk_state` says so, that one program has a sixth
        output, the state entering block `split`, and `_score_fn` is the
        door in front of it: five results to every caller, all six to the
        one that asks (`_process_chunk`)."""
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        if self.seq2seq:
            def score_seq2seq(train_params, frozen_params, ref_params, query, response):
                params = merge_params(train_params, frozen_params)
                attention_mask = (query != pad_id).astype(jnp.int32)
                decoder_attention_mask = (response != pad_id).astype(jnp.int32)
                decoder_attention_mask = decoder_attention_mask.at[:, 0].set(1)
                logits, values, ref_logits = forward_seq2seq_policy_and_ref(
                    model, params, ref_params,
                    query, attention_mask, response, decoder_attention_mask, split,
                )
                logprobs = logprobs_of_labels(logits[:, :-1, :], response[:, 1:])
                ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], response[:, 1:])
                log_ratio = (logprobs - ref_logprobs) * decoder_attention_mask[:, 1:]
                kl = jnp.exp(log_ratio) - 1 - log_ratio
                mean_kl_per_token = kl.mean()
                mean_kl = kl.sum(1).mean()
                return logprobs, values[:, :-1], log_ratio, mean_kl, mean_kl_per_token

            self._score_fn = self._ljit(score_seq2seq, "score_seq2seq", budget=2)
            return

        def score(train_params, frozen_params, ref_params, all_tokens, with_trunk_state=False):
            params = merge_params(train_params, frozen_params)
            attention_mask = (all_tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            logits, values, ref_logits, h_split = forward_policy_and_ref(
                model, params, ref_params, all_tokens, attention_mask, split, positions
            )
            logprobs = logprobs_of_labels(logits[:, :-1, :], all_tokens[:, 1:])
            ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], all_tokens[:, 1:])
            # per-token log ratio, masked (reference accelerate_ppo_trainer.py:457)
            log_ratio = (logprobs - ref_logprobs) * attention_mask[:, :-1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            mean_kl_per_token = kl.mean()
            mean_kl = kl.sum(1).mean()
            if with_trunk_state and self._planned_chunks() == 1:
                # The state (what `trunk_cache_fill` returns for these
                # tokens) leaves behind a barrier it shares with the
                # reference logits, and nobody reads the barrier's logits: a
                # nudge, measured and not derived. gpt2-xl's one chunk (128 x
                # 104 x 1600 bfloat16, 43 MB) as a plain sixth output lost the
                # 46 frozen blocks' weight prefetches (109 for 245 joins; 2.2
                # ms a block, 0.676 against 0.566 s a chunk) and behind the
                # barrier plans as for five outputs (0.570 s; PR 40); lfm2's
                # (268 MB) has one plan either way. pythia's chunks (16 x 1024
                # x 2048, 67 MB, four a collection) are the other way round,
                # 19 for 97 joins behind the barrier and 2.033 against 1.920
                # s, and leave plainly (PR 49). PERF.md section 6.
                h_split, _ = jax.lax.optimization_barrier((h_split, ref_logits))
            scored = (logprobs, values[:, :-1], log_ratio, mean_kl, mean_kl_per_token)
            return (*scored, self._place_trunk_cache(h_split)) if with_trunk_state else scored

        # the function's name is the program's in a device trace (`jit_score`), with either width
        program = self._ljit(score, "score", budget=2, static_argnames="with_trunk_state")
        self._score_with_trunk_state = self._score_hands_out_trunk_state(program)
        if not self._score_with_trunk_state:
            self._score_fn = program
            return
        program = jax.tree_util.Partial(program, with_trunk_state=True)  # the door's call stays as its kernels' cache key has it

        def score_door(*args, trunk_state=False):
            # the state dropped here is freed at once: it never stands
            # beside a caller that did not ask for it
            out = program(*args)
            return out if trunk_state else out[:5]

        self._score_fn = score_door

    # ------------------------------------------------------------------
    # Disaggregated rollouts: the fleet backend (train.rollout_backend)
    # ------------------------------------------------------------------

    def _fleet_rollouts_enabled(self) -> bool:
        """Whether make_experience should generate on the rollout fleet.
        Default "local" keeps the pre-fleet path bit-identical."""
        backend = getattr(self.config.train, "rollout_backend", "local")
        if backend not in ("local", "fleet"):
            raise ValueError(
                f"unknown train.rollout_backend {backend!r} (want 'local' or 'fleet')"
            )
        if backend != "fleet":
            return False
        if self.seq2seq:
            logger.warning_once(
                "rollout_backend='fleet' does not support seq2seq models; "
                "generating locally"
            )
            return False
        return True

    def _get_rollout_router(self):
        """Build (once) the ReplicaRouter from train.rollout_fleet_*.
        Under train.rollout_fleet_supervised the router is owned by a
        FleetSupervisor that launches the replicas itself."""
        if self._rollout_router is None:
            train = self.config.train
            if getattr(train, "rollout_fleet_supervised", False):
                self._rollout_router = self._start_rollout_supervisor().router
                return self._rollout_router
            from trlx_tpu.inference.fleet import ReplicaRouter

            urls = list(getattr(train, "rollout_fleet_urls", None) or [])
            if not urls:
                raise ValueError(
                    "train.rollout_backend='fleet' needs train.rollout_fleet_urls"
                )
            kwargs = dict(getattr(train, "rollout_fleet_kwargs", None) or {})
            kwargs.setdefault(
                "max_staleness_steps",
                getattr(train, "rollout_max_staleness_steps", 1),
            )
            if train.tracing:
                kwargs.setdefault("tracer", self._get_rollout_tracer())
            self._rollout_router = ReplicaRouter(urls, **kwargs)
        return self._rollout_router

    def _get_rollout_tracer(self):
        """Router-side tracer (train.tracing): dispatch/attempt span
        trees with the winning replica's server-side spans grafted in."""
        if self._rollout_tracer is None:
            from trlx_tpu.observability import Tracer

            icfg = self.config.inference
            self._rollout_tracer = Tracer(
                max_traces=icfg.trace_ring,
                sample_rate=icfg.trace_sample_rate,
            )
        return self._rollout_tracer

    def _start_rollout_supervisor(self):
        """Launch the self-supervised rollout fleet: `rollout_fleet_size`
        in-process thread replicas (+ `rollout_fleet_spares` warm spares)
        spawned from the trainer's own serve(), lifecycle-managed by a
        FleetSupervisor — crashed replicas respawn with backoff,
        crash-loopers quarantine, and new manifest-complete checkpoints
        under train.checkpoint_dir roll through the fleet one replica at
        a time (capacity >= N-1 throughout)."""
        if self._rollout_supervisor is None:
            from trlx_tpu.inference.supervisor import FleetSupervisor, ThreadReplica

            train = self.config.train
            sup_kwargs = dict(
                getattr(train, "rollout_fleet_supervisor_kwargs", None) or {}
            )
            router_kwargs = dict(getattr(train, "rollout_fleet_kwargs", None) or {})
            router_kwargs.setdefault(
                "max_staleness_steps",
                getattr(train, "rollout_max_staleness_steps", 1),
            )
            if train.tracing:
                from trlx_tpu.observability import FlightRecorder

                router_kwargs.setdefault("tracer", self._get_rollout_tracer())
                sup_kwargs.setdefault(
                    "recorder",
                    FlightRecorder(
                        "supervisor",
                        self.config.inference.flight_recorder_events,
                    ),
                )
                sup_kwargs.setdefault("postmortem_dir", train.postmortem_dir)
            watch_dir = sup_kwargs.pop("watch_dir", train.checkpoint_dir)

            def factory(seat_index):
                def boot():
                    # watch_dir="" (-> None): replicas must NOT self-watch
                    # checkpoints — the supervisor owns reloads (rolling,
                    # one replica at a time)
                    server = self.serve(
                        host="127.0.0.1", port=0, watch_dir="", background=True
                    )
                    # replica-level fault injection (healthz_hang_s,
                    # kill_replica) follows the trainer's injector
                    server.fault_injector = self.fault_injector
                    return server

                return ThreadReplica(boot)

            supervisor = FleetSupervisor(
                factory,
                num_replicas=int(getattr(train, "rollout_fleet_size", 2)),
                spares=int(getattr(train, "rollout_fleet_spares", 0)),
                router_kwargs=router_kwargs,
                watch_dir=watch_dir,
                fault_injector=self.fault_injector,
                **sup_kwargs,
            )
            supervisor.start()
            if not supervisor.wait_ready(timeout_s=supervisor.start_timeout_s):
                supervisor.stop()
                raise RuntimeError(
                    "supervised rollout fleet failed to reach full capacity "
                    f"within {supervisor.start_timeout_s}s"
                )
            self._rollout_supervisor = supervisor
        return self._rollout_supervisor

    def shutdown_rollout_fleet(self) -> None:
        """Tear down the rollout fleet: stop supervision, kill thread
        replicas, close the router. Safe to call when no fleet was ever
        started; learn() calls this on the way out so replicas never
        outlive the trainer."""
        supervisor, self._rollout_supervisor = self._rollout_supervisor, None
        router, self._rollout_router = self._rollout_router, None
        if supervisor is not None:
            supervisor.stop()  # kills replicas + closes the router it owns
        elif router is not None:
            router.close()
        if self._rollout_tracer is not None:
            import os

            trace_dir = self.config.train.trace_dir or "logs/traces"
            try:
                path = self._rollout_tracer.write_chrome_trace(
                    os.path.join(trace_dir, "rollout_requests.json")
                )
                logger.info(f"Wrote rollout request trace to {path}")
            except Exception:
                logger.exception("Failed to write rollout request trace")

    def _push_params_to_thread_replicas(self) -> None:
        """Refresh in-process (ThreadReplica) seats with the live policy.
        Out-of-process replicas pick up new weights through the
        supervisor's checkpoint rolling sync; thread replicas share our
        process, so their engines hold direct references to trainer
        buffers — which the jitted train step donates every optimizer
        step. Push a donation-safe snapshot (one copy, shared by every
        seat) whenever the trainer has stepped since the last push, so a
        rollout cycle after an update never serves from deleted arrays."""
        sup = self._rollout_supervisor
        if sup is None or self.iter_count == self._fleet_params_step:
            return
        params = None
        for seat in sup.seats:
            engine = getattr(getattr(seat.handle, "server", None), "engine", None)
            if engine is None:
                continue
            if params is None:
                params = self.serving_params()
            engine.set_params(params)
        self._fleet_params_step = self.iter_count

    def _fleet_generate(self, batch, gen_kwargs, trainer_step: int = 0):
        """Generate one chunk on the rollout fleet; same out-dict shape as
        the local sampler (`samples` = prompt block + response columns,
        `response_tokens`/`response_mask`) plus per-token behavior-policy
        logprobs from the replicas' decode path. If the whole fleet is
        down the chunk degrades to local generation with a one-time
        warning — a cycle never fails because replicas did."""
        from trlx_tpu.inference.fleet import FleetUnavailableError

        pad_id = self.tokenizer.pad_token_id
        max_new = int(gen_kwargs.get("max_new_tokens", 40))
        input_ids = np.asarray(batch["input_ids"])
        attention_mask = np.asarray(batch["attention_mask"])
        # per-row unpadded prompt ids (replicas left-pad nothing; the
        # local layout is restored when reassembling `samples` below)
        prompts = [
            [int(t) for t, m in zip(row, mask) if m]
            for row, mask in zip(input_ids, attention_mask)
        ]
        router = self._get_rollout_router()
        if self._rollout_supervisor is not None:
            self._push_params_to_thread_replicas()
            # supervised replicas only advance when the supervisor rolls
            # a checkpoint through the fleet, so the staleness bound
            # anchors to the last synced step — anchoring to the raw
            # trainer step would blacklist the whole fleet whenever
            # checkpoint cadence lags the optimizer
            router.set_trainer_step(self._rollout_supervisor.synced_step)
        else:
            router.set_trainer_step(trainer_step)
        try:
            replies = router.generate(prompts, max_new_tokens=max_new)
        except FleetUnavailableError as e:
            logger.warning_once(
                f"rollout fleet unavailable; degrading to local generation ({e})"
            )
            out = dict(self.generate(batch["input_ids"], batch["attention_mask"], gen_kwargs))
            out["fleet_degraded"] = True
            return out

        n, plen = input_ids.shape
        samples = np.full((n, plen + max_new), pad_id, dtype=np.int32)
        samples[:, :plen] = input_ids
        response_tokens = np.full((n, max_new), pad_id, dtype=np.int32)
        response_mask = np.zeros((n, max_new), dtype=np.int32)
        behavior_logprobs = np.zeros((n, max_new), dtype=np.float32)
        for i, rep in enumerate(replies):
            toks = list(rep["token_ids"])[:max_new]
            lps = list(rep.get("token_logprobs") or [])[: len(toks)]
            samples[i, plen : plen + len(toks)] = toks
            response_tokens[i, : len(toks)] = toks
            response_mask[i, : len(toks)] = 1
            behavior_logprobs[i, : len(lps)] = lps
        return {
            "samples": samples,
            "response_tokens": response_tokens,
            "response_mask": response_mask,
            "behavior_logprobs": behavior_logprobs,
            "fleet": True,
        }

    def _apply_behavior_logprobs(self, logprobs, out, prompt_tensors, sample_outputs):
        """Overwrite the scorer's policy logprobs with the replicas'
        per-token BEHAVIOR-policy logprobs for rows where the retokenized
        response round-tripped exactly (raw sampled tokens == retokenized
        tokens — the same arbitration the rollout fast path uses). The
        importance ratio wants the sampling policy's logprobs; on a
        one-step-stale replica those differ from the trainer's. Rows that
        don't round-trip keep the trainer-side logprobs. Returns the
        number of rows overwritten; `logprobs` is modified in place."""
        pad_id = self.tokenizer.pad_token_id
        raw_tokens = np.asarray(out["response_tokens"])
        raw_mask = np.asarray(out["response_mask"])
        behavior = np.asarray(out["behavior_logprobs"])
        start = prompt_tensors.shape[1] - 1
        hits = 0
        for ix in range(len(sample_outputs)):
            n_resp = int((sample_outputs[ix] != pad_id).sum())
            n_raw = int(raw_mask[ix].sum())
            if n_resp == 0 or n_resp != n_raw:
                continue
            if not np.array_equal(sample_outputs[ix, :n_resp], raw_tokens[ix, :n_resp]):
                continue
            logprobs[ix, start : start + n_resp] = behavior[ix, :n_resp]
            hits += 1
        return hits

    def make_experience(self, num_rollouts: int = 1024, iter_count: int = 0):
        """Collect rollouts: generate -> (host) decode & reward -> jitted
        logprob/value/ref precompute -> per-token KL-penalized rewards ->
        store (reference accelerate_ppo_trainer.py:251-524).

        Multi-host: every host runs this identical host loop over the SAME
        global chunk (device compute is sharded by GSPMD; host work is
        replicated), except reward scoring, which shards by process and
        allgathers (_score_samples) — the counterpart of the reference's
        rank-0 score + scatter (accelerate_ppo_trainer.py:292-338), chosen
        so a stochastic reward_fn still yields host-identical stores."""
        if getattr(self.config.method, "multiturn_env", None):
            return self.make_experience_multiturn(num_rollouts, iter_count)
        logger.info("Collecting rollouts")
        if self._score_fn is None:
            self._build_score_fn()
        with self._span("ppo.make_experience", phase="make_experience", step=iter_count):
            self._collect_rollouts(num_rollouts, iter_count)

    def _collect_rollouts(self, num_rollouts: int, iter_count: int):
        """The chunk loop of `make_experience`, inside its span. Every site
        that is timed is one `_span`: the profiler's span, the phase on the
        timeline and the `time/*` stat come from the same two clock reads."""
        ppo_rl_elements: List[PPORLElement] = []
        accumulated_stats: List[Dict] = []
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        max_new = int(gen_kwargs.get("max_new_tokens", 40))
        self._open_trunk_cache()
        self._prefill_tally[:] = 0

        # Double-buffered generation: the NEXT chunk's sampling is
        # dispatched before the current chunk's device->host sync, so the
        # host-side decode/reward/element work runs while the device is
        # already generating ahead (params are fixed for the whole
        # collection, so this changes no semantics). Each chunk appends
        # exactly one element per prompt, so "will another chunk be
        # needed" is decidable before processing this one.
        use_fleet = self._fleet_rollouts_enabled()
        chunk_ids = itertools.count()

        def _dispatch_next():
            """(batch, generation handles, chunk number, when it was dispatched)"""
            b = next(self.prompt_iterator)
            chunk, t_dispatch = next(chunk_ids), time.monotonic()
            with self._span("ppo.generate_dispatch", chunk=chunk, rows=len(b["input_ids"])):
                if use_fleet:
                    out = self._fleet_generate(b, gen_kwargs, trainer_step=iter_count)
                else:
                    out = self._rollout_generate(b, gen_kwargs)
            return b, out, chunk, t_dispatch

        pending = _dispatch_next()

        while len(ppo_rl_elements) < num_rollouts:
            if self._watchdog is not None:
                # rollout chunks are legitimate long gaps between step
                # boundaries — each one is a heartbeat
                self._watchdog.beat()
            if pending is None:
                # the quarantine pass can drop rows and under-fill the
                # prefetch prediction below: dispatch another chunk
                pending = _dispatch_next()
            stats: Dict[str, float] = {}
            batch, out, chunk, t_dispatch = pending
            pending = None
            n_this = len(np.asarray(batch["input_ids"]))
            if len(ppo_rl_elements) + n_this < num_rollouts:
                pending = _dispatch_next()

            with self._span(
                "ppo.rollout_fetch", phase="rollout_generate", step=iter_count,
                chunk=chunk, rows=n_this,
                # a fleet chunk that fell back to local generation is
                # degraded capacity — the goodput ledger charges its
                # wall time to waste/fleet_degraded
                degraded=bool(use_fleet and not out.get("fleet")),
            ) as fetch:
                samples = np.asarray(out["samples"])  # materialize (also syncs device)
            # from the chunk's dispatch to its samples on the host: with the
            # next chunk dispatched ahead, the fetch alone is only the wait
            # for what was left of the generation
            gen_s = max(fetch.t1 - t_dispatch, 1e-9)
            stats["time/rollout_generate"] = 1e3 * gen_s
            # throughput over REAL generated tokens (the validity mask —
            # padding after eos doesn't count)
            real_tokens = int(np.asarray(out["response_mask"]).sum())
            stats["throughput/rollout_tokens_per_s"] = real_tokens / gen_s
            stats["throughput/rollout_requests_per_s"] = n_this / gen_s

            with self._span("ppo.rollout_process", phase="rollout_process",
                            step=iter_count, chunk=chunk) as process:
                elements, mean_kl, mean_kl_per_token = self._process_chunk(
                    batch, out, samples, stats, iter_count, chunk, use_fleet, fetch.t0)
                ppo_rl_elements.extend(elements)
            stats["time/rollout_time"] = 1e3 * process.seconds
            if self._goodput is not None:
                self._goodput_configure(np.asarray(batch["input_ids"]).shape[1], max_new)
                self._goodput.note_rollout_chunk(n_this)
            stats["policy/sqrt_kl"] = float(np.sqrt(max(mean_kl, 0.0)))
            stats["policy/kl_per_token"] = float(np.sqrt(max(mean_kl_per_token, 0.0)))
            accumulated_stats.append(stats)
            logger.info(f"[rollout {len(ppo_rl_elements)} / {num_rollouts}]")

        stats = {
            k: sum(xs[k] for xs in accumulated_stats) / len(accumulated_stats)
            for k in accumulated_stats[-1]
        }
        stats["kl_ctl_value"] = self.kl_ctl.value
        calls, widths, padded, pad = (int(x) for x in self._prefill_tally)
        if calls:
            stats["rollout/prefill_width"] = widths / calls
            stats["rollout/prefill_padding_share"] = pad / padded
        if use_fleet and self._rollout_router is not None:
            # router lifetime counters (not per-chunk, so merged after
            # the per-chunk averaging above)
            for k, v in self._rollout_router.stats().items():
                if isinstance(v, (int, float)):
                    stats[f"fleet/{k}"] = float(v)
        if use_fleet and self._rollout_supervisor is not None:
            # supervisor lifecycle counters (respawns, quarantines,
            # promotions, rolling-sync progress, live capacity)
            for k, v in self._rollout_supervisor.stats().items():
                if isinstance(v, (int, float)):
                    stats[f"fleet/{k}"] = float(v)
        self.mean_kl = stats["policy/sqrt_kl"] ** 2
        self.tracker.log(stats, step=iter_count)
        self._close_trunk_cache()
        self.push_to_store(ppo_rl_elements)

    def _process_chunk(self, batch, out, samples, stats, iter_count, chunk,
                       use_fleet, t_chunk0):
        """One fetched chunk, from its samples on the host to its store
        elements: host decode and reward, the scorer's dispatch and fetch,
        the sentinel's quarantine. Returns (elements, mean_kl,
        mean_kl_per_token) and writes the chunk's stats into `stats`."""
        with self._span("ppo.host_process", phase="rollout_score", step=iter_count):
            prompt_tensors, sample_outputs, outputs, scores, scores_mask = (
                self._host_process_chunk(batch, samples, stats)
            )

        # Jitted precompute of logprobs/values/ref KL
        with self._span("ppo.score_dispatch", chunk=chunk):
            if self.seq2seq:
                scored = self._score_fn(
                    self.train_params, self.frozen_params, self.ref_params,
                    jnp.asarray(prompt_tensors), jnp.asarray(sample_outputs),
                )
            else:
                all_tokens = np.concatenate([prompt_tensors, sample_outputs], axis=1)
                # a chunk the rule counted (`_score_hands_out_trunk_state`):
                # its trunk state stays on the device as the score program
                # leaves it. A chunk that a quarantine made necessary beyond
                # the collection's planned number was not in the reckoning
                # and is filled at the collection's end like any other.
                take_state = self._takes_trunk_state(chunk)
                scored = self._score_fn(
                    self.train_params, self.frozen_params, self.ref_params,
                    jnp.asarray(all_tokens), **({"trunk_state": True} if take_state else {}),
                )
        # ONE batched device->host fetch: sequential np.asarray calls
        # each block until their own transfer lands, jax.device_get
        # pipelines them together. The trunk state is not in it.
        trunk_state = scored[5:]
        logprobs, values, log_ratio, mean_kl, mean_kl_per_token = jax.device_get(scored[:5])
        trunk_row0 = None
        if self._trunk_chunks is not None:
            # the chunk's rows of the cycle's trunk cache: its elements
            # carry their numbers, and the collection's end fills those
            # whose state the score program did not hand out
            trunk_row0 = self._note_trunk_chunk(prompt_tensors, sample_outputs, *trunk_state)
        mean_kl = float(mean_kl)
        mean_kl_per_token = float(mean_kl_per_token)

        if use_fleet:
            # stats keys must be identical across chunks (the final
            # averaging iterates the last chunk's keys), so both are
            # set every chunk — including degraded ones
            if out.get("fleet"):
                logprobs = np.array(logprobs)  # device_get can be read-only
                hits = self._apply_behavior_logprobs(
                    logprobs, out, prompt_tensors, sample_outputs
                )
                stats["fleet/behavior_logprob_rows"] = float(hits)
                stats["fleet/degraded_chunks"] = 0.0
            else:
                stats["fleet/behavior_logprob_rows"] = 0.0
                stats["fleet/degraded_chunks"] = 1.0

        elements = self._chunk_to_elements(
            prompt_tensors, sample_outputs, outputs, scores, scores_mask,
            logprobs, values, log_ratio, trunk_row0,
        )
        if self._sentinel is not None:
            # rollout quarantine + anomaly observation. Element-level
            # (post-scorer) so dropping rows never changes the jitted
            # score fn's shapes; stats keys are set on EVERY chunk
            # (the final averaging iterates the last chunk's keys).
            elements, n_dropped = self._quarantine_elements(
                elements, scores, scores_mask, outputs
            )
            stats["sentinel/quarantined_rows"] = float(n_dropped)
            if n_dropped and self._goodput is not None:
                # the dropped rows' share of this chunk's wall time is
                # MOVED (not added) into waste/quarantined so the
                # ledger keeps summing to wall time
                self._goodput.note_quarantine(
                    n_dropped,
                    (n_dropped / max(len(samples), 1))
                    * (time.monotonic() - t_chunk0),
                )
            stats["rollout/entropy"] = (
                float(np.mean([-np.mean(e.logprobs) for e in elements]))
                if elements else 0.0
            )
            self._sentinel.observe_rollout(stats)
        return elements, mean_kl, mean_kl_per_token

    # ------------------------------------------------------------------
    # Multi-turn experience (tool-use environments over fleet sessions)
    # ------------------------------------------------------------------

    def _multiturn_group_size(self) -> int:
        """Episodes per shared environment seed. 1 for PPO; GRPO overrides
        with G so group-relative advantages compare same-task episodes."""
        return 1

    def _run_episode(self, router, env, seed, max_new, max_turns):
        """One conversation: alternate policy turns (fleet chat session —
        the serving replica retains the conversation's KV between turns,
        so every turn after the first prefills only its delta tokens) with
        environment responses. Returns ``(prompt_ids, segments,
        retained_hits)``; segments are ``(kind, ids, logprobs, reward)``
        with kind "policy" or "env" — the reward belongs to the policy
        turn it is attached to."""
        import uuid as _uuid

        tok = self.tokenizer
        obs = env.reset(seed)
        prompt_ids = [int(t) for t in tok.encode(obs)]
        key = f"mt-{_uuid.uuid4().hex[:12]}"
        segments = []
        retained_hits = 0
        turn_ids = prompt_ids
        try:
            for t in range(max_turns):
                out = router.chat(turn_ids, session_key=key,
                                  max_new_tokens=max_new)
                resp_ids = [int(x) for x in out["token_ids"]]
                retained_hits += int(bool(out.get("retained_hit")))
                text = out.get("text")
                if text is None:
                    text = tok.decode(resp_ids)
                step_out = env.step(text)
                lps = [float(x) for x in (out.get("token_logprobs") or [])]
                segments.append(
                    ("policy", resp_ids, lps[: len(resp_ids)],
                     float(step_out.reward))
                )
                if step_out.done or t == max_turns - 1:
                    break
                env_ids = [int(x) for x in tok.encode(step_out.text)]
                if not env_ids:
                    # /chat needs a non-empty turn; a silent environment
                    # still has to hand the floor back to the policy
                    env_ids = [int(x) for x in tok.encode(" ")]
                segments.append(("env", env_ids, None, 0.0))
                turn_ids = env_ids
        finally:
            router.end_session(key)
        return prompt_ids, segments, retained_hits

    def make_experience_multiturn(self, num_rollouts: int = 1024,
                                  iter_count: int = 0):
        """Collect multi-turn rollouts (method.multiturn_env): whole
        environment episodes driven through fleet chat sessions. Each
        episode becomes ONE rollout element whose response concatenates
        every turn after the opening observation — policy turns carry
        loss_mask 1.0 and their turn reward on their last token;
        environment-authored turns carry loss_mask 0.0 (context, not
        actions) and no KL penalty. Raw turn rewards are used as-is
        (environments own their scale; scale_reward does not apply)."""
        from trlx_tpu.environments import make_environment

        logger.info("Collecting multi-turn rollouts")
        if self.seq2seq:
            raise NotImplementedError("multi-turn rollouts are causal-only")
        if not self._fleet_rollouts_enabled():
            raise ValueError(
                "method.multiturn_env requires train.rollout_backend='fleet' "
                "(episodes run through fleet chat sessions)"
            )
        if self._score_fn is None:
            self._build_score_fn()
        method = self.config.method
        env_kwargs = dict(getattr(method, "multiturn_env_kwargs", None) or {})
        max_turns = max(int(getattr(method, "multiturn_max_turns", 4)), 1)
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        max_new = int(gen_kwargs.get("max_new_tokens", 40))
        G = max(self._multiturn_group_size(), 1)

        router = self._get_rollout_router()
        if self._rollout_supervisor is not None:
            self._push_params_to_thread_replicas()
            router.set_trainer_step(self._rollout_supervisor.synced_step)
        else:
            router.set_trainer_step(iter_count)

        elements: List[PPORLElement] = []
        accumulated: List[Dict] = []
        seed0 = int(getattr(self, "_mt_seed_offset", 0))
        chunk_size = max(int(method.chunk_size), 1)
        clock = Clock()
        while len(elements) < num_rollouts:
            if self._watchdog is not None:
                self._watchdog.beat()
            n_chunk = min(chunk_size, num_rollouts - len(elements))
            n_chunk = max((n_chunk + G - 1) // G * G, G)  # whole groups
            clock.tick()

            def one(i):
                env = make_environment(method.multiturn_env, **env_kwargs)
                # same-seed groups: episodes i with equal i // G play the
                # same task, differing only by sampling
                return self._run_episode(
                    router, env, seed0 + i // G, max_new, max_turns
                )

            with ThreadPoolExecutor(max_workers=min(n_chunk, 8)) as pool:
                episodes = list(pool.map(one, range(n_chunk)))
            seed0 += n_chunk // G
            stats: Dict[str, float] = {
                "time/rollout_generate": clock.tick(),
            }
            elements.extend(self._episodes_to_elements(episodes, stats))
            stats["time/rollout_time"] = clock.tick()
            accumulated.append(stats)
            logger.info(
                f"[multi-turn rollout {len(elements)} / {num_rollouts}]"
            )
        self._mt_seed_offset = seed0
        stats = {
            k: sum(x[k] for x in accumulated) / len(accumulated)
            for k in accumulated[-1]
        }
        stats["kl_ctl_value"] = self.kl_ctl.value
        if self._rollout_router is not None:
            for k, v in self._rollout_router.stats().items():
                if isinstance(v, (int, float)):
                    stats[f"fleet/{k}"] = float(v)
        self.mean_kl = stats["policy/sqrt_kl"] ** 2
        self.tracker.log(stats, step=iter_count)
        self.push_to_store(elements)

    def _episodes_to_elements(self, episodes, stats):
        """Pad one chunk of episodes into a fixed-shape batch, run the
        jitted scorer, splice in the replicas' behavior logprobs on
        policy tokens, and hand off to `_multiturn_elements` (PPO per-
        token rewards; GRPO group advantages)."""
        pad_id = self.tokenizer.pad_token_id
        n = len(episodes)
        max_q = max(len(p) for p, _, _ in episodes)
        rows = []
        for prompt_ids, segments, hits in episodes:
            ids: List[int] = []
            lmask: List[float] = []
            erew: List[float] = []
            blps: List[Optional[float]] = []
            for kind, seg_ids, lps, reward in segments:
                pol = kind == "policy"
                ids.extend(seg_ids)
                lmask.extend([1.0 if pol else 0.0] * len(seg_ids))
                erew.extend([0.0] * len(seg_ids))
                if pol and seg_ids:
                    erew[-1] = float(reward)  # turn reward on last token
                if pol:
                    blps.extend(
                        list(lps) + [None] * (len(seg_ids) - len(lps))
                    )
                else:
                    blps.extend([None] * len(seg_ids))
            if not ids:  # degenerate episode (empty first reply)
                ids, lmask, erew, blps = [pad_id], [0.0], [0.0], [None]
            rows.append((prompt_ids, ids, lmask, erew, blps, hits))
        # cap the scored width at the train context; a conversation past
        # it loses its tail tokens (and any reward sitting on them)
        cap = max(int(self.config.train.seq_length) - max_q, 1)
        max_r = min(max(len(r[1]) for r in rows), cap)

        prompt_tensors = np.full((n, max_q), pad_id, np.int32)
        sample_outputs = np.full((n, max_r), pad_id, np.int32)
        loss_mask = np.zeros((n, max_r), np.float32)
        env_rewards = np.zeros((n, max_r), np.float32)
        left = self.tokenizer.padding_side == "left"
        for i, (p, ids, lm, er, _bl, _h) in enumerate(rows):
            w = min(len(ids), max_r)
            if left:
                prompt_tensors[i, max_q - len(p):] = p
            else:
                prompt_tensors[i, : len(p)] = p
            sample_outputs[i, :w] = ids[:w]
            loss_mask[i, :w] = lm[:w]
            env_rewards[i, :w] = er[:w]

        all_tokens = np.concatenate([prompt_tensors, sample_outputs], axis=1)
        logprobs, values, log_ratio, mean_kl, mean_kl_per_token = self._score_fn(
            self.train_params, self.frozen_params, self.ref_params,
            jnp.asarray(all_tokens),
        )
        logprobs, values, log_ratio, mean_kl, mean_kl_per_token = jax.device_get(
            (logprobs, values, log_ratio, mean_kl, mean_kl_per_token)
        )
        logprobs = np.array(logprobs)  # device_get can be read-only
        start = max_q - 1
        # the replica's sampler is the behavior policy: its logprob for
        # response token j (all_tokens column max_q + j) lands at scorer
        # column start + j
        for i, (_p, _ids, _lm, _er, bl, _h) in enumerate(rows):
            for j, lp in enumerate(bl[:max_r]):
                if lp is not None:
                    logprobs[i, start + j] = lp
        stats["policy/sqrt_kl"] = float(np.sqrt(max(float(mean_kl), 0.0)))
        stats["policy/kl_per_token"] = float(
            np.sqrt(max(float(mean_kl_per_token), 0.0))
        )
        stats["rollout/mean_env_reward"] = float(env_rewards.sum(1).mean())
        stats["rollout/mean_turns"] = float(
            np.mean([
                sum(1 for s in segs if s[0] == "policy")
                for _, segs, _ in episodes
            ])
        )
        stats["rollout/retained_hit_turns"] = float(
            sum(r[5] for r in rows)
        )
        return self._multiturn_elements(
            rows, prompt_tensors, sample_outputs, loss_mask, env_rewards,
            np.asarray(logprobs), np.asarray(values), np.asarray(log_ratio),
            start, max_r,
        )

    def _multiturn_elements(self, rows, prompt_tensors, sample_outputs,
                            loss_mask, env_rewards, logprobs, values,
                            log_ratio, start, max_r):
        """PPO rewards for one multi-turn chunk: per-token KL penalty on
        policy tokens only, plus each turn's environment reward on that
        turn's last token. GAE then runs over the whole response; the
        loss mask keeps environment tokens out of the objective."""
        kl_coef = self.kl_ctl.value
        if self._sentinel is not None:
            kl_coef *= self._sentinel.kl_scale(self.iter_count)
        elements = []
        for i, (_p, ids, _lm, _er, _bl, _h) in enumerate(rows):
            n_resp = max(min(len(ids), max_r), 1)
            end = start + n_resp
            lmask_row = np.asarray(loss_mask[i, :n_resp], np.float32)
            rewards = (-kl_coef * log_ratio[i, start:end]) * lmask_row
            rewards = rewards.astype(np.float32) + env_rewards[i, :n_resp]
            elements.append(
                PPORLElement(
                    query_tensor=prompt_tensors[i],
                    response_tensor=sample_outputs[i, :n_resp],
                    logprobs=logprobs[i, start:end],
                    values=values[i, start:end],
                    rewards=rewards,
                    loss_mask=lmask_row.copy(),
                )
            )
        return elements

    # ------------------------------------------------------------------
    # Loop wiring (reference accelerate_ppo_trainer.py:219-249)
    # ------------------------------------------------------------------

    def _score_samples(self, str_samples, str_prompts, str_outputs, metadata):
        """reward_fn over a decoded chunk -> list of per-sample score rows
        (np arrays; length 1 for scalar rewards, >1 for dense).

        Multi-host: each process scores only its slice of the chunk, the
        padded rows are allgathered, and every host reconstructs the full
        chunk's scores — one scoring pass total instead of one per host,
        and host-identical results even for a stochastic reward_fn
        (reference: rank-0 scoring + scatter,
        accelerate_ppo_trainer.py:292-338)."""
        n = len(str_samples)
        P = jax.process_count()

        def score(sl):
            rows = self.reward_fn(
                samples=str_samples[sl],
                prompts=str_prompts[sl],
                outputs=str_outputs[sl],
                tokenizer=self.tokenizer,
                **{k: v[sl] for k, v in metadata.items()},
            )
            return [np.atleast_1d(np.asarray(r, dtype=np.float32)) for r in rows]

        if P == 1:
            return score(slice(None))
        from jax.experimental import multihost_utils

        if n % P == 0:
            p = jax.process_index()
            nl = n // P
            local = score(slice(p * nl, (p + 1) * nl))
        else:
            # ragged chunk (e.g. a drop_last=False epoch tail): rank 0
            # scores everything and the gather below broadcasts its rows —
            # per-host independent scoring would diverge for a stochastic
            # reward_fn (set_seed offsets np.random per process)
            nl = n
            local = (score(slice(None)) if jax.process_index() == 0
                     else [np.zeros(1, np.float32)] * n)

        # Explicit per-row lengths + a host-agreed width: no truncation of
        # dense rows longer than max_new, and data values (incl. a user's
        # interior -inf) survive the round trip untouched.
        local_w = max((len(r) for r in local), default=1)
        W = max(int(np.max(multihost_utils.process_allgather(np.int32(local_w)))), 1)
        buf = np.zeros((nl, W), dtype=np.float32)
        lens = np.zeros(nl, dtype=np.int32)
        for i, r in enumerate(local):
            lens[i] = len(r)
            buf[i, : len(r)] = r
        gbuf = np.asarray(multihost_utils.process_allgather(buf))
        glens = np.asarray(multihost_utils.process_allgather(lens))
        if n % P == 0:
            gbuf, glens = gbuf.reshape(n, W), glens.reshape(n)
        else:
            gbuf, glens = gbuf[0], glens[0]  # everyone adopts rank 0's rows
        return [gbuf[i, : max(int(glens[i]), 1)] for i in range(n)]

    def _host_process_chunk(self, batch, samples, stats=None):
        """The host stage of one rollout chunk: decode -> reward_fn ->
        retokenize/right-pad the (possibly stop-trimmed) outputs ->
        clip -> running-moments reward scaling. Shared by make_experience
        and pipelined_cycle so the two cycle paths cannot drift
        (reference accelerate_ppo_trainer.py:303-380). Returns
        (prompt_tensors, sample_outputs, outputs, scores, scores_mask);
        records score timing + rollout_scores stats into `stats`."""
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        max_new = int(gen_kwargs.get("max_new_tokens", 40))

        prompt_tensors = np.asarray(batch["input_ids"])
        n_samples = len(samples)
        prompt_sizes = [prompt_tensors.shape[1]] * n_samples
        with self._span("ppo.host_decode"):
            str_samples, str_prompts, str_outputs = self.decode(
                prompt_tensors, samples, prompt_sizes, append_eos_token=True
            )
        metadata = {
            k: v for k, v in batch.items() if k not in ("input_ids", "attention_mask")
        }
        # the host reward round trip, split out of rollout_score so the
        # goodput ledger can attribute reward RTT as its own cause
        with self._span("ppo.reward", phase="host_reward") as reward:
            score_rows = self._score_samples(str_samples, str_prompts, str_outputs, metadata)
        if stats is not None:
            stats["time/rollout_score"] = 1e3 * reward.seconds
        S = max(len(r) for r in score_rows)
        scores = np.full((n_samples, S), -np.inf, dtype=np.float32)
        for i, r in enumerate(score_rows):
            scores[i, : len(r)] = r
        scores_mask = scores != -np.inf

        outputs = [
            self.tokenizer.encode(o, add_special_tokens=False)[:max_new]
            for o in str_outputs
        ]
        if self.seq2seq:
            # decoder-side responses start with decoder_start_token
            start_id = int(getattr(self.model_cfg, "decoder_start_token_id", pad_id))
            sample_outputs = np.full((n_samples, 1 + max_new), pad_id, dtype=np.int32)
            sample_outputs[:, 0] = start_id
            for i, o in enumerate(outputs):
                sample_outputs[i, 1 : 1 + len(o)] = o
        else:
            sample_outputs = np.full((n_samples, max_new), pad_id, dtype=np.int32)
            for i, o in enumerate(outputs):
                sample_outputs[i, : len(o)] = o

        if method.cliprange_reward:
            scores = np.where(
                scores_mask,
                np.clip(scores, -method.cliprange_reward, method.cliprange_reward),
                scores,
            )

        # Reward scaling stats (reference accelerate_ppo_trainer.py:364-380)
        sample_scores = (np.where(scores_mask, scores, 0.0)).sum(axis=1)
        if self.ref_mean is None:
            self.ref_mean, self.ref_std = float(sample_scores.mean()), float(sample_scores.std())
        all_scores_mean, all_scores_std = self.running_moments.update(sample_scores)
        if stats is not None:
            stats["rollout_scores/mean"] = all_scores_mean
            stats["rollout_scores/std"] = all_scores_std
            stats["rollout_scores/running_mean"] = self.running_moments.mean
            stats["rollout_scores/running_std"] = self.running_moments.std
        if method.scale_reward == "running":
            scores = np.where(scores_mask, scores / max(self.running_moments.std, 1e-8), scores)
        elif method.scale_reward == "ref":
            scores = np.where(scores_mask, scores / max(self.ref_std, 1e-8), scores)
        return prompt_tensors, sample_outputs, outputs, scores, scores_mask

    def _chunk_to_elements(self, prompt_tensors, sample_outputs, outputs,
                           scores, scores_mask, logprobs, values, log_ratio,
                           trunk_row0=None):
        """Slice per-sample response windows into PPORLElements (host
        numpy). logprob[i] is the (log)prob with which all_tokens[i+1] was
        sampled; for seq2seq everything is decoder-relative, so the window
        starts at 0. The in-graph reward construction of the pipelined
        cycle (_build_score_reward_fn) mirrors this block exactly — the
        parity test ties them together. `trunk_row0` is the row the
        chunk's first sample holds in the cycle's trunk cache (None: the
        chunk was not cached)."""
        pad_id = self.tokenizer.pad_token_id
        start = 0 if self.seq2seq else prompt_tensors.shape[1] - 1
        kl_coef = self.kl_ctl.value
        if self._sentinel is not None:
            # post-rewind cooldown: temporarily strengthen the pull toward
            # the reference policy (train.sentinel_kl_boost; 1.0 = off)
            kl_coef *= self._sentinel.kl_scale(self.iter_count)
        kl_penalty = -kl_coef * log_ratio

        elements = []
        for ix in range(len(sample_outputs)):
            if self.seq2seq:
                n_resp = max(len(outputs[ix]), 1)
                response_tensor = sample_outputs[ix, : n_resp + 1]
            else:
                n_resp = int((sample_outputs[ix] != pad_id).sum())
                if n_resp == 0:
                    n_resp = 1  # degenerate empty response: keep one slot
                response_tensor = sample_outputs[ix, :n_resp]
            end = start + n_resp
            rewards = kl_penalty[ix, start:end].copy()
            if scores.shape[1] == 1:
                # scalar score lands on the final token (HHH practice)
                rewards[-1] += scores[ix, 0]
            else:
                score_len = int(scores_mask[ix].sum())
                dense = scores[ix, :score_len]
                dense = dense[: len(rewards)]
                rewards[: len(dense)] += dense

            elements.append(
                PPORLElement(
                    query_tensor=prompt_tensors[ix],
                    response_tensor=response_tensor,
                    logprobs=logprobs[ix, start:end],
                    values=values[ix, start:end],
                    rewards=rewards,
                    trunk_row=None if trunk_row0 is None else trunk_row0 + ix,
                )
            )
        return elements

    def _quarantine_elements(self, elements, scores, scores_mask, outputs):
        """Sentinel rollout quarantine: drop reward-outlier and degenerate
        (length-collapse / repetition) rows from one chunk's elements
        before they enter the PPO store. Returns (kept, n_dropped)."""
        from trlx_tpu.sentinel import repetition_frac

        sample_scores = (np.where(scores_mask, scores, 0.0)).sum(axis=1)
        resp_lens = np.array([len(o) for o in outputs], dtype=np.int32)
        rep_fracs = np.array([repetition_frac(o) for o in outputs], dtype=np.float64)
        drop = self._sentinel.quarantine_mask(sample_scores, resp_lens, rep_fracs)
        if not drop.any():
            return elements, 0
        kept = [e for e, d in zip(elements, drop) if not d]
        return kept, int(drop.sum())

    def add_prompt_pipeline(self, pipeline):
        self.prompt_iterator = self._rollout_stream(pipeline, self.config.method.chunk_size)

    #: the width the rollout loader pads every chunk's prompts to, where its
    #: pipeline says (`_rollout_stream`)
    _rollout_prompt_width: Optional[int] = None

    def _rollout_plan(self, width: int, gen_kwargs):
        """The `BlockPlan` a rollout chunk of that prompt width is generated
        by, or None: the sampler keeps the one-shot prefill."""
        return self._block_plan(self._bucket_shape(1, width)[1], gen_kwargs)

    def _rollout_stream(self, pipeline, rows: int, **loader_kwargs) -> LoaderStream:
        """The rollout loader's chunks of `rows` prompts, forever. A pipeline
        that knows its prompts' lengths has every collection's prompts (a
        window of the shuffled order) sorted by length before they are cut
        into chunks: that is what makes a chunk's longest prompt short.
        Every chunk is generated at the pool's width; whether the program
        then follows the chunk's longest prompt is the sampler's own rule
        (`_rollout_plan`). The stream's place is part of the resume state."""
        window = rows * self._planned_chunks()
        if getattr(pipeline, "prompt_lengths", None) is not None:
            loader_kwargs["group_window"] = window
        self._prompt_stream = LoaderStream(pipeline.create_loader(rows, shuffle=True, **loader_kwargs))
        self._rollout_prompt_width = getattr(pipeline, "max_prompt_length", None)
        return self._prompt_stream

    def _rollout_generate(self, batch, gen_kwargs, **generate_kwargs):
        """`generate` for one rollout chunk. Its prefill is counted first:
        the rows and width the program runs, the prompt tokens among those
        positions, and the rest, padding; under a `BlockPlan` the width is
        that of the blocks run, and the span says how far the mechanism
        engaged (blocks run of the program's, cache columns a decode step
        reads of the cache's), from the chunk's mask by the program's own
        rule."""
        from trlx_tpu.ops.sampling import first_live_column

        input_ids = np.asarray(batch["input_ids"])
        attention_mask = np.asarray(batch["attention_mask"])
        rows, width = self._bucket_shape(*attention_mask.shape)
        plan = self._rollout_plan(attention_mask.shape[1], gen_kwargs)
        engaged = {}
        if plan is not None:
            # `_bucket_prompts` pads on the left, as the prompts are
            first = int(first_live_column(attention_mask)) + width - attention_mask.shape[1]
            blocks_run = plan.blocks - int(plan.first_block(first))
            width = blocks_run * plan.block
            engaged = dict(blocks=plan.blocks, blocks_run=blocks_run,
                           read_columns=plan.read_columns(first),
                           cache_columns=plan.columns)
        padded, tokens = rows * width, int(attention_mask.sum())
        self._prefill_tally += (1, width, padded, padded - tokens)
        if tracing.active():
            tracing.counters("ppo.prefill", calls=1, rows=rows, width=width,
                             prompt_tokens=tokens, padded_tokens=padded,
                             pad_tokens=padded - tokens, **engaged)
        return self.generate(input_ids, attention_mask, gen_kwargs, **generate_kwargs)

    def post_epoch_callback(self):
        if self.log_rollouts:
            self.store.export_history(location=self.rollout_logging_dir)
        self.store.clear_history()
        self.make_experience(self.config.method.num_rollouts, self.iter_count)

    def _post_rewind(self):
        """After a sentinel rewind the restored rollout store is the one
        whose successors bred the anomaly; drop it and collect fresh
        experience under the post-rewind PRNG stream and cooldown
        coefficients (damped LR / boosted KL)."""
        self.store.clear_history()
        self.make_experience(self.config.method.num_rollouts, self.iter_count)

    def _extra_resume_state(self):
        """PPO host state for exact resume: the in-flight rollout store
        (regenerating it would consume PRNG splits the interrupted run
        never drew), the KL controller, and the reward running moments —
        composed with the base trainer's state (sentinel ladder)."""
        extra = super()._extra_resume_state()
        if self._prompt_stream is not None:
            extra["prompt_stream"] = self._prompt_stream.state()
        extra.update({
            "store_history": list(self.store.history),
            "kl_ctl_value": float(self.kl_ctl.value),
            "mean_kl": float(self.mean_kl),
            "running_moments": {
                "mean": self.running_moments.mean,
                "std": self.running_moments.std,
                "var": self.running_moments.var,
                "count": self.running_moments.count,
            },
        })
        return extra

    def _load_extra_resume_state(self, state):
        super()._load_extra_resume_state(state)
        if "store_history" in state:
            self.store.clear_history()
            # the rows these rollouts held in a trunk cache went with the
            # process that filled it: they train from the whole forward
            self._trunk_cache = None
            self.store.push(
                [dataclasses.replace(e, trunk_row=None) for e in state["store_history"]])
        if "prompt_stream" in state and self._prompt_stream is not None:
            self._prompt_stream.restore(state["prompt_stream"])
        if "kl_ctl_value" in state:
            self.kl_ctl.value = state["kl_ctl_value"]
        self.mean_kl = state.get("mean_kl", self.mean_kl)
        for k, v in state.get("running_moments", {}).items():
            setattr(self.running_moments, k, v)

    # ------------------------------------------------------------------
    # Low-sync pipelined cycle: one blocking host fetch per PPO iteration
    # ------------------------------------------------------------------

    def dispatch_rollout_generation(self):
        """Dispatch generation for the next chunk WITHOUT a host sync.
        Called right after a train dispatch, the device runs it on the
        just-updated param handles, so rollouts stay on-policy. Under the
        rollout fast path the sampler additionally captures per-token
        logprobs/values and the hydra-split activations (and the cycle
        dispatches it BEFORE train, one step stale — still PPO-correct:
        the captured logprobs are the behavior policy's, which is exactly
        what the importance ratio needs)."""
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        batch = next(self.prompt_iterator)
        out = self._rollout_generate(batch, gen_kwargs, capture=self._fast_rollout_available())
        return batch, out

    def _build_score_reward_fn(self, scalar_scores: bool):
        """The score fn PLUS the per-token reward construction in-graph
        (mirrors _chunk_to_elements' numpy block), so logprobs/values/
        rewards never round-trip to the host: every blocking fetch stalls
        dispatch until the device drains, and the classic cycle pays three
        per iteration (samples, score outputs, loss). Returns
        (PPORLBatch chunk on device, mean_kl, mean_kl_per_token)."""
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        if self.seq2seq:
            # decoder-relative windows (start 0); response carries the
            # decoder start token at position 0, so the valid-response
            # count looks at positions 1: (mirrors _chunk_to_elements'
            # n_resp = max(len(outputs[ix]), 1)).
            # Deliberate divergence from reference seq2seq make_experience
            # (accelerate_ppo_trainer.py:470-486): the reference places the
            # scalar score at ends = n_nonpad + 1 (one slot PAST the last
            # real token, landing on a pad position) and masks log_ratio
            # with the decoder OUTPUT mask taken over positions [:-1] —
            # i.e. aligned with the decoder inputs, one slot off the label
            # positions the logprobs describe (not the encoder mask, which
            # never enters that expression). Both read as off-by-one
            # artifacts of its torch indexing; here the score lands on the
            # last real response token (j == n_resp - 1) and the KL mask is
            # the decoder mask shifted with the labels
            # (decoder_attention_mask[:, 1:]),
            # consistent with this repo's _chunk_to_elements and with the
            # causal path below. Curve parity is asserted on the causal
            # path (PARITY_CURVES.json); seq2seq bit-parity with the
            # reference's indexing is explicitly not a goal.
            def score_reward_s2s(train_params, frozen_params, ref_params,
                                 prompt_tensors, sample_outputs, scores_eff,
                                 kl_coef):
                params = merge_params(train_params, frozen_params)
                attention_mask = (prompt_tensors != pad_id).astype(jnp.int32)
                decoder_attention_mask = (sample_outputs != pad_id).astype(jnp.int32)
                decoder_attention_mask = decoder_attention_mask.at[:, 0].set(1)
                logits, values, ref_logits = forward_seq2seq_policy_and_ref(
                    model, params, ref_params,
                    prompt_tensors, attention_mask, sample_outputs,
                    decoder_attention_mask, split,
                )
                logprobs = logprobs_of_labels(logits[:, :-1, :], sample_outputs[:, 1:])
                ref_logprobs = logprobs_of_labels(
                    ref_logits[:, :-1, :], sample_outputs[:, 1:]
                )
                log_ratio = (logprobs - ref_logprobs) * decoder_attention_mask[:, 1:]
                kl = jnp.exp(log_ratio) - 1 - log_ratio
                mean_kl = kl.sum(1).mean()
                mean_kl_per_token = kl.mean()

                r = sample_outputs.shape[1] - 1
                j = jnp.arange(r)[None, :]
                n_resp = jnp.maximum(
                    (sample_outputs[:, 1:] != pad_id).sum(axis=1), 1
                )[:, None]
                valid = (j < n_resp).astype(jnp.float32)
                rewards = (-kl_coef) * log_ratio * valid
                if scalar_scores:
                    rewards = rewards + (j == n_resp - 1) * scores_eff[:, :1]
                else:
                    rewards = rewards + scores_eff[:, :r] * valid
                chunk = PPORLBatch(
                    query_tensors=prompt_tensors,
                    response_tensors=sample_outputs,
                    logprobs=logprobs * valid,
                    values=values[:, :-1] * valid,
                    rewards=rewards,
                )
                return chunk, mean_kl, mean_kl_per_token

            return self._ljit(
                score_reward_s2s,
                f"score_reward_s2s[{'scalar' if scalar_scores else 'dense'}]",
                budget=2,
            )

        def score_reward(train_params, frozen_params, ref_params,
                         prompt_tensors, sample_outputs, scores_eff, kl_coef):
            params = merge_params(train_params, frozen_params)
            all_tokens = jnp.concatenate([prompt_tensors, sample_outputs], axis=1)
            attention_mask = (all_tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            logits, values, ref_logits, _ = forward_policy_and_ref(
                model, params, ref_params, all_tokens, attention_mask, split, positions
            )
            logprobs = logprobs_of_labels(logits[:, :-1, :], all_tokens[:, 1:])
            ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], all_tokens[:, 1:])
            log_ratio = (logprobs - ref_logprobs) * attention_mask[:, :-1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            mean_kl = kl.sum(1).mean()
            mean_kl_per_token = kl.mean()

            q = prompt_tensors.shape[1]
            r = sample_outputs.shape[1]
            start = q - 1
            j = jnp.arange(r)[None, :]
            # degenerate empty responses keep one slot (classic n_resp clamp)
            n_resp = jnp.maximum((sample_outputs != pad_id).sum(axis=1), 1)[:, None]
            valid = (j < n_resp).astype(jnp.float32)
            rewards = (-kl_coef) * log_ratio[:, start:start + r] * valid
            if scalar_scores:
                # scalar score lands on the final real token
                rewards = rewards + (j == n_resp - 1) * scores_eff[:, :1]
            else:
                # dense per-token scores, truncated to the response window
                # (scores_eff is host-prepadded to width r with zeros)
                rewards = rewards + scores_eff * valid
            chunk = PPORLBatch(
                query_tensors=prompt_tensors,
                response_tensors=sample_outputs,
                logprobs=logprobs[:, start:start + r] * valid,
                values=values[:, start:start + r] * valid,
                rewards=rewards,
            )
            return chunk, mean_kl, mean_kl_per_token

        return self._ljit(
            score_reward,
            f"score_reward[{'scalar' if scalar_scores else 'dense'}]",
            budget=2,
        )

    def train_epochs_from_chunk(self, chunk: PPORLBatch, n_epochs: int):
        """All inner epochs' optimizer steps from a DEVICE-resident chunk:
        per-epoch shuffles are host permutation indices, the stacked
        [n_steps, batch, ...] batches are gathered on device, and the whole
        thing runs as the existing one-scan train dispatch. No host copy of
        the chunk ever exists (the classic path collates through the numpy
        store)."""
        n = int(chunk.query_tensors.shape[0])
        bs = self.config.train.batch_size
        if n % bs != 0:
            raise ValueError(f"chunk of {n} rollouts not divisible by batch_size {bs}")
        steps = n // bs
        if self._train_step_fn is None:
            self._build_steps()
        rng = np.random.default_rng(self.config.train.seed + self.iter_count)
        idx = np.concatenate(
            [rng.permutation(n) for _ in range(n_epochs)]
        ).reshape(n_epochs * steps, bs)
        # (the trunk cache is not per-row: the steps gather their rows of it)
        per_row, rejoin = self._split_shared(chunk)
        stacked = rejoin(jax.tree_util.tree_map(lambda a: a[jnp.asarray(idx)], per_row))
        self.train_params, self.opt_state, stats = self._train_scan_fn(
            self.train_params, self.frozen_params, self.opt_state, stacked,
            *self._sentinel_args(),
        )
        self._normalize_state_shardings()
        # advance like learn() does per optimizer step — the next cycle's
        # shuffle seed (and checkpoint naming) must not repeat this one's
        self.iter_count += n_epochs * steps
        return stats

    def _spec_path_available(self) -> bool:
        """The speculative rollout scorer needs an in-graph equivalent of
        the host decode->encode round trip: an id-local tokenizer and no
        stop sequences (those trim by string content). Dense (per-token)
        rewards disable it after the first observed chunk — the merge fast
        path is scalar-only, so dispatching the speculative forward would
        just double the scoring FLOPs forever."""
        return (
            not self.seq2seq
            and not self.stop_sequences
            and not getattr(self, "_spec_disabled_dense", False)
            and getattr(self.tokenizer, "_n_plain_ids", None) is not None
        )

    def _fast_rollout_available(self) -> bool:
        """The rollout fast path (method.capture_rollout_stats) needs
        everything the speculative scorer needs — the host retokenize
        stays the arbiter — PLUS a real hydra split (split > 0: the
        frozen-reference suffix is what's left to compute after capture),
        per-step values from the plain v_head (no deep value branch), and
        single-beam sampling (the while-loop sampler is where capture
        lives). Overridden to False by the pipelined/sequence-parallel
        trainers, whose param layouts can't run the unstacked suffix
        resume."""
        if not getattr(self.config.method, "capture_rollout_stats", False):
            return False
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        return (
            self._spec_path_available()
            and self.split > 0
            and getattr(self.config.method, "num_value_layers_unfrozen", 0) == 0
            and int(gen_kwargs.get("num_beams", 1) or 1) == 1
        )

    # ------------------------------------------------------------------
    # Int8 frozen-trunk decode view
    # ------------------------------------------------------------------

    def _decode_params(self):
        """Sampler param view: the int8 frozen-trunk tree when
        method.quantize_frozen_trunk is on (quantized ONCE — those leaves
        never train — and re-merged with the live trainable leaves every
        dispatch), else the dense merged tree."""
        if getattr(self.config.method, "quantize_frozen_trunk", False):
            from trlx_tpu.models.policy import refuse_over_looped_stack

            refuse_over_looped_stack(self.model_cfg, "method.quantize_frozen_trunk (an int8 frozen trunk)")
        if not (
            getattr(self.config.method, "quantize_frozen_trunk", False)
            and self.split > 0
            and not self.seq2seq
        ):
            return self.params
        quant = getattr(self, "_quant_frozen_cache", None)
        if quant is None:
            from trlx_tpu.ops.quant import quantize_frozen_flat

            quant = quantize_frozen_flat(self.frozen_params, self.split)
            self._quant_frozen_cache = quant
        return merge_params(self.train_params, quant)

    # ------------------------------------------------------------------
    # Frozen-trunk activation cache: the trunk runs once a cycle
    # ------------------------------------------------------------------

    def _trunk_cache_available(self) -> bool:
        """Whether the cycle trains from cached trunk activations: the
        state entering block `split` is computed once for each rollout
        chunk, stays on the device for the cycle, and every optimizer step
        resumes from it (`forward(start=split)`). The schedule decides from
        what it can observe; there is no flag. Of the model and layout: a
        real hydra split (split > 0 means blocks [0, split) are entirely
        frozen, so the cache never goes stale within a collection), a
        causal LM (seq2seq's encoder/decoder split has no single trunk
        activation), no auxiliary router loss (`MoEMLP` recomputes it from
        the full forward), a value branch tapping at/above the split (its
        input must be derivable from the cached state), and this class's
        own loss (a trainer that builds its own, GRPO's, has no resumed
        forward; the pipelined/sequence-parallel trainers, whose param
        layouts cannot run the unstacked suffix resume, say so themselves).
        Of the recipe: more than one optimizer pass over a chunk's rows
        (with one epoch the fill costs what it saves). Of the chip: the
        cycle's cache within its share of one device's memory. Where this
        says no, every step runs the whole forward."""
        method = self.config.method
        n_value = getattr(method, "num_value_layers_unfrozen", 0)
        if not (
            not self.seq2seq
            and self.split > 0
            and not getattr(self.model_cfg, "sows_moe_aux", False)
            and self.model_cfg.n_layers - n_value >= self.split
            and type(self).make_loss_fn is PPOTrainer.make_loss_fn
            and method.ppo_epochs > 1
        ):
            return False
        if self._trunk_cache_budget is None:
            from trlx_tpu.observability.hbm import device_hbm_bytes

            self._trunk_cache_budget = int(
                TRUNK_CACHE_HBM_SHARE * device_hbm_bytes(self.runtime.mesh.devices.flat[0]))
        # 0: a backend that does not say what it holds (the CPU) bounds nothing
        return not self._trunk_cache_budget or (
            self._trunk_cache_device_bytes() <= self._trunk_cache_budget)

    #: What `_score_hands_out_trunk_state` leaves free beyond the parts it
    #: adds up, for what it does not size: the code of the cycle's other
    #: programs (the train steps, the cache's concat), the prompts and results
    #: of two chunks in flight, the allocator's rounding and the holes
    #: between standing arrays.
    #: In `pythia-1.4b.ppo-hh` those came to about 100 MB (`bytes_in_use` at a
    #: collection's first dispatch 415 MB over what it was when the scorer was
    #: built, 314 MB of it the two sized programs' code; my chip runs, PR 49).
    COLLECTION_HBM_MARGIN = 256 * 2**20

    def _score_hands_out_trunk_state(self, score_program=None) -> bool:
        """Whether the score program returns the state entering block
        `split` as one more output, which `_process_chunk` puts into the
        cycle's trunk cache in the tokens' place: the scorer runs blocks
        [0, split) over exactly the tokens a fill would, so the collection's
        end then has nothing to fill. Read once, when the scorer is built and
        before it is compiled (one compiled program a trainer);
        `score_program` is that scorer, jitted. Of the trainer: this class's
        own `score` (GRPO, the pipelined and the sequence-parallel trainers
        build theirs). Of the schedule: the cycle trains from the trunk cache
        (`_trunk_cache_available`). Of the recipe and the chip, one of two.
        A collection is one chunk (`num_rollouts <= chunk_size`): generation
        is double-buffered (`_collect_rollouts` dispatches the next chunk's
        before it fetches this one's), so with one chunk nothing is
        dispatched after a chunk has been scored, and no memory is read. Or
        the collection's states fit beside a generation in flight and the
        scorer, by the device's own account. A dispatch takes its buffers at
        once and does not wait for memory, so what the device has free now
        (`_device_free_bytes`: the weights, the optimizer and the reference
        stand, and nothing else yet) has to hold four things. The programs'
        temporaries: the device keeps ONE region for them, as large as the
        largest program it has run needs and never given back (cell 1:
        `bytes_reserved` 3.39 GB after the first `score`, 6.42 GB from the
        first `generate` on, the same with a score dispatched beside a
        generation in flight; my chip runs, PR 49), so the larger of the two
        programs' counts, not their sum. What each program holds beside that
        region: its code, which lives on the device from its first call on
        (the six-output `score` of cell 1: 304 MB), and its results. Every
        chunk's state (`_trunk_cache_device_bytes`). And
        `COLLECTION_HBM_MARGIN`. Both programs are sized by the compiler's
        analysis of the executables the collection then runs
        (`_generate_held_bytes`, `_score_held_bytes`): `generate` first,
        which any answer runs, and `score` only where the states fit beside
        `generate` alone, so that a plain no never compiles the six-output
        program. A backend that reports no capacity (the CPU) bounds
        nothing, as in `_trunk_cache_available`, and nothing is lowered for
        it; a program that cannot be sized declines. Where this says no, the
        score program has its five outputs and `_close_trunk_cache` fills
        every chunk when the collection has ended."""
        if not (type(self)._build_score_fn is PPOTrainer._build_score_fn
                and self._trunk_cache_available()):
            return False
        if self._planned_chunks() == 1 or not self._trunk_cache_budget:
            return True
        t0 = time.monotonic()
        free = self._device_free_bytes()
        asked = self._trunk_cache_device_bytes() + self.COLLECTION_HBM_MARGIN
        generate, score = self._generate_held_bytes(), None
        if generate is not None and sum(generate) + asked <= free:
            score = self._score_held_bytes(score_program)
        fits = score is not None and (
            max(generate[0], score[0]) + generate[1] + score[1] + asked <= free)
        logger.info(
            f"a collection's trunk states beside a generation in flight: {free} B free; (temporaries, "
            f"code and results) of generate {generate}, of score {score or 'not sized'}; "
            f"states and margin {asked} B: the score program "
            f"{'hands them out' if fits else 'does not hand them out'} "
            f"(reckoned in {time.monotonic() - t0:.1f} s)")
        return fits

    def _takes_trunk_state(self, chunk: int) -> bool:
        """Whether chunk number `chunk` of the collection under way keeps the
        state the score program hands out: a chunk the rule counted
        (`_score_hands_out_trunk_state`), one of the collection's planned
        number with the states of the chunks before it the only ones
        standing. A chunk that a quarantine makes necessary beyond those, and
        the chunks of a several-chunk collection that adds to a live cache
        (`_open_trunk_cache`), were not in the reckoning: they are filled at
        the collection's end, when nothing is in flight."""
        planned = self._planned_chunks()
        return (self._score_with_trunk_state and self._trunk_chunks is not None
                and chunk < planned and (planned == 1 or len(self._trunk_chunks) == chunk))

    def _planned_chunks(self) -> int:
        """Chunks a collection of the recipe takes when no row is quarantined."""
        method = self.config.method
        return -(-int(method.num_rollouts) // max(int(method.chunk_size), 1))

    def _device_free_bytes(self) -> int:
        """What the fullest of this process's devices of the mesh has free
        for programs and new arrays, by its own account: its limit less the
        arrays in use and the region reserved for programs' temporaries.
        `bytes_in_use` counts arrays and loaded code and not that region
        (PERF.md section 6, PR 38), `bytes_reserved` counts it."""
        free = []
        for device in self.runtime.mesh.local_devices:
            stats = device.memory_stats() or {}
            free.append(int(stats.get("bytes_limit", 0)) - int(stats.get("bytes_in_use", 0))
                        - int(stats.get("bytes_reserved", 0)))
        return min(free)

    @staticmethod
    def _program_held_bytes(program, *args, **kwargs) -> Optional[Tuple[int, int]]:
        """What a jitted program takes on a device, beside its arguments, by
        the compiler's analysis of the executable compiled for those
        arguments (shapes will do): (its temporaries, which stand in the
        device's one region for them while it runs; its code and its
        results, which stand beside). The executable is the one a call with
        such arguments then runs. None where the program or the backend
        cannot say."""
        jitted = getattr(program, "_jitted", program)  # behind the compile ledger's wrapper
        if not hasattr(jitted, "lower"):
            return None
        stats = jitted.lower(*args, **kwargs).compile().memory_analysis()
        if stats is None:
            return None
        return (int(stats.temp_size_in_bytes),
                int(stats.generated_code_size_in_bytes) + int(stats.output_size_in_bytes))

    def _chunk_prompt_width(self) -> int:
        """Prompt columns of a collection's chunk: the width the rollout
        loader pads to where its pipeline says, else what the recipe leaves
        room for."""
        return (self._rollout_prompt_width
                or self.config.train.seq_length - self._trunk_response_width())

    def _generate_held_bytes(self) -> Optional[Tuple[int, int]]:
        """`_program_held_bytes` of the `generate` program a collection's
        chunks run: `chunk_size` prompts of `_chunk_prompt_width`, through
        the buckets `generate` rounds to, under the collection's
        `gen_kwargs`. Nothing where the generation is not on this device
        (fleet rollouts)."""
        if self._fleet_rollouts_enabled():
            return 0, 0
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        rows, width = self._bucket_shape(int(self.config.method.chunk_size), self._chunk_prompt_width())
        prompts = jax.ShapeDtypeStruct((rows, width), jnp.int32)
        return self._program_held_bytes(
            self.get_generate_fn(rows, width, gen_kwargs),
            self._decode_params(), prompts, prompts,
            jax.ShapeDtypeStruct(self.rng.shape, self.rng.dtype))

    def _score_held_bytes(self, score_program) -> Optional[Tuple[int, int]]:
        """`_program_held_bytes` of the six-output score program over one
        chunk of the collection, its state (one of those
        `_trunk_cache_device_bytes` counts) included."""
        tokens = jax.ShapeDtypeStruct(
            (int(self.config.method.chunk_size),
             self._chunk_prompt_width() + self._trunk_response_width()), jnp.int32)
        return self._program_held_bytes(
            score_program, self.train_params, self.frozen_params, self.ref_params, tokens,
            with_trunk_state=True)

    def _trunk_cache_device_bytes(self) -> int:
        """What one device holds of a cycle's cache at its widest: whole
        chunks of `num_rollouts` rows of `seq_length` states in the
        forward's dtype, over the devices `_trunk_cache_sharding` spreads
        rows and columns on."""
        from trlx_tpu.observability.hbm import trunk_cache_bytes

        shape = (self._planned_chunks() * max(int(self.config.method.chunk_size), 1),
                 self.config.train.seq_length, self.model_cfg.d_model)
        sharding = self._trunk_cache_sharding(shape)
        if sharding is not None:
            shape = sharding.shard_shape(shape)
        return trunk_cache_bytes(*shape, self.model_cfg.dtype)

    def _trunk_cache_sharding(self, shape=None):
        """NamedSharding for a [b, T, d] activation cache: batch over the
        DP axes, sequence over the sequence axis, features replicated — an
        EXPLICIT constraint so param donation in the train step never
        relayouts the cache between epochs. None when the mesh doesn't
        carry the standard axes (the pipe mesh; those trainers gate the
        cache off anyway), or when `shape` does not divide over them (a
        chunk of fewer rows than data-parallel ways: the compiler places it)."""
        mesh = self.runtime.mesh
        if "data" not in mesh.axis_names:
            return None
        batch_axes = ("data", "fsdp") if "fsdp" in mesh.axis_names else ("data",)
        seq_axis = "sequence" if "sequence" in mesh.axis_names else None
        if shape is not None and (
                shape[0] % int(np.prod([mesh.shape[a] for a in batch_axes]))
                or (seq_axis and shape[1] % mesh.shape[seq_axis])):
            return None
        return self.runtime.sharding(batch_axes, seq_axis, None)

    def _place_trunk_cache(self, h):
        """Inside a jit: `h` [b, T, d] held to `_trunk_cache_sharding`."""
        sharding = self._trunk_cache_sharding(h.shape)
        return h if sharding is None else jax.lax.with_sharding_constraint(h, sharding)

    def _trunk_response_width(self) -> int:
        """Columns of a cached row past its query: what the sampler may add."""
        gen_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        return int(gen_kwargs.get("max_new_tokens", 40))

    def _left_queries(self) -> bool:
        return self.store.padding_side == "left"

    def _build_trunk_cache_fn(self):
        """Jitted frozen-prefix pass: concat(query, response) tokens -> the
        state entering block `split` in the dtype the forward hands that
        block, placed per _trunk_cache_sharding. One call per rollout chunk,
        amortized over ppo_epochs inner epochs of suffix-only training."""
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        # the function's name is the program's in a device trace
        # (`jit_trunk_cache_fill`: bench/metrics/ppo.trunk_fill_s.json)
        def trunk_cache_fill(train_params, frozen_params, tokens):
            params = merge_params(train_params, frozen_params)
            attention_mask = (tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            _, h, _ = model.apply(
                {"params": params}, tokens, attention_mask, positions, stop=split,
                method=type(model).forward,
            )
            return self._place_trunk_cache(h)

        return self._ljit(trunk_cache_fill, "trunk_cache_fill", budget=2)

    def _open_trunk_cache(self):
        """A collection starts. Over an empty store the last cycle's cache
        goes and the rows count from 0; over a store whose rollouts all
        hold a row of the live cache the collection adds to it; over any
        other store (restored from a checkpoint, or not cached) it does not
        cache, because a batch trains from the cache only if all its rows
        are there."""
        self._trunk_chunks = None
        if (self._trunk_cache_available() and self._trunk_cache is not None and len(self.store)
                and all(e.trunk_row is not None for e in self.store.history)):
            self._trunk_chunks = [self._trunk_cache]
            return
        self._trunk_scored_rows = 0
        if self._trunk_cache is not None:
            # gone before the collection's first program is dispatched: a
            # dispatch takes its buffers at once and does not wait for
            # memory, and a recipe sized to the chip leaves the sampler's
            # programs no room beside the cache (so the steps that read it
            # have to have ended, and no stray reference keeps it)
            jax.block_until_ready(self.train_params)
            self._trunk_cache.delete()
            self._trunk_cache = None
        if self._trunk_cache_available() and len(self.store) == 0:
            self._trunk_chunks = []

    def _note_trunk_chunk(self, prompt_tensors, sample_outputs, state=None) -> int:
        """One scored chunk's place in the cycle's cache; returns the row
        its first sample will hold there. `state` is the chunk's trunk
        state where the score program handed it out (a device array in the
        scorer's layout, which stays where it is); without it the chunk's
        tokens are kept on the host until the collection ends
        (`_close_trunk_cache` fills them). The tokens are laid out as
        the loader will lay out the batches that train on them (queries
        padded to the width `create_train_dataloader` buckets them to), so a
        cached row is the state the whole forward of such a batch computes,
        column for column, and the step has nothing to move."""
        row0 = sum(len(c) for c in self._trunk_chunks)
        if state is not None:
            self._trunk_chunks.append(state)
            self._trunk_scored_rows += len(state)
            return row0
        q = prompt_tensors.shape[1]
        tokens = _to_batch_columns(
            np.concatenate([prompt_tensors, sample_outputs], axis=1), q,
            self._train_query_width(q), None, self._left_queries(),
            fill=self.tokenizer.pad_token_id)
        self._trunk_chunks.append(tokens)
        return row0

    def _close_trunk_cache(self):
        """The collection has ended: one frozen-prefix pass for each of its
        chunks whose state the score program did not hand out, over the
        SAME retokenized tokens the scorer saw, amortized
        over ppo_epochs inner epochs of suffix-only training, and the
        results as ONE device array [rollouts, query + response, d], which
        every train step of the cycle takes beside its batch and which never
        leaves the device. The fills wait for the end because only then do
        the sampler and the scorer hold nothing: while a generation is in
        flight its buffers stand, and a fill's own come on top of them; the
        chunks that wait here are those the rule found no room for, or did
        not count (`_score_hands_out_trunk_state`). The device is as
        busy either way; the train steps queue behind the fills. Chunks of
        different query widths (a prompt pipeline that pads batch by batch),
        and a scored state whose queries the loader will pad wider, move to
        the widest first. While a profiler session listens, the counter
        span that says how many of the cache's rows the score program made."""
        chunks, self._trunk_chunks = self._trunk_chunks, None
        if not chunks:
            return

        def fill(tokens):
            if self._trunk_cache_fn is None:
                self._trunk_cache_fn = self._build_trunk_cache_fn()
            return self._trunk_cache_fn(self.train_params, self.frozen_params, jnp.asarray(tokens))

        chunks = [c if isinstance(c, jax.Array) else fill(c) for c in chunks]
        r = self._trunk_response_width()
        train_q = lambda h: self._train_query_width(h.shape[1] - r)  # noqa: E731
        if len(chunks) > 1 or train_q(chunks[0]) + r != chunks[0].shape[1]:
            if self._trunk_concat_fn is None:
                left = self._left_queries()

                def trunk_cache_concat(hs):
                    q = max(train_q(h) for h in hs)
                    return self._place_trunk_cache(jnp.concatenate(
                        [_to_batch_columns(h, h.shape[1] - r, q, q + r, left) for h in hs]))

                self._trunk_concat_fn = self._ljit(
                    trunk_cache_concat, "trunk_cache_concat", budget=2)
            chunks = [self._trunk_concat_fn(chunks)]
        self._trunk_cache = chunks[0]
        if tracing.active():
            tracing.counters("ppo.trunk_rows", rows=len(self._trunk_cache),
                             scored=self._trunk_scored_rows)

    def _bind_shared(self, batch):
        """The cycle's trunk cache beside the rows a placed batch names (one
        more argument of the train and accumulation steps, not donated);
        rows without a live cache (a restored store) are dropped and the
        step runs the whole forward. While a profiler session listens, the
        counter span that says which it was."""
        rows = batch.trunk_rows
        if rows is not None:
            batch = (batch.replace(trunk_rows=None) if self._trunk_cache is None
                     else batch.replace(trunk_cache=self._trunk_cache))
        if tracing.active():
            tracing.counters(
                "ppo.trunk_cache", blocks=self.model_cfg.n_layers,
                cached_blocks=self.split if batch.trunk_cache is not None else 0,
                rows=int(np.prod(batch.query_tensors.shape[:-1])))
        return batch

    def _split_shared(self, stacked_batches):
        cache = stacked_batches.trunk_cache
        return (stacked_batches.replace(trunk_cache=None),
                lambda batch: batch.replace(trunk_cache=cache))

    def _attach_trunk_cache(self, chunk: PPORLBatch, captured=None) -> PPORLBatch:
        """A device-resident chunk of the fused cycle with its trunk cache,
        carried as the store's batches carry theirs: `trunk_rows` naming
        rows of one `trunk_cache` array. `captured` is the sampler's
        in-loop capture of the same state (rollout fast path), reused when
        its width matches the chunk's concat(query, response) layout (a
        fast-path spec hit guarantees raw == retokenized, so it does);
        otherwise one jitted trunk pass computes it. No-op where the
        schedule trains from the whole forward."""
        if not self._trunk_cache_available():
            return chunk
        n = chunk.query_tensors.shape[0]
        width = chunk.query_tensors.shape[1] + chunk.response_tensors.shape[1]
        if captured is not None and captured.shape[1] == width:
            h = captured
        else:
            if self._trunk_cache_fn is None:
                self._trunk_cache_fn = self._build_trunk_cache_fn()
            tokens = jnp.concatenate(
                [jnp.asarray(chunk.query_tensors), jnp.asarray(chunk.response_tensors)],
                axis=1,
            )
            h = self._trunk_cache_fn(self.train_params, self.frozen_params, tokens)
        return chunk.replace(trunk_rows=jnp.arange(n, dtype=jnp.int32), trunk_cache=h)

    def _build_spec_trim_fn(self, q: int, max_new: int):
        """Tiny jit: device-retokenize the raw responses. Kept SEPARATE
        from the speculative forward so the cycle's blocking fetch (which
        carries the trim for host arbitration) only waits for this, while
        the expensive forward keeps the device busy through the fetch RTT
        and host reward scoring."""
        tok = self.tokenizer

        def trim(samples):
            return tok.device_retokenize(samples[:, q:], max_new)

        return self._ljit(trim, f"spec_trim[q{q},r{max_new}]")

    def _build_spec_fwd_fn(self, q: int, max_new: int):
        """Speculative half of _build_score_reward_fn: the policy/value/
        reference forward on the device-trimmed samples — dispatched right
        after generation, so it executes WHILE the host fetches samples
        and scores them. The host-side retokenization
        remains the arbiter: pipelined_cycle compares it
        element-for-element with the device trim and falls back to the
        classic fused score+reward when they differ, so the math cannot
        drift."""
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        def spec_fwd(train_params, frozen_params, ref_params, samples, trimmed):
            params = merge_params(train_params, frozen_params)
            prompt_tensors = samples[:, :q]
            all_tokens = jnp.concatenate([prompt_tensors, trimmed], axis=1)
            attention_mask = (all_tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            logits, values, ref_logits, _ = forward_policy_and_ref(
                model, params, ref_params, all_tokens, attention_mask, split, positions
            )
            logprobs = logprobs_of_labels(logits[:, :-1, :], all_tokens[:, 1:])
            ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], all_tokens[:, 1:])
            log_ratio = (logprobs - ref_logprobs) * attention_mask[:, :-1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            start = q - 1
            return (
                logprobs[:, start:start + max_new],
                values[:, start:start + max_new],
                log_ratio[:, start:start + max_new],
                kl.sum(1).mean(),
            )

        return self._ljit(spec_fwd, f"spec_fwd[q{q},r{max_new}]")

    def _build_spec_merge_fn(self, scalar_scores: bool):
        """Cheap tail of the scorer: per-token reward construction from the
        speculative forward's windows + the host scores. Formulas identical
        to _build_score_reward_fn's merge block."""
        pad_id = self.tokenizer.pad_token_id

        def merge(prompt_tensors, trimmed, lp_win, v_win, logratio_win,
                  scores_eff, kl_coef):
            r = trimmed.shape[1]
            j = jnp.arange(r)[None, :]
            n_resp = jnp.maximum((trimmed != pad_id).sum(axis=1), 1)[:, None]
            valid = (j < n_resp).astype(jnp.float32)
            rewards = (-kl_coef) * logratio_win * valid
            if scalar_scores:
                rewards = rewards + (j == n_resp - 1) * scores_eff[:, :1]
            else:
                rewards = rewards + scores_eff * valid
            return PPORLBatch(
                query_tensors=prompt_tensors,
                response_tensors=trimmed,
                logprobs=lp_win * valid,
                values=v_win * valid,
                rewards=rewards,
            )

        return self._ljit(
            merge, f"spec_merge[{'scalar' if scalar_scores else 'dense'}]")

    def _dispatch_spec_score(self, out):
        """Dispatch the speculative trim (tiny) then the scorer forward
        (big) on the raw device samples — no host sync; returns
        (trimmed, lp_win, v_win, logratio_win, mean_kl) device handles.
        The fetch only ever waits on `trimmed`."""
        max_new = int(
            (self.generate_experience_kwargs or self.generate_kwargs)
            .get("max_new_tokens", 40)
        )
        samples = out["samples"]
        q = samples.shape[1] - out["response_tokens"].shape[1]
        fns = getattr(self, "_spec_score_fns", None)
        if fns is None:
            fns = self._spec_score_fns = {}
        if (q, max_new) not in fns:
            fns[(q, max_new)] = (
                self._build_spec_trim_fn(q, max_new),
                self._build_spec_fwd_fn(q, max_new),
            )
        trim_fn, fwd_fn = fns[(q, max_new)]
        trimmed = trim_fn(samples)
        lp, v, lr, mean_kl = fwd_fn(
            self.train_params, self.frozen_params, self.ref_params, samples, trimmed
        )
        return (trimmed, lp, v, lr, mean_kl)

    def _build_fast_fwd_fn(self, q: int, max_new: int):
        """Score phase of the rollout fast path: the sampler already
        captured the policy logprobs, values, and the activations entering
        the hydra split, so all that's left is the frozen-REFERENCE suffix
        (blocks [split:] + a response-window unembedding) — no policy or
        value re-forward at all, ~the suffix fraction of the classic 73 ms
        score at bench shapes.

        Window semantics match _build_spec_fwd_fn. One documented
        divergence: mean_kl sums over the response window's real (label)
        tokens only, while the classic scorer's full-width sum also counts
        prompt positions (zero there) and the pad label right after an
        early eos. The difference only feeds the KL controller and
        logging, and is gated behind method.capture_rollout_stats; the
        importance ratios used by the loss are identical."""
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        def fast_fwd(ref_params, samples, h_split, lp_cap, v_cap):
            attention_mask = (samples != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            start = q - 1
            ref_logits_w, _, _ = model.apply(
                {"params": {"lm": ref_params}}, h_split, attention_mask, positions,
                start=split, window=(start, max_new), with_value=False,
                method=type(model).forward,
            )
            labels = jax.lax.dynamic_slice_in_dim(samples, q, max_new, axis=1)
            ref_lp = logprobs_of_labels(ref_logits_w, labels)
            valid_lab = (labels != pad_id).astype(jnp.float32)
            log_ratio_w = (lp_cap - ref_lp) * valid_lab
            kl = jnp.exp(log_ratio_w) - 1 - log_ratio_w
            # kl is exactly 0 wherever valid_lab is 0, so this window sum
            # counts real response tokens only
            return lp_cap, v_cap, log_ratio_w, kl.sum(1).mean()

        return self._ljit(fast_fwd, f"fast_fwd[q{q},r{max_new}]")

    def _dispatch_fast_score(self, out):
        """Fast-path analogue of _dispatch_spec_score — same (trimmed,
        lp_win, v_win, logratio_win, mean_kl) contract so the cycle's
        merge/arbitration machinery is shared. The trim still ships for
        host arbitration; the forward is just the reference suffix over
        the CAPTURED activations."""
        max_new = int(
            (self.generate_experience_kwargs or self.generate_kwargs)
            .get("max_new_tokens", 40)
        )
        samples = out["samples"]
        q = samples.shape[1] - out["response_tokens"].shape[1]
        fns = getattr(self, "_fast_score_fns", None)
        if fns is None:
            fns = self._fast_score_fns = {}
        if (q, max_new) not in fns:
            fns[(q, max_new)] = (
                self._build_spec_trim_fn(q, max_new),
                self._build_fast_fwd_fn(q, max_new),
            )
        trim_fn, fwd_fn = fns[(q, max_new)]
        trimmed = trim_fn(samples)
        lp, v, lr, mean_kl = fwd_fn(
            self.ref_params, samples, out["h_split"], out["logprobs"], out["values"]
        )
        if self._trunk_cache_available():
            # hand the captured activations onward instead of discarding
            # them after fast scoring: the cycle attaches them to the
            # chunk once the spec hit confirms raw == retokenized, so the
            # fast-rollout schedule pays zero extra forwards for the
            # trunk cache. Side channel on `out` — the 5-tuple return
            # contract is pinned by test_fast_dispatch_contract_matches_spec.
            out["trunk_cache"] = out["h_split"]
        return (trimmed, lp, v, lr, mean_kl)

    def pipelined_cycle(self, pending=None):
        """One full PPO iteration — rollouts, scoring, all inner epochs,
        and the NEXT chunk's generation — with exactly ONE blocking host
        fetch. The fetch bundles this chunk's samples with the PREVIOUS
        cycle's loss and mean-KL; the KL controller then updates with the
        classic cadence (once per inner epoch, between a cycle's training
        and the next cycle's scoring — reference post_backward_callback,
        replayed n_inner_epochs times by the fused path).

        When the tokenizer supports the in-graph retokenize
        (_spec_path_available), the expensive policy/value/reference
        forward is dispatched SPECULATIVELY right after generation on the
        device-trimmed samples, so it overlaps the fetch RTT and host
        reward scoring; the host retokenization arbitrates (exact
        element-for-element match, else classic fallback — counted in
        self.spec_fallbacks).

        Under the rollout fast path (method.capture_rollout_stats +
        _fast_rollout_available) the schedule restructures further into a
        one-rollout-ahead double buffer: generation captures the policy
        logprobs/values in-loop, scoring is just the frozen-ref suffix,
        and the NEXT cycle's generation is dispatched BEFORE this cycle's
        train — so on the device stream gen(N+1) runs ahead of train(N),
        and next cycle's blocking samples fetch + host reward scoring
        overlap train(N) instead of serializing after it. Generation then
        runs on one-step-stale params; the captured logprobs are the
        behavior policy's (exactly what the PPO ratio needs), and the
        host-side KL-controller update shifts one cycle later to keep the
        single-fetch discipline.

        num_rollouts = k * chunk_size collects k device-resident chunks per
        cycle (all generated on the same params, like make_experience) and
        trains on their concatenation.

        Returns (prev_cycle_loss | None, pending)
        — pass `pending` back in to continue, and fetch the final cycle's
        loss from pending[2][0] when done.

        Skips the rollout store / logging (use make_experience + learn for
        those). seq2seq runs the cycle too (decoder-relative score+reward
        fn) — just without the speculative scorer (the host retokenize is
        not id-local there)."""
        method = self.config.method
        if method.num_rollouts % method.chunk_size != 0:
            raise NotImplementedError(
                f"pipelined_cycle requires num_rollouts to be a multiple of "
                f"chunk_size (got {method.num_rollouts} vs {method.chunk_size}); "
                "use make_experience + learn for ragged collections"
            )
        if self._fleet_rollouts_enabled():
            logger.warning_once(
                "rollout_backend='fleet' applies to make_experience only; "
                "pipelined_cycle keeps generating locally (its single-fetch "
                "schedule is device-resident end to end)"
            )
        # k > 1 (r4, VERDICT item 7): the cycle collects k device-resident
        # chunks — all generated on the SAME params, like make_experience —
        # before the epoch loop trains on their concatenation
        k = method.num_rollouts // method.chunk_size
        max_new = int(
            (self.generate_experience_kwargs or self.generate_kwargs)
            .get("max_new_tokens", 40)
        )
        def dispatch_chunks():
            # all generations enqueue first, then the speculative scorers —
            # the fetch waits on gens + (tiny) trims, so the score forwards
            # overlap the fetch RTT and host reward scoring.
            # Availability is re-checked at every dispatch: once a dense
            # reward_fn flips _spec_disabled_dense mid-cycle, no further
            # speculative forwards are wasted.
            fast_ok = self._fast_rollout_available()
            spec_ok = fast_ok or self._spec_path_available()
            gens = [self.dispatch_rollout_generation() for _ in range(k)]
            if fast_ok:
                specs = [self._dispatch_fast_score(o) for _, o in gens]
            elif spec_ok:
                specs = [self._dispatch_spec_score(o) for _, o in gens]
            else:
                specs = [None] * k
            # which scorer these handles came from, read back next cycle
            self._pending_fast = fast_ok
            return gens, specs

        if pending is None:
            gens, specs = dispatch_chunks()
            pending = (gens, specs, None)
        gens, specs, prev = pending
        # what was actually dispatched last cycle, not current availability
        use_spec = specs[0] is not None
        use_fast = use_spec and bool(getattr(self, "_pending_fast", False))

        # The cycle's blocking fetch: every chunk's raw samples (+ the
        # speculative trims for arbitration) + the previous cycle's
        # loss/KL handles, bundled into one device_get. Fast schedule:
        # the previous TRAIN was dispatched after these generations, so
        # waiting on its handles here would forfeit the overlap — fetch
        # samples/trims only, do all host reward work, and collect the
        # train handles in a second (by then already-resolved) fetch.
        fetch = [o["samples"] for _, o in gens]
        if use_spec:
            fetch.extend(s[0] for s in specs)
        if prev is not None and not use_fast:
            fetch.extend(prev)
        # the cycle's blocking device->host sync: under the fast schedule
        # this is where generation overlap is (or isn't) hiding the
        # previous train step
        with self._span("ppo.pipelined_fetch", phase="pipelined_fetch",
                        step=self.iter_count):
            fetched = jax.device_get(tuple(fetch))
        samples_list = fetched[:k]
        trimmed_list = fetched[k:2 * k] if use_spec else [None] * k

        processed = None
        if use_fast:
            # host decode + reward scoring for every chunk, overlapping
            # the previous cycle's still-running train
            processed = []
            for (batch, _), samples in zip(gens, samples_list):
                stats: Dict[str, float] = {}
                processed.append(self._host_process_chunk(batch, samples, stats))
            if prev is not None:
                prev_vals = jax.device_get(tuple(prev))
                prev_loss = float(prev_vals[0])
                self.mean_kl = float(prev_vals[1])
                for _ in range(method.ppo_epochs):
                    self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
            else:
                prev_loss = None
        elif prev is not None:
            prev_loss = float(fetched[-2])
            self.mean_kl = float(fetched[-1])
            # classic cadence: post_backward_callback fires once per inner
            # epoch (base_trainer replays it n_inner_epochs times in the
            # fused path; tests/test_kl_cadence.py)
            for _ in range(method.ppo_epochs):
                self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
        else:
            prev_loss = None

        chunks, kl_handles = [], []
        for ci, ((batch, out), spec, samples, spec_trimmed) in enumerate(zip(
            gens, specs, samples_list, trimmed_list
        )):
            if processed is not None:
                prompt_tensors, sample_outputs, outputs, scores, scores_mask = processed[ci]
            else:
                stats = {}
                prompt_tensors, sample_outputs, outputs, scores, scores_mask = (
                    self._host_process_chunk(batch, samples, stats)
                )

            scalar = scores.shape[1] == 1
            if scalar:
                scores_eff = np.where(scores_mask, scores, 0.0).astype(np.float32)
            else:
                scores_eff = np.zeros((len(sample_outputs), max_new), np.float32)
                w = min(scores.shape[1], max_new)
                scores_eff[:, :w] = np.where(scores_mask, scores, 0.0)[:, :w]
                # reward density is a property of the reward_fn: stop
                # dispatching speculative forwards from the next cycle on
                # (the scalar-only merge path can never consume them)
                self._spec_disabled_dense = True

            spec_hit = (
                spec is not None
                and spec_trimmed is not None
                and scalar  # dense rewards recheck widths; keep the fast path simple
                and spec_trimmed.shape == sample_outputs.shape
                and np.array_equal(spec_trimmed, sample_outputs)
                and np.array_equal(
                    np.asarray(batch["input_ids"]),
                    samples[:, :prompt_tensors.shape[1]],
                )
                # fast path: captured stats index the RAW response tokens
                # — require raw == host-retokenized so the windows align
                # 1:1 (else classic fallback rescoring, like a trim miss)
                and (
                    not use_fast
                    or np.array_equal(samples[:, prompt_tensors.shape[1]:], sample_outputs)
                )
            )
            if spec_hit:
                _, lp_win, v_win, logratio_win, mean_kl = spec
                merges = getattr(self, "_spec_merge_fns", None)
                if merges is None:
                    merges = self._spec_merge_fns = {}
                if scalar not in merges:
                    merges[scalar] = self._build_spec_merge_fn(scalar)
                chunk = merges[scalar](
                    jnp.asarray(prompt_tensors), jnp.asarray(sample_outputs),
                    lp_win, v_win, logratio_win,
                    jnp.asarray(scores_eff), jnp.float32(self.kl_ctl.value),
                )
            else:
                if spec is not None and scalar:
                    # count only real arbitration misses (trim mismatches),
                    # not the one-time dense-reward discovery chunk
                    self.spec_fallbacks = getattr(self, "spec_fallbacks", 0) + 1
                fns = getattr(self, "_score_reward_fns", None)
                if fns is None:
                    fns = self._score_reward_fns = {}
                if scalar not in fns:
                    fns[scalar] = self._build_score_reward_fn(scalar)
                chunk, mean_kl, _ = fns[scalar](
                    self.train_params, self.frozen_params, self.ref_params,
                    jnp.asarray(prompt_tensors), jnp.asarray(sample_outputs),
                    jnp.asarray(scores_eff), jnp.float32(self.kl_ctl.value),
                )
            # Trunk cache: reuse the sampler's captured h_split on a fast
            # spec hit (raw == retokenized, so the rows align 1:1 with the
            # chunk); otherwise one jitted trunk pass. No-op where the
            # schedule trains from the whole forward.
            chunk = self._attach_trunk_cache(
                chunk, captured=out.get("trunk_cache") if spec_hit else None
            )
            chunks.append(chunk)
            kl_handles.append(mean_kl)

        if k == 1:
            full, mean_kl = chunks[0], kl_handles[0]
        else:
            full = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0), *chunks
            )
            if full.trunk_rows is not None:
                # the chunks' caches are one array now, row for row
                full = full.replace(
                    trunk_rows=jnp.arange(full.trunk_rows.shape[0], dtype=jnp.int32))
            # cycle KL = mean over chunks (classic make_experience averages
            # its per-chunk stats the same way)
            mean_kl = jnp.mean(jnp.stack(kl_handles))

        if self._fast_rollout_available():
            # double-buffer one rollout ahead: gen(N+1) enqueues BEFORE
            # train(N), so next cycle's samples fetch and host reward
            # scoring hide under train(N). One step stale is PPO-sound —
            # the captured logprobs ARE the behavior policy's — and
            # donation-safe: train's donated buffers only invalidate
            # consumers enqueued after it, and the gens are already in.
            nxt_gens, nxt_specs = dispatch_chunks()
            stats = self._timed_train_epochs(full, method.ppo_epochs)
        else:
            stats = self._timed_train_epochs(full, method.ppo_epochs)
            nxt_gens, nxt_specs = dispatch_chunks()
        handles = (stats["losses"]["total_loss"], mean_kl)
        return prev_loss, (nxt_gens, nxt_specs, handles)

    def _timed_train_epochs(self, full, n_epochs):
        """train_epochs_from_chunk under a "train_epochs" phase span (the
        pipelined path does not go through `train_minibatch`)."""
        with self._span("ppo.train_epochs", phase="train_epochs", step=self.iter_count):
            return self.train_epochs_from_chunk(full, n_epochs)

    def post_backward_callback(self):
        # imported here, not at the top: the lines above hold the numbers that
        # the Pallas programs' cache keys keep (ROADMAP S5)
        from trlx_tpu.observability.compile_ledger import account

        self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
        account().mark("train.first_epoch")  # every program of a cycle has run once

    def create_train_dataloader(self, seed_offset: int = 0, drop_last: bool = False):
        # seed moves with iter_count so each inner epoch reshuffles (the
        # reference's torch DataLoader draws from global RNG each epoch);
        # seed_offset distinguishes epochs created up front by the fused path.
        # Pad widths are BUCKETED: the store's observed query maximum
        # rounds up to a 64-token bucket (capped by the config budget), so
        # batch shapes stay identical across rollout collections while
        # short prompts never pay the worst-case seq_length in train-step
        # FLOPs — padding a 64-token prompt to the 984-token budget made
        # every optimizer step ~10x more expensive. A recompile happens
        # only if a later collection crosses a bucket boundary.
        # Responses/stats use the experience budget (tight already).
        exp_kwargs = self.generate_experience_kwargs or self.generate_kwargs
        exp_max_new = int(exp_kwargs.get("max_new_tokens", 40))
        obs_q = max((len(e.query_tensor) for e in self.store.history), default=0)
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, drop_last=drop_last,
            seed=self.config.train.seed + self.iter_count + seed_offset,
            max_query_len=self._train_query_width(obs_q),
            max_response_len=exp_max_new + (1 if self.seq2seq else 0),
            max_stat_len=exp_max_new,
        )

    def _train_query_width(self, obs_q: int) -> int:
        """The width a train batch pads queries to, given the widest query
        of its store: the next 64-token bucket, capped by the config budget
        and never under the query itself (the collator raises a hint to
        what it observes). The trunk cache is filled in this layout."""
        eval_max_new = int(self.generate_kwargs.get("max_new_tokens", 40))
        budget_q = self.config.train.seq_length - eval_max_new
        return max(obs_q, min(budget_q, -(-obs_q // 64) * 64))

    def prepare_learning(self):
        self.eval_dataloader = self.eval_pipeline.create_loader(self.config.method.chunk_size)
        if self._resumed and len(self.store) > 0:
            # exact resume: the checkpoint restored the in-flight rollout
            # store (load() runs before prepare_learning); collecting a
            # fresh one here would both waste a collection and consume PRNG
            # splits the interrupted run never drew
            logger.info(
                f"Resume: reusing the restored rollout store "
                f"({len(self.store)} rollouts); skipping collection"
            )
        else:
            self.make_experience(self.config.method.num_rollouts)
        self.train_dataloader = self.create_train_dataloader()
        self.n_inner_epochs = self.config.method.ppo_epochs
        self.total_steps = (
            self.config.train.epochs * self.n_inner_epochs * len(self.train_dataloader)
        )
        self.total_steps = min(self.total_steps, self.config.train.total_steps)
