"""GRPO / RLOO trainer: critic-free group-relative policy optimization.

GRPO (Shao et al., DeepSeekMath 2024) samples G completions per prompt and
uses the group-standardized reward as the advantage — no value head, no
GAE, no value loss. RLOO (Ahmadian et al. 2024) is the same machinery with
a leave-one-out baseline instead of group standardization
(`method.advantage_mode`). Both keep PPO's clipped ratio and add an
explicit in-loss k3 KL penalty to the frozen reference
(trlx_tpu/ops/ppo.py:grpo_loss).

Structurally this subclasses PPOTrainer for the rollout cycle (fleet
routing, behavior-logprob arbitration, sentinel quarantine, resume state)
but swaps out everything the critic touched:

- the model is CausalLMPolicy — zero value-head parameters anywhere in the
  tree (and with the head gone, every hydra/value-tap gate constraint
  drops out);
- the scorer returns REFERENCE logprobs in the values slot (grpo_loss's
  KL anchor) instead of V(s);
- `make_experience` samples G completions per prompt: through the fleet
  via the server's `n` fan-out (Scheduler.submit_n — one full prefill +
  G suffix prefills against shared prefix blocks), or locally via batched
  generation over G-repeated prompts;
- rollout elements carry a `group_id` so advantages are normalized per
  prompt group, never per chunk.
"""

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import PPORLBatch, PPORLElement
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.method_configs import MethodConfig, register_method
from trlx_tpu.models import build_model, forward_policy_and_ref, position_ids
from trlx_tpu.ops.ppo import group_relative_advantages, grpo_loss
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.ppo_trainer import PPOTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import logprobs_of_labels

logger = logging.get_logger(__name__)

ADVANTAGE_MODES = ("grpo", "rloo")


@dataclass
@register_method
class GRPOConfig(MethodConfig):
    """Critic-free method section. The PPO-named fields keep their PPO
    meaning (the rollout cycle is shared); the value-function fields
    (gamma/lam/cliprange_value/vf_coef) are gone because the method has
    no value function."""

    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    # completions per prompt (G). chunk_size and num_rollouts count
    # COMPLETIONS and must be divisible by it.
    group_size: int = 8
    # "grpo": A_i = (r_i - mean_G) / (std_G + eps)
    # "rloo": A_i = r_i - mean(r_{j != i})
    advantage_mode: str = "grpo"
    # in-loss k3 KL-to-reference coefficient (GRPO eq. 3's beta)
    grpo_kl_coef: float = 0.02
    # optional PPO-style per-token KL reward shaping on top (0 = pure GRPO)
    init_kl_coef: float = 0.0
    target: Optional[float] = None
    horizon: int = 10000
    cliprange: float = 0.2
    scale_reward: Optional[str] = None
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    gen_kwargs: dict = field(default_factory=dict)
    gen_experience_kwargs: Optional[dict] = None
    # multi-turn rollouts (trlx_tpu/environments.py): registered env name
    # drives make_experience_multiturn through fleet chat sessions. The G
    # completions of a group share one env seed (same task) so the
    # group-relative advantage compares like with like. None (default)
    # keeps single-turn rollouts bit-identical.
    multiturn_env: Optional[str] = None
    multiturn_max_turns: int = 4
    multiturn_env_kwargs: dict = field(default_factory=dict)


@register_trainer
class GRPOTrainer(PPOTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        method = config.method
        if config.model.model_arch_type == "seq2seq":
            raise NotImplementedError("GRPO/RLOO are causal-only")
        mode = getattr(method, "advantage_mode", "grpo")
        if mode not in ADVANTAGE_MODES:
            raise ValueError(
                f"method.advantage_mode {mode!r} not in {ADVANTAGE_MODES}"
            )
        G = int(method.group_size)
        if G < 1:
            raise ValueError(f"method.group_size must be >= 1, got {G}")
        if method.chunk_size % G or method.num_rollouts % G:
            raise ValueError(
                f"chunk_size ({method.chunk_size}) and num_rollouts "
                f"({method.num_rollouts}) must be divisible by group_size ({G})"
            )
        if config.model.num_layers_unfrozen == 0:
            raise ValueError(
                "GRPO has no value head: num_layers_unfrozen=0 would leave "
                "nothing trainable (use -1 or a positive layer count)"
            )
        super().__init__(config, **kwargs)
        # running prompt-group counter; every element's group_id comes from
        # here so normalization stays per-group across chunk boundaries
        self._group_offset = 0

    def get_arch(self, config: TRLConfig):
        return build_model(
            config.model,
            vocab_size=self.tokenizer.vocab_size,
            rng=jax.random.PRNGKey(config.train.seed),
            value_head=False,
        )

    # ------------------------------------------------------------------
    # Loss: clipped ratio + in-loss KL to reference; no GAE, no value loss
    # ------------------------------------------------------------------

    def make_loss_fn(self) -> Callable:
        model = self.model
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id

        def loss_fn(train_params, frozen_params, batch: PPORLBatch):
            params = merge_params(train_params, frozen_params)
            query_tensors = batch.query_tensors
            response_tensors = batch.response_tensors
            old_logprobs = batch.logprobs
            ref_logprobs = batch.values  # scorer packs ref logprobs here
            advantages = batch.rewards  # per-token broadcast group advantage
            response_length = advantages.shape[1]

            tokens = jnp.concatenate([query_tensors, response_tensors], axis=1)
            attention_mask = (tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            start = query_tensors.shape[1] - 1
            end = start + response_length
            mask = attention_mask[:, start + 1 : end + 1]
            if batch.loss_masks is not None:
                # multi-turn rollouts: environment-authored tokens carry
                # zero loss weight (context, not actions)
                mask = mask * batch.loss_masks.astype(mask.dtype)

            moe_aux = 0.0
            if getattr(self.model_cfg, "moe_experts", 0) > 0:
                from trlx_tpu.utils.modeling import apply_with_moe_aux

                (logits, _, _), moe_aux = apply_with_moe_aux(
                    self.model_cfg, model, params,
                    tokens, attention_mask, positions,
                )
                logprobs = logprobs_of_labels(logits[:, :-1, :], tokens[:, 1:])
                logprobs = logprobs[:, start:end]
            else:
                window = (start, response_length) if self._window_loss_ok() else None
                logits, _, _ = model.apply(
                    {"params": params}, tokens, attention_mask, positions,
                    window=window, method=type(model).forward,
                )
                if window is not None:
                    logprobs = logprobs_of_labels(
                        logits, tokens[:, start + 1 : end + 1]
                    )
                else:
                    logprobs = logprobs_of_labels(logits[:, :-1, :], tokens[:, 1:])
                    logprobs = logprobs[:, start:end]

            loss, stats = grpo_loss(
                logprobs=logprobs,
                old_logprobs=old_logprobs,
                ref_logprobs=ref_logprobs,
                advantages=advantages,
                mask=mask,
                cliprange=method.cliprange,
                kl_coef=method.grpo_kl_coef,
            )
            if getattr(self.model_cfg, "moe_experts", 0) > 0:
                loss = loss + moe_aux
                stats = {
                    **stats, "moe_aux_loss": moe_aux,
                    "losses": {**stats["losses"], "total_loss": loss},
                }
            return loss, stats

        return loss_fn

    # ------------------------------------------------------------------
    # Scoring: policy + reference logprobs (the values slot carries the
    # reference — grpo_loss's KL anchor — instead of V(s))
    # ------------------------------------------------------------------

    def _build_score_fn(self):
        model = self.model
        split = self.split
        pad_id = self.tokenizer.pad_token_id

        def score(train_params, frozen_params, ref_params, all_tokens):
            params = merge_params(train_params, frozen_params)
            attention_mask = (all_tokens != pad_id).astype(jnp.int32)
            positions = position_ids(attention_mask)
            logits, _, ref_logits, _ = forward_policy_and_ref(
                model, params, ref_params, all_tokens, attention_mask, split, positions
            )
            logprobs = logprobs_of_labels(logits[:, :-1, :], all_tokens[:, 1:])
            ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], all_tokens[:, 1:])
            log_ratio = (logprobs - ref_logprobs) * attention_mask[:, :-1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            mean_kl_per_token = kl.mean()
            mean_kl = kl.sum(1).mean()
            return logprobs, ref_logprobs, log_ratio, mean_kl, mean_kl_per_token

        self._score_fn = self._ljit(score, "grpo_score", budget=2)

    # ------------------------------------------------------------------
    # G-per-prompt experience collection
    # ------------------------------------------------------------------

    def add_prompt_pipeline(self, pipeline):
        """Each chunk holds chunk_size COMPLETIONS = chunk_size/G prompts.
        The iterator yields pre-expanded batches (each prompt repeated G
        adjacent times) so the inherited make_experience loop, reward
        scoring, and scorer all see one row per completion."""
        G = int(self.config.method.group_size)
        prompts_per_chunk = max(self.config.method.chunk_size // G, 1)
        base = self._rollout_stream(pipeline, prompts_per_chunk)

        def repeat_rows(v):
            if isinstance(v, np.ndarray):
                return np.repeat(v, G, axis=0)
            arr = np.asarray(v)
            if arr.dtype != object and arr.ndim >= 1:
                return np.repeat(arr, G, axis=0)
            return [x for x in v for _ in range(G)]

        def expanded():
            while True:
                b = next(base)
                yield {k: repeat_rows(v) for k, v in b.items()}

        self.prompt_iterator = expanded()

    def _fleet_generate(self, batch, gen_kwargs, trainer_step: int = 0):
        """Route the G-per-prompt fan-out through the fleet's `n` field —
        the server turns it into Scheduler.submit_n, so the G sequences
        share the prompt's prefix blocks (one full prefill + G suffix
        prefills when kv paging + prefix cache are on). The batch arrives
        pre-expanded (G adjacent identical rows per prompt); only the
        unique prompts travel. Degrades to local batched generation over
        the repeated prompts when the whole fleet is down."""
        from trlx_tpu.inference.fleet import FleetUnavailableError

        G = int(self.config.method.group_size)
        if G == 1:
            return super()._fleet_generate(batch, gen_kwargs, trainer_step)

        pad_id = self.tokenizer.pad_token_id
        max_new = int(gen_kwargs.get("max_new_tokens", 40))
        input_ids = np.asarray(batch["input_ids"])
        attention_mask = np.asarray(batch["attention_mask"])
        n_rows, plen = input_ids.shape
        assert n_rows % G == 0, "expanded batch must hold whole groups"
        prompts = [
            [int(t) for t, m in zip(row, mask) if m]
            for row, mask in zip(input_ids[::G], attention_mask[::G])
        ]
        router = self._get_rollout_router()
        if self._rollout_supervisor is not None:
            self._push_params_to_thread_replicas()
            router.set_trainer_step(self._rollout_supervisor.synced_step)
        else:
            router.set_trainer_step(trainer_step)
        try:
            replies = router.generate(prompts, max_new_tokens=max_new, n=G)
        except FleetUnavailableError as e:
            logger.warning_once(
                f"rollout fleet unavailable; degrading to local generation ({e})"
            )
            out = dict(
                self.generate(batch["input_ids"], batch["attention_mask"], gen_kwargs)
            )
            out["fleet_degraded"] = True
            return out

        samples = np.full((n_rows, plen + max_new), pad_id, dtype=np.int32)
        samples[:, :plen] = input_ids
        response_tokens = np.full((n_rows, max_new), pad_id, dtype=np.int32)
        response_mask = np.zeros((n_rows, max_new), dtype=np.int32)
        behavior_logprobs = np.zeros((n_rows, max_new), dtype=np.float32)
        for p, rep in enumerate(replies):
            seqs = rep.get("sequences") or [rep]
            for g in range(G):
                i = p * G + g
                seq = seqs[min(g, len(seqs) - 1)]
                toks = list(seq["token_ids"])[:max_new]
                lps = list(seq.get("token_logprobs") or [])[: len(toks)]
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

    def _chunk_to_elements(self, prompt_tensors, sample_outputs, outputs,
                           scores, scores_mask, logprobs, values, log_ratio,
                           trunk_row0=None):
        """Group-relative advantages instead of per-token rewards + GAE.
        Each group's G rows are adjacent (the expanded batch guarantees
        it); the sequence-level advantage is broadcast over the response
        tokens into the `rewards` slot, and `values` carries the
        reference logprobs the scorer packed there. An optional PPO-style
        per-token KL penalty (init_kl_coef > 0) adds on top; at the
        default 0.0 the advantage is pure."""
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id
        G = int(method.group_size)
        start = prompt_tensors.shape[1] - 1
        n_rows = len(sample_outputs)
        assert n_rows % G == 0, "chunk must hold whole prompt groups"

        sample_scores = (np.where(scores_mask, scores, 0.0)).sum(axis=1)
        adv = np.asarray(
            group_relative_advantages(
                jnp.asarray(sample_scores.reshape(-1, G)),
                mode=method.advantage_mode,
            )
        ).reshape(-1)

        kl_coef = self.kl_ctl.value
        if self._sentinel is not None:
            kl_coef *= self._sentinel.kl_scale(self.iter_count)
        kl_penalty = -kl_coef * log_ratio

        elements = []
        for ix in range(n_rows):
            n_resp = int((sample_outputs[ix] != pad_id).sum())
            if n_resp == 0:
                n_resp = 1  # degenerate empty response: keep one slot
            end = start + n_resp
            rewards = kl_penalty[ix, start:end].copy()
            rewards += adv[ix]
            elements.append(
                PPORLElement(
                    query_tensor=prompt_tensors[ix],
                    response_tensor=sample_outputs[ix, :n_resp],
                    logprobs=logprobs[ix, start:end],
                    values=values[ix, start:end],
                    rewards=rewards,
                    group_id=self._group_offset + ix // G,
                )
            )
        self._group_offset += n_rows // G
        return elements

    # ------------------------------------------------------------------
    # Multi-turn experience overrides
    # ------------------------------------------------------------------

    def _multiturn_group_size(self) -> int:
        """Same-seed groups of G episodes (the multi-turn analogue of G
        completions per prompt)."""
        return int(self.config.method.group_size)

    def _multiturn_elements(self, rows, prompt_tensors, sample_outputs,
                            loss_mask, env_rewards, logprobs, values,
                            log_ratio, start, max_r):
        """Group-relative EPISODE advantages: each episode's total
        environment reward is group-standardized against its G same-seed
        siblings and broadcast over the response; `values` already
        carries the reference logprobs this trainer's scorer packs there
        (the in-loss grpo_kl_coef anchor). The optional init_kl_coef
        per-token shaping lands on policy tokens only — environment
        tokens are context, not actions."""
        method = self.config.method
        G = int(method.group_size)
        n = len(rows)
        assert n % G == 0, "multi-turn chunk must hold whole seed groups"

        totals = env_rewards.sum(axis=1)
        adv = np.asarray(
            group_relative_advantages(
                jnp.asarray(totals.reshape(-1, G)),
                mode=method.advantage_mode,
            )
        ).reshape(-1)

        kl_coef = self.kl_ctl.value
        if self._sentinel is not None:
            kl_coef *= self._sentinel.kl_scale(self.iter_count)

        elements = []
        for i, (_p, ids, _lm, _er, _bl, _h) in enumerate(rows):
            n_resp = max(min(len(ids), max_r), 1)
            end = start + n_resp
            lmask_row = np.asarray(loss_mask[i, :n_resp], np.float32)
            rewards = (-kl_coef * log_ratio[i, start:end]) * lmask_row
            rewards = rewards.astype(np.float32) + adv[i]
            elements.append(
                PPORLElement(
                    query_tensor=prompt_tensors[i],
                    response_tensor=sample_outputs[i, :n_resp],
                    logprobs=logprobs[i, start:end],
                    values=values[i, start:end],
                    rewards=rewards,
                    group_id=self._group_offset + i // G,
                    loss_mask=lmask_row.copy(),
                )
            )
        self._group_offset += n // G
        return elements
