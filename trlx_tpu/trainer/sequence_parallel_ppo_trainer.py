"""Sequence-parallel (context-parallel) PPO trainer: long-context RLHF
with the policy/reference/value forwards sharded along the sequence dim
and ring attention streaming K/V around the `sequence` mesh axis.

Division of labor (same pattern as SequenceParallelSFTTrainer):
- INSIDE one `shard_map` program: the transformer forwards (policy, the
  hydra reference branch, the value head) and per-position
  logprob-of-labels — everything that is elementwise along sequence or a
  ring collective.
- OUTSIDE (plain GSPMD on small [b, t] arrays): the label shift (crosses
  shard boundaries), GAE over the stored response values, the response
  slicing, and the clipped PPO loss/stats.
- Generation stays on the cached decode engine (replicated arrays; cached
  decode never uses the fused kernels).

PPO queries are LEFT-padded (PPORolloutStorage collation), so positions
are computed globally from the attention mask and passed in explicitly —
the ring shard-offset default assumes right padding and is bypassed.

Enable with:
    train.trainer: "SequenceParallelPPOTrainer"
    parallel: {data: D, sequence: S}  (+ optional fsdp/tensor: GSPMD-auto
        inside the shard_map — parallel/context.py partial_shard_map;
        pipeline stays 1)
"""

from typing import Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.policy import forward_policy_and_ref
from trlx_tpu.models.transformer import position_ids
from trlx_tpu.ops.ppo import get_advantages_and_returns, ppo_loss
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.ppo_trainer import PPOTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import logprobs_of_labels

logger = logging.get_logger(__name__)


@register_trainer
class SequenceParallelPPOTrainer(PPOTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        from trlx_tpu.trainer.sequence_parallel_sft_trainer import (
            validate_sequence_parallel_config,
        )

        config = validate_sequence_parallel_config(config, type(self).__name__)
        if config.model.model_arch_type != "causal":
            raise NotImplementedError("sequence-parallel PPO covers causal models")
        if getattr(config.method, "advantage_mode", None) is not None:
            # refuse critic-free method sections (GRPO/RLOO) up front with
            # the one-time warning, not a shape error deep in shard_map setup
            if not getattr(self, "_warned_no_critic_free", False):
                self._warned_no_critic_free = True
                logger.warning(
                    "critic-free methods (GRPO/RLOO) are not supported under "
                    "sequence parallelism; use the GSPMD GRPOTrainer"
                )
            raise NotImplementedError(
                "GRPO/RLOO method configs are not supported under sequence "
                "parallelism; use the GSPMD GRPOTrainer"
            )
        if getattr(config.method, "num_value_layers_unfrozen", 0):
            raise NotImplementedError(
                "the deeper value branch under sequence parallelism is not "
                "supported yet"
            )
        super().__init__(config, **kwargs)

    def add_prompt_pipeline(self, pipeline):
        # ragged last chunks can't divide across the shard_map's data axis
        from trlx_tpu.utils import infinite_dataloader

        loader = pipeline.create_loader(
            self.config.method.chunk_size, shuffle=True, drop_last=True
        )
        self.prompt_iterator = infinite_dataloader(loader)

    def create_train_dataloader(self, seed_offset: int = 0, drop_last: bool = True):
        return super().create_train_dataloader(seed_offset, drop_last=True)

    def _fast_rollout_available(self) -> bool:
        """The rollout fast path is unavailable here: scoring runs inside
        a shard_map over the sequence axis (_build_score_fn below), and
        the captured h_split/suffix resume lives outside that layout —
        the speculative/classic scorer stays in charge."""
        if (
            getattr(self.config.method, "capture_rollout_stats", False)
            and not getattr(self, "_warned_no_fast_rollout", False)
        ):
            self._warned_no_fast_rollout = True
            logger.warning(
                "method.capture_rollout_stats is ignored under sequence "
                "parallelism (sharded scoring cannot consume the captured "
                "split activations); using the speculative/classic scorer"
            )
        return False

    def _trunk_cache_available(self) -> bool:
        """The trunk cache is unavailable here: the train loss runs inside
        a shard_map over the sequence axis, and the cached-split resume
        lives outside that layout — the full-forward loss stays in charge."""
        return False

    def _decode_params(self):
        """The int8 decode view is unavailable here: the sharded decode
        path consumes the dense replicated tree — dense weights stay in
        charge."""
        if (
            getattr(self.config.method, "quantize_frozen_trunk", False)
            and not getattr(self, "_warned_no_quantize", False)
        ):
            self._warned_no_quantize = True
            logger.warning(
                "method.quantize_frozen_trunk is ignored under sequence "
                "parallelism (the sharded decode path consumes dense "
                "weights); sampling with dense weights"
            )
        return self.params

    # ------------------------------------------------------------------
    # Shared shard_map forward: per-position logprobs (+values, +ref)
    # ------------------------------------------------------------------

    def _sp_spec(self):
        return P("data", "sequence")

    def _seq_pad(self, tokens):
        """Right-pad [b, t] to a sequence-divisible width with pad_id
        (pads are mask-0, so all downstream slices stay valid)."""
        S = self.config.parallel.sequence
        t = tokens.shape[1]
        rem = (-t) % S
        if rem:
            tokens = jnp.pad(
                tokens, ((0, 0), (0, rem)),
                constant_values=self.tokenizer.pad_token_id,
            )
        return tokens

    def _global_inputs(self, tokens):
        """Global (unsharded) mask / positions / shifted labels — the
        pieces that cross shard boundaries."""
        pad_id = self.tokenizer.pad_token_id
        mask = (tokens != pad_id).astype(jnp.int32)
        positions = position_ids(mask)
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.full_like(tokens[:, :1], pad_id)], axis=1
        )
        return mask, positions, labels

    def make_loss_fn(self) -> Callable:
        model = self.model
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id
        mesh = self.runtime.mesh
        spec = self._sp_spec()

        def local_fwd(params, tokens, mask, positions, labels):
            logits, values, _ = model.apply(
                {"params": params}, tokens, mask, positions
            )
            lp = logprobs_of_labels(logits, labels)
            return lp, values

        from trlx_tpu.parallel.context import partial_shard_map

        smap = partial_shard_map(
            local_fwd, mesh,
            in_specs=(P(), spec, spec, spec, spec),
            out_specs=(spec, spec),
            manual={"data", "sequence"},
            compute_dtype=self.model_cfg.dtype,
        )

        def loss_fn(train_params, frozen_params, batch):
            params = merge_params(train_params, frozen_params)
            query_tensors = batch.query_tensors
            response_tensors = batch.response_tensors
            response_length = batch.rewards.shape[1]

            advantages, returns = get_advantages_and_returns(
                batch.values, batch.rewards, method.gamma, method.lam
            )

            tokens = jnp.concatenate([query_tensors, response_tensors], axis=1)
            tokens_p = self._seq_pad(tokens)
            mask, positions, labels = self._global_inputs(tokens_p)
            lp_full, values_full = smap(params, tokens_p, mask, positions, labels)

            start = query_tensors.shape[1] - 1
            end = start + response_length
            logprobs = lp_full[:, start:end]
            values_pred = values_full[:, start:end]
            resp_mask = mask[:, start + 1 : end + 1]

            loss, stats = ppo_loss(
                logprobs=logprobs,
                values=values_pred,
                old_logprobs=batch.logprobs,
                old_values=batch.values,
                advantages=advantages,
                returns=returns,
                mask=resp_mask,
                cliprange=method.cliprange,
                cliprange_value=method.cliprange_value,
                vf_coef=method.vf_coef,
            )
            return loss, stats

        return loss_fn

    def _build_score_fn(self):
        model = self.model
        split = self.split
        mesh = self.runtime.mesh
        spec = self._sp_spec()

        def local_score(params, ref_params, tokens, mask, positions, labels):
            logits, values, ref_logits, _ = forward_policy_and_ref(
                model, params, ref_params, tokens, mask, split, positions
            )
            lp = logprobs_of_labels(logits, labels)
            ref_lp = logprobs_of_labels(ref_logits, labels)
            return lp, ref_lp, values

        from trlx_tpu.parallel.context import partial_shard_map

        smap = partial_shard_map(
            local_score, mesh,
            in_specs=(P(), P(), spec, spec, spec, spec),
            out_specs=(spec, spec, spec),
            manual={"data", "sequence"},
            compute_dtype=self.model_cfg.dtype,
        )

        def score(train_params, frozen_params, ref_params, all_tokens):
            params = merge_params(train_params, frozen_params)
            t = all_tokens.shape[1]
            tokens_p = self._seq_pad(all_tokens)
            mask, positions, labels = self._global_inputs(tokens_p)
            lp_full, ref_full, values_full = smap(
                params, ref_params, tokens_p, mask, positions, labels
            )
            logprobs = lp_full[:, : t - 1]
            ref_logprobs = ref_full[:, : t - 1]
            log_ratio = (logprobs - ref_logprobs) * mask[:, : t - 1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            mean_kl_per_token = kl.mean()
            mean_kl = kl.sum(1).mean()
            return logprobs, values_full[:, : t - 1], log_ratio, mean_kl, mean_kl_per_token

        self._score_fn = self._ljit(score, "sp_score", budget=2)
