"""Pipeline-parallel PPO trainer.

Parity: the reference's NeMoPPOTrainer/PPOGPT path — PPO driven through
the Apex pipeline engine with a pinned-memory weight-swap reference model
and a double pipeline pass for logprob/value/ref precompute
(nemo_ppo_trainer.py:37-441, modeling_nemo_ppo.py:1095-1156). TPU-native
design:

- TRAIN loss runs as the stacked GPipe shard_map program (logits +
  replicated final hidden -> value head), like the other pipelined
  trainers;
- the rollout scorer makes TWO pipelined passes — policy(+value), then
  the frozen reference — the same schedule as NeMo's
  infer_logprobs_and_values, but the reference lives as a second stacked
  param tree sharded over the pipe axis instead of CPU<->GPU weight
  swaps;
- generation uses the sampling engine on a per-step-cached unstacked
  view SHARDED over the decode mesh (pipe folds into an fsdp' weight
  axis — PipeMeshRuntime.decode_mesh): NeMo instead decodes through the
  pipeline every token (modeling_nemo_ppo.py:1028-1093); here the
  decoder stays a single program while each chip holds only
  1/(pipe*fsdp*tensor) of the params, so models that need PP to fit can
  still collect rollouts.

Enable with:
    train.trainer: "PipelinedPPOTrainer"
    parallel: {data: D, pipeline: S}  (+ optional fsdp/tensor)

num_layers_unfrozen: any value. The frozen reference is always the full
stacked copy taken at init (numerically identical to the hydra branch for
any split, since everything below the split never trains); bottom-layer
freezing cuts gradients inside the stage scan and masks optimizer
updates at layer granularity (pipelined_mixin.make_update_mask). LoRA:
adapter leaves are separate stacked leaves, so peft trains through the
pipeline with per-leaf partitioning; the init-time copy doubles as the
adapter-zero reference (B starts at 0).
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from trlx_tpu.data import PPORLBatch
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.heads import MLPHead
from trlx_tpu.ops.ppo import get_advantages_and_returns, ppo_loss
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.pipelined_mixin import PipelinedCausalMixin
from trlx_tpu.trainer.ppo_trainer import PPOTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import logprobs_of_labels

logger = logging.get_logger(__name__)


@register_trainer
class PipelinedPPOTrainer(PipelinedCausalMixin, PPOTrainer):
    _supports_moe_pp = True  # in-pipe aux-loss carry consumed in make_loss_fn
    # r4: the 1F1B loss is expressed in full token width (prepare() scatters
    # the response windows to their predicting positions, CE-preshift
    # style), so it composes with sequence parallelism — the deep-model
    # long-context RL layout (reference megatron_65b.yaml:49-50,:80) no
    # longer falls back to GPipe's [B, t, V] logits bank.
    _1f1b_supports_sequence = True

    def __init__(self, config: TRLConfig, n_microbatches: Optional[int] = None, **kwargs):
        config = self._validate_pipeline_config(config)
        if getattr(config.method, "advantage_mode", None) is not None:
            # refuse critic-free method sections (GRPO/RLOO) up front with
            # the one-time warning, not a shape error deep in pipe setup
            if not getattr(self, "_warned_no_critic_free", False):
                self._warned_no_critic_free = True
                logger.warning(
                    "critic-free methods (GRPO/RLOO) are not supported under "
                    "pipeline parallelism; use the GSPMD GRPOTrainer"
                )
            raise NotImplementedError(
                "GRPO/RLOO method configs are not supported under pipeline "
                "parallelism; use the GSPMD GRPOTrainer"
            )
        if getattr(config.method, "num_value_layers_unfrozen", 0):
            raise NotImplementedError(
                "num_value_layers_unfrozen (the deeper value branch) is not "
                "supported under pipeline parallelism; use the GSPMD PPOTrainer"
            )
        self._n_microbatches = n_microbatches
        super().__init__(config, **kwargs)

    # ------------------------------------------------------------------
    # Frozen reference: a stacked copy sharded over the pipe axis
    # (replaces PPOTrainer.__init__'s ref_param_subtree on the standard
    # layout, which this layout cannot feed)
    # ------------------------------------------------------------------

    def _build_ref_params(self):
        """Frozen reference = a second stacked copy sharded over the pipe
        axis (the NeMo path's RefLMHeads weight-swap role, without the
        CPU<->GPU swaps)."""
        params = merge_params(self.train_params, self.frozen_params)
        return jax.tree_util.tree_map(
            jnp.copy, {"lm_stacked": params["lm_stacked"], "lm_rest": params["lm_rest"]}
        )

    def _head_module(self):
        return MLPHead(1, self.model_cfg.dtype, self.model_cfg.param_dtype)

    def _fast_rollout_available(self) -> bool:
        """The rollout fast path is unavailable here: the frozen reference
        lives STACKED over the pipe axis (_build_ref_params above), and
        the suffix resume (`forward(start=split)`) needs the unstacked
        per-block layout — the speculative/classic scorer stays in
        charge."""
        if (
            getattr(self.config.method, "capture_rollout_stats", False)
            and not getattr(self, "_warned_no_fast_rollout", False)
        ):
            self._warned_no_fast_rollout = True
            logger.warning(
                "method.capture_rollout_stats is ignored under pipeline "
                "parallelism (stacked reference cannot run the suffix "
                "resume); using the speculative/classic scorer"
            )
        return False

    def _trunk_cache_available(self) -> bool:
        """The trunk cache is unavailable here for the same reason as the
        fast rollout path: params live STACKED over the pipe axis, and
        the suffix resume (`forward(start=split)`) needs the unstacked
        per-block layout — the full-forward train loss stays in charge."""
        return False

    def _rollout_plan(self, width: int, gen_kwargs):
        """None: `PipelinedCausalMixin.generate` runs no `BlockPlan`."""
        return None

    def _decode_params(self):
        """The int8 decode view is unavailable here: quantize_frozen_flat
        walks the unstacked per-block layout, not the lm_stacked pytree —
        the dense merged tree stays in charge."""
        if (
            getattr(self.config.method, "quantize_frozen_trunk", False)
            and not getattr(self, "_warned_no_quantize", False)
        ):
            self._warned_no_quantize = True
            logger.warning(
                "method.quantize_frozen_trunk is ignored under pipeline "
                "parallelism (the int8 view targets the unstacked block "
                "layout); sampling with dense weights"
            )
        return self.params

    # ------------------------------------------------------------------
    # Loss through the GPipe program
    # ------------------------------------------------------------------

    def make_loss_fn(self) -> Callable:
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id
        moe, moe_coef = self._moe_loss_cfg()
        fwd = self.make_stacked_lm_forward(with_hidden=True, with_aux=moe)
        v_head = self._head_module()

        def loss_fn(train_params, frozen_params, batch: PPORLBatch):
            params = merge_params(train_params, frozen_params)
            query_tensors = batch.query_tensors
            response_tensors = batch.response_tensors
            response_length = batch.rewards.shape[1]

            advantages, returns = get_advantages_and_returns(
                batch.values, batch.rewards, method.gamma, method.lam
            )

            tokens = jnp.concatenate([query_tensors, response_tensors], axis=1)
            attention_mask = (tokens != pad_id).astype(jnp.int32)
            out = fwd(
                params["lm_stacked"], params["lm_rest"], tokens, attention_mask
            )
            if moe:
                logits, h_final, moe_aux = out
            else:
                logits, h_final = out
            values_pred = v_head.apply({"params": params["v_head"]}, h_final)[..., 0]
            values_pred = values_pred[:, :-1]
            logprobs = logprobs_of_labels(logits[:, :-1, :], tokens[:, 1:])

            start = query_tensors.shape[1] - 1
            end = start + response_length
            loss, stats = ppo_loss(
                logprobs=logprobs[:, start:end],
                values=values_pred[:, start:end],
                old_logprobs=batch.logprobs,
                old_values=batch.values,
                advantages=advantages,
                returns=returns,
                mask=attention_mask[:, start + 1 : end + 1],
                cliprange=method.cliprange,
                cliprange_value=method.cliprange_value,
                vf_coef=method.vf_coef,
            )
            if moe:
                # in-pipe aux carry, same coefficient as the GSPMD route
                aux = moe_coef * moe_aux
                loss = loss + aux
                stats = {
                    **stats, "moe_aux_loss": aux,
                    "losses": {**stats["losses"], "total_loss": loss},
                }
            return loss, stats

        return loss_fn

    # ------------------------------------------------------------------
    # 1F1B loss (parallel.pipeline_schedule: "1f1b"): the per-microbatch
    # decomposition of ppo_loss. Every sum in the clipped objective and
    # its stats is normalized by the GLOBAL masked-token count (computed
    # once in ctx), so summed microbatch contributions equal the
    # batch-level loss exactly; min/max stats ride pmin/pmax and std uses
    # the algebraically-equal sqrt(E[x^2] - mean^2) form.
    # ------------------------------------------------------------------

    def make_1f1b_loss_parts(self, model):
        method = self.config.method
        pad_id = self.tokenizer.pad_token_id
        v_head = self._head_module()

        from trlx_tpu.parallel.onef1b import (
            finalize_tensor_stats,
            gated_reducers,
            masked_sums,
        )

        def prepare(batch: PPORLBatch):
            """Re-express the response-window PPO loss in FULL token width:
            every per-position tensor (old logprobs/values, advantages,
            returns, masks) is placed at its PREDICTING position p (the
            logit at p scores token p+1 — the same global preshift the CE
            trainers use), so the in-pipe loss is purely elementwise and a
            sequence shard never reads a neighbor's window. The windows
            live here, outside the shard_map, where they are free."""
            tokens = jnp.concatenate(
                [batch.query_tensors, batch.response_tensors], axis=1
            )
            attn = (tokens != pad_id).astype(jnp.int32)
            advantages, returns = get_advantages_and_returns(
                batch.values, batch.rewards, method.gamma, method.lam
            )
            B, t = tokens.shape
            q = batch.query_tensors.shape[1]
            r = batch.response_tensors.shape[1]
            start = q - 1  # predicting positions for the response: start..t-2

            def widen(x):
                full = jnp.zeros((B, t), jnp.float32)
                return jax.lax.dynamic_update_slice(
                    full, x.astype(jnp.float32), (0, start)
                )

            m_full = widen(attn[:, start + 1 : start + r + 1])
            win_full = widen(jnp.ones((B, r), jnp.float32))
            loss_batch = dict(
                # CE-style preshifted labels: label[p] = token[p+1]
                labels=jnp.pad(tokens[:, 1:], ((0, 0), (0, 1))),
                mask=m_full,
                window=win_full,
                old_logprobs=widen(batch.logprobs),
                old_values=widen(batch.values),
                advantages=widen(advantages),
                returns=widen(returns),
            )
            return tokens, attn, loss_batch

        def ctx_fn(tokens, attn_mask, batch):
            # reduced over ("data", "sequence"): under PP x SP each shard
            # contributes its local masked count; without SP the sequence
            # axis is size 1 but still manual, so the psum keeps n
            # replicated as the out_specs require
            count = jax.lax.psum(batch["mask"].sum(), ("data", "sequence"))
            n = jnp.maximum(count, 1.0)
            size = jax.lax.psum(batch["window"].sum(), ("data", "sequence"))
            return {"n": n, "count": count, "size": size}

        def loss_mb(rest, heads, h, tok, mask, mb, ctx):
            logits, h_final = model.apply({"params": rest}, h, method=model.unembed)
            values = v_head.apply({"params": heads["v_head"]}, h_final)[..., 0]
            lp = logprobs_of_labels(logits, mb["labels"])
            vp = values
            m = mb["mask"]
            old_lp, old_v = mb["old_logprobs"], mb["old_values"]
            adv, ret = mb["advantages"], mb["returns"]
            n = ctx["n"]

            vc = jnp.clip(
                vp, old_v - method.cliprange_value, old_v + method.cliprange_value
            )
            vf1 = (vp - ret) ** 2
            vf2 = (vc - ret) ** 2
            vf_max_sum = (jnp.maximum(vf1, vf2) * m).sum()
            log_ratio = (lp - old_lp) * m
            ratio = jnp.exp(log_ratio)
            pg1 = -adv * ratio
            pg2 = -adv * jnp.clip(
                ratio, 1.0 - method.cliprange, 1.0 + method.cliprange
            )
            pg_sum = (jnp.maximum(pg1, pg2) * m).sum()

            loss_contrib = pg_sum / n + method.vf_coef * 0.5 * vf_max_sum / n
            stats = dict(
                pg_sum=pg_sum,
                vf_max_sum=vf_max_sum,
                vf_clip_sum=((vf2 > vf1).astype(jnp.float32) * m).sum(),
                pg_clip_sum=((pg2 > pg1).astype(jnp.float32) * m).sum(),
                ratio_sum=(ratio * m).sum(),
                kl_sum=((ratio - 1) - log_ratio).sum(),
                verr_sum=(((vp - ret) * m) ** 2).sum(),
                values=masked_sums(vp, m),
                old_values=masked_sums(old_v, m),
                returns=masked_sums(ret, m),
            )
            return loss_contrib, jax.lax.stop_gradient(stats)

        def finalize_fn(ts, gate, ctx):
            n, size = ctx["n"], ctx["size"]
            gsum, gmin, gmax = gated_reducers(gate)

            def tensor_stats(d):
                return finalize_tensor_stats(d, n, gsum, gmin, gmax,
                                             count=ctx.get("count"))

            pg_loss = gsum(ts["pg_sum"]) / n
            vf_loss = 0.5 * gsum(ts["vf_max_sum"]) / n
            loss = pg_loss + method.vf_coef * vf_loss
            return dict(
                losses=dict(
                    total_loss=loss, policy_loss=pg_loss, value_loss=vf_loss
                ),
                values=dict(
                    **tensor_stats(ts["values"]),
                    values_error=gsum(ts["verr_sum"]) / n,
                    clipfrac=gsum(ts["vf_clip_sum"]) / n,
                ),
                old_values=tensor_stats(ts["old_values"]),
                returns=tensor_stats(ts["returns"]),
                policy=dict(
                    approx_kl=gsum(ts["kl_sum"]) / size,
                    clipfrac=gsum(ts["pg_clip_sum"]) / n,
                ),
                ratio=gsum(ts["ratio_sum"]) / n,
                padding_percentage=1.0 - n / size,
            )

        return {
            "prepare": prepare,
            "ctx_fn": ctx_fn,
            "loss_mb": loss_mb,
            "finalize_fn": finalize_fn,
            # every loss_batch leaf is full token width by construction, so
            # all of them take the SP divisibility padding
            "seq_aligned": {
                "labels", "mask", "window", "old_logprobs", "old_values",
                "advantages", "returns",
            },
        }

    # ------------------------------------------------------------------
    # Rollout scorer: double pipelined pass (policy+value, then reference)
    # ------------------------------------------------------------------

    def _build_score_fn(self):
        pad_id = self.tokenizer.pad_token_id
        fwd = self.make_stacked_lm_forward(with_hidden=True)
        v_head = self._head_module()

        def score(train_params, frozen_params, ref_params, all_tokens):
            params = merge_params(train_params, frozen_params)
            attention_mask = (all_tokens != pad_id).astype(jnp.int32)
            logits, h_final = fwd(
                params["lm_stacked"], params["lm_rest"], all_tokens, attention_mask
            )
            values = v_head.apply({"params": params["v_head"]}, h_final)[..., 0]
            ref_logits, _ = fwd(
                ref_params["lm_stacked"], ref_params["lm_rest"], all_tokens, attention_mask
            )
            ref_logits = jax.lax.stop_gradient(ref_logits)

            logprobs = logprobs_of_labels(logits[:, :-1, :], all_tokens[:, 1:])
            ref_logprobs = logprobs_of_labels(ref_logits[:, :-1, :], all_tokens[:, 1:])
            log_ratio = (logprobs - ref_logprobs) * attention_mask[:, :-1]
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            # order matches PPOTrainer's score fn: (..., mean per-sequence
            # KL, mean per-token KL) — the KL controller consumes the first
            return logprobs, values[:, :-1], log_ratio, kl.sum(1).mean(), kl.mean()

        self._score_fn = self._ljit(score, "pipelined_score", budget=2)

    def create_train_dataloader(self, seed_offset: int = 0):
        # PPO's static-pad-width loader, with the pipelined drop_last
        # (GPipe cannot replicate a ragged tail batch)
        return PPOTrainer.create_train_dataloader(self, seed_offset, drop_last=True)
