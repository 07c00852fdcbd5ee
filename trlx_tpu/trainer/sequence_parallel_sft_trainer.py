"""Sequence-parallel (context-parallel) SFT trainer: long-context training
with activations sharded along the sequence dim and ring attention
streaming K/V blocks around the `sequence` mesh axis.

The reference's longest context is one TP group's memory under Megatron SP
(SURVEY.md §5.7: encoder_seq_length 2048, no ring/Ulysses/CP anywhere);
this trainer is the capability it lacks: context length scales with chips.
The train step is one `shard_map` program over the standard
("data","fsdp","tensor","sequence") mesh — batch over (data, fsdp),
sequence over `sequence`, params replicated across the sequence axis —
whose blocks run shard-local except ring attention's K/V ppermute ring;
the CE label shift (which crosses shard boundaries) happens on the global
arrays before entering the shard_map, and the masked-mean reduction is a
psum. Backward is pure autodiff (ppermute transposes to the reverse ring).

Enable with:
    train.trainer: "SequenceParallelSFTTrainer"
    train.seq_length: <long, divisible by parallel.sequence>
    tokenizer.padding_side: "right"   (ring positions assume right padding)
    parallel: {data: D, sequence: S}  (+ optional fsdp/tensor: those axes
        stay GSPMD-auto inside the shard_map, so ZeRO/TP param sharding
        composes with the sequence axis — parallel/context.py
        partial_shard_map; pipeline stays 1)

Generation (eval) runs the regular cached decode engine on replicated
arrays — the einsum path, since cached decode never uses the fused
kernels — so only the training forward is context-parallel.
"""

from typing import Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.pipeline.offline_pipeline import DialogStore
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.sft_trainer import SFTTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.modeling import logprobs_of_labels

logger = logging.get_logger(__name__)


def validate_sequence_parallel_config(config: TRLConfig, cls_name: str) -> TRLConfig:
    """Shared constraints of the sequence-parallel trainers: a real
    sequence axis, no pipeline composition, ring attention forced,
    divisible seq_length, no MoE (the load-balancing aux loss cannot
    cross the shard_map program). fsdp/tensor COMPOSE: they stay
    GSPMD-auto inside the SP shard_map (parallel/context.py
    partial_shard_map), so params keep their rule-table shardings and
    long-context training is no longer capped by one chip's param memory
    (reference: Megatron SP inside a TP group,
    modeling_nemo_ppo.py:160-164). Returns a COPY of the config with
    attn_impl='ring' pinned — the caller's config object is left
    untouched so it can be reused with other trainer families."""
    pc = config.parallel
    if pc.sequence <= 1:
        raise ValueError(
            f"{cls_name} requires parallel.sequence > 1 "
            "(use the plain trainer otherwise)"
        )
    if getattr(pc, "pipeline", 1) != 1:
        raise NotImplementedError(
            f"{cls_name} is the single-program SP family; for PP x SP use "
            "the Pipelined* trainers with parallel.sequence > 1 (ring "
            "attention runs inside every pipeline stage)"
        )
    if config.train.seq_length % pc.sequence != 0:
        raise ValueError(
            f"train.seq_length={config.train.seq_length} must divide "
            f"into parallel.sequence={pc.sequence} shards"
        )
    extra = dict(config.model.model_extra_configs or {})
    if extra.get("attn_impl", "ring") != "ring":
        raise ValueError(
            f"{cls_name} uses ring attention; leave "
            "model_extra_configs.attn_impl unset or set it to 'ring'"
        )
    if extra.get("moe_experts", 0):
        raise NotImplementedError(
            "MoE under sequence parallelism is not supported yet (the "
            "load-balancing aux loss cannot cross the shard_map program)"
        )
    extra["attn_impl"] = "ring"
    return config.evolve(model=dict(model_extra_configs=extra))


def warn_if_drop_last_empties_epoch(store, batch_size: int) -> None:
    """Shared by the sequence-parallel trainers' drop_last loaders: a
    store smaller than one batch silently trains ZERO steps."""
    n = len(store)
    if n < batch_size:
        logger.warning(
            f"store holds {n} samples < batch_size {batch_size}; with "
            "drop_last the epoch runs ZERO optimizer steps"
        )


@register_trainer
class SequenceParallelSFTTrainer(SFTTrainer):
    def __init__(self, config: TRLConfig, **kwargs):
        config = validate_sequence_parallel_config(config, type(self).__name__)
        if config.tokenizer.padding_side != "right":
            # the ring position rule derives positions from the shard
            # offset, which is only correct for right-padded batches
            raise ValueError(
                "SequenceParallelSFTTrainer requires tokenizer.padding_side"
                " = 'right' (ring-attention positions assume right padding)"
            )
        super().__init__(config, **kwargs)

    def make_loss_fn(self) -> Callable:
        model = self.model
        mesh = self.runtime.mesh
        ignore_index = DialogStore.IGNORE_INDEX
        batch_spec = P("data", "sequence")
        all_axes = ("data", "sequence")

        def local_ce(params, ids, mask, labels_sh, valid):
            # ring attention binds the "sequence" axis here; positions come
            # from the model's ring rule (shard offset — right-padded data)
            logits, _, _ = model.apply({"params": params}, ids, mask)
            nll = -logprobs_of_labels(logits, jnp.where(valid > 0, labels_sh, 0))
            s = jax.lax.psum(jnp.sum(jnp.where(valid > 0, nll, 0.0)), all_axes)
            n = jax.lax.psum(jnp.sum(valid), all_axes)
            return s, n

        from trlx_tpu.parallel.context import partial_shard_map

        smap = partial_shard_map(
            local_ce,
            mesh,
            in_specs=(P(), batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(P(), P()),
            manual={"data", "sequence"},
            compute_dtype=self.model_cfg.dtype,
        )

        def loss_fn(train_params, frozen_params, batch):
            params = merge_params(train_params, frozen_params)
            ids = batch["input_ids"]
            mask = batch["attention_mask"]
            labels = batch.get("labels")
            if labels is None:
                labels = jnp.where(mask > 0, ids, ignore_index)
            # the CE shift crosses shard boundaries, so it happens on the
            # GLOBAL arrays (XLA inserts the halo exchange) before shard_map
            labels_sh = jnp.concatenate(
                [labels[:, 1:], jnp.full_like(labels[:, :1], ignore_index)], axis=1
            )
            mask_sh = jnp.concatenate(
                [mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1
            )
            valid = ((labels_sh != ignore_index) & (mask_sh > 0)).astype(jnp.int32)
            s, n = smap(params, ids, mask, labels_sh, valid)
            loss = s / jnp.maximum(n, 1)
            return loss, {"loss": loss}

        return loss_fn

    def batch_to_device(self, batch):
        # loaders pad to the longest sequence IN the batch; the shard_map
        # needs the seq dim divisible by parallel.sequence — right-pad up
        # (pads are masked out, so the loss is unchanged)
        import numpy as np

        S = self.config.parallel.sequence
        pad_id = self.tokenizer.pad_token_id

        def pad(x, value):
            x = np.asarray(x)
            rem = (-x.shape[1]) % S
            if rem == 0:
                return x
            return np.pad(x, ((0, 0), (0, rem)), constant_values=value)

        out = dict(batch)
        out["input_ids"] = pad(batch["input_ids"], pad_id)
        out["attention_mask"] = pad(batch["attention_mask"], 0)
        if batch.get("labels") is not None:
            out["labels"] = pad(batch["labels"], DialogStore.IGNORE_INDEX)
        return super().batch_to_device(out)

    def create_train_dataloader(self, seed_offset: int = 0):
        # shard_map needs every batch divisible by data x fsdp — drop the
        # ragged tail instead of replicating it (same policy as the
        # pipelined trainers)
        warn_if_drop_last_empties_epoch(self.store, self.config.train.batch_size)
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, drop_last=True,
            seed=self.config.train.seed + self.iter_count + seed_offset,
        )
