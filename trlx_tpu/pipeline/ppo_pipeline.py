"""PPO rollout storage.

Parity: trlx/pipeline/ppo_pipeline.py — append-only PPORLElement history,
JSON export for rollout logging, and a loader whose collation left-pads
queries and right-pads responses/logprobs/values/rewards so the
query|response seam sits at one fixed column (ppo_collate_fn :14-50).
Padded widths are store-wide maxima (static shapes for XLA).
"""

import json
import os
import time
from typing import Iterable, List

import numpy as np

from trlx_tpu.data import PPORLBatch, PPORLElement
from trlx_tpu.pipeline import BaseRolloutStore, DataLoader


class PPORolloutStorage(BaseRolloutStore):
    def __init__(self, pad_token_id: int, padding_side: str = "left"):
        super().__init__()
        self.pad_token_id = pad_token_id
        self.padding_side = padding_side
        self.history: List[PPORLElement] = []

    def push(self, exps: Iterable[PPORLElement]):
        self.history += list(exps)

    def clear_history(self):
        self.history = []

    def export_history(self, location: str, only_text: bool = True):
        """Dump rollouts as JSON for offline analysis / algorithm
        distillation (reference ppo_pipeline.py:71-89)."""
        assert os.path.exists(location)
        fpath = os.path.join(location, f"epoch-{str(time.time())}.json")

        def exp_to_dict(exp):
            return {k: np.asarray(v).tolist() for k, v in exp.__dict__.items()
                    if v is not None}

        data = [exp_to_dict(exp) for exp in self.history]
        if only_text:
            keys = ["query_tensor", "response_tensor"]
            data = [{k: d[k] for k in keys} for d in data]
        with open(fpath, "w") as f:
            f.write(json.dumps(data, indent=2))

    def __getitem__(self, index: int) -> PPORLElement:
        return self.history[index]

    def __len__(self) -> int:
        return len(self.history)

    def create_loader(
        self,
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        max_query_len: int = 0,
        max_response_len: int = 0,
        max_stat_len: int = 0,
        drop_last: bool = False,
    ) -> DataLoader:
        """Loader with padded-batch collation. Passing the max_*_len
        widths makes batch shapes STATIC across rollout collections (the
        store-wide maxima below vary cycle to cycle, which would recompile
        every jitted consumer — SURVEY.md §7's recompilation-control
        note); widths are raised to the observed maxima if an element
        exceeds them, so correctness never depends on the hints."""
        max_q = max(max(len(e.query_tensor) for e in self.history), max_query_len)
        max_r = max(max(len(e.response_tensor) for e in self.history), max_response_len)
        # seq2seq responses carry a leading decoder_start token, so the
        # per-token stats are one shorter than the response; pad each field
        # to its own store-wide max.
        max_p = max(max(len(e.logprobs) for e in self.history), max_stat_len)
        pad_id = self.pad_token_id
        left_queries = self.padding_side == "left"

        def collate(elems: List[PPORLElement]) -> PPORLBatch:
            # Fused native collation (trlx_tpu/native.py; numpy fallback
            # inside) — the host-side hot path of every optimizer step.
            from trlx_tpu.native import ppo_collate

            queries, responses, logprobs, values, rewards = ppo_collate(
                elems, max_q, max_r, max_p, pad_id, left_queries
            )
            trunk_rows = None
            if all(e.trunk_row is not None for e in elems):
                # the trunk cache stays on the device: a batch names its
                # rows of it and the train step gathers them
                trunk_rows = np.asarray([e.trunk_row for e in elems], dtype=np.int32)
            group_ids = None
            if all(e.group_id is not None for e in elems):
                group_ids = np.asarray([e.group_id for e in elems], dtype=np.int32)
            loss_masks = None
            if all(e.loss_mask is not None for e in elems):
                # right-padded like the per-token stats; pad positions are
                # 0.0 (they are also attention-masked, so this is belt
                # and braces)
                loss_masks = np.zeros((len(elems), max_p), dtype=np.float32)
                for i, e in enumerate(elems):
                    loss_masks[i, : len(e.loss_mask)] = e.loss_mask
            return PPORLBatch(
                query_tensors=queries,
                response_tensors=responses,
                logprobs=logprobs,
                values=values,
                rewards=rewards,
                trunk_rows=trunk_rows,
                group_ids=group_ids,
                loss_masks=loss_masks,
            )

        return DataLoader(
            self.history, batch_size, shuffle=shuffle, collate_fn=collate,
            seed=seed, drop_last=drop_last,
        )
