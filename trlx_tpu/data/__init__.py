"""Typed data containers flowing between pipelines, stores, and trainers.

Parity: trlx/data/{__init__,accelerate_base_datatypes,ppo_types,ilql_types}.py.
Host-side per-sample elements are plain dataclasses of numpy arrays; batched
containers are `flax.struct.dataclass` pytrees so they can cross the jit
boundary directly (the reference's dataclass↔tensor-list flattening for the
NeMo pipeline engine — flatten_dataclass/unflatten_dataclass — is subsumed
by JAX pytree flattening, which is the same idea done by the framework).
"""

from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional

import flax.struct
import numpy as np


@dataclass
class GeneralElement:
    """Universal element to represent all data used in the framework."""

    pass


@dataclass
class RLElement:
    """A single state-action pair."""

    state: str = None
    action: str = None


@dataclass
class PromptElement:
    """Tokenized prompt with its text."""

    text: str
    tokens: np.ndarray


@dataclass
class PromptBatch:
    """Batch of tokenized prompts (reference accelerate_base_datatypes.py:24)."""

    text: List[str]
    tokens: np.ndarray


# ---------------------------------------------------------------------------
# PPO data (reference trlx/data/ppo_types.py)
# ---------------------------------------------------------------------------


@dataclass
class PPORLElement:
    """One rollout: prompt tokens, sampled response tokens, and per-response
    logprobs/values/KL-penalized rewards (reference ppo_types.py:7-34)."""

    query_tensor: np.ndarray  # [query_size]
    response_tensor: np.ndarray  # [response_size]
    logprobs: np.ndarray  # [response_size]
    values: np.ndarray  # [response_size]
    rewards: np.ndarray  # [response_size]
    # this rollout's row in the trainer's cycle-wide trunk cache (the
    # state entering the first trainable block, kept on the device for the
    # cycle); None where the schedule trains from the whole forward
    trunk_row: Optional[int] = None
    # GRPO/RLOO: id of the G-completion prompt group this rollout belongs
    # to — rides the store so group-relative normalization happens per
    # prompt group, not per chunk (None for PPO)
    group_id: Optional[int] = None
    # multi-turn rollouts: f32 [response_size] with 1.0 on policy-authored
    # tokens and 0.0 on environment-authored ones (tool output, game
    # state) — the loss and whitening only see policy tokens. None for
    # single-turn rollouts (everything policy-authored).
    loss_mask: Optional[np.ndarray] = None


@flax.struct.dataclass
class PPORLBatch:
    """Batched rollouts: left-padded queries, right-padded responses
    (reference ppo_types.py:37-63). A pytree — crosses jit directly."""

    query_tensors: Any  # int32 [b, padded_query]
    response_tensors: Any  # int32 [b, padded_response]
    logprobs: Any  # f32 [b, padded_response]
    values: Any  # f32 [b, padded_response]
    rewards: Any  # f32 [b, padded_response]
    # optional int32 [b]: each rollout's row in the cycle's trunk cache, and
    # the cache itself, [rollouts, query + response, d] in the forward's own
    # dtype: ONE device array for the cycle, the same in every batch (the
    # trainer attaches it to a placed batch; a collator never sees it).
    # None (no pytree leaf) where the step runs the whole forward, so every
    # 5-field constructor/scan still works
    trunk_rows: Any = None
    trunk_cache: Any = None
    # optional int32 [b] prompt-group ids (GRPO/RLOO); None for PPO
    group_ids: Any = None
    # optional f32 [b, padded_response] policy-token masks (multi-turn
    # rollouts); None (no pytree leaf) for single-turn training
    loss_masks: Any = None


# ---------------------------------------------------------------------------
# ILQL data (reference trlx/data/ilql_types.py)
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class ILQLElement:
    """Offline RL datapoint: tokens plus state/action index maps
    (reference ilql_types.py:7-48)."""

    input_ids: Any
    attention_mask: Any
    rewards: Any
    states_ixs: Any
    actions_ixs: Any
    dones: Any


@flax.struct.dataclass
class ILQLSeq2SeqElement:
    """Offline RL datapoint for encoder-decoder models
    (reference ilql_types.py:51-97)."""

    input_ids: Any
    attention_mask: Any
    decoder_input_ids: Any
    rewards: Any
    states_ixs: Any
    actions_ixs: Any
    dones: Any


# Batches have the same field layout as elements, with a leading batch dim.
ILQLBatch = ILQLElement
ILQLSeq2SeqBatch = ILQLSeq2SeqElement


def flatten_dataclass(cls: type):
    """dataclass instance -> list of leaves (reference upstream
    trlx/data/ilql_types.py; here it is just pytree flattening)."""
    import jax

    def flatten(obj) -> List:
        return jax.tree_util.tree_leaves(obj)

    return flatten


def unflatten_dataclass(cls: type):
    """list of leaves -> dataclass instance, using the field order."""
    import dataclasses

    fields = [f.name for f in dataclasses.fields(cls)]

    def unflatten(leaves: List):
        return cls(**dict(zip(fields, leaves)))

    return unflatten
