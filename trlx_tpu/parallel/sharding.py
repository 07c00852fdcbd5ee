"""Path-based parameter sharding rules.

The reference encodes TP layouts imperatively in Apex modules
(ColumnParallelLinear / RowParallelLinear, modeling_nemo_ppo.py:67-149) and
ZeRO sharding in DeepSpeed config. Here both are declarative: a rule table
maps parameter-path regexes to PartitionSpecs, and anything unmatched falls
back to a generic FSDP rule (shard the largest divisible dim over "fsdp").
XLA then inserts all of ZeRO's gather/scatter and megatron's all-reduces
automatically.
"""

import re
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def param_path(keypath) -> str:
    """Render a jax tree keypath as a '/'-joined string."""
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclass
class ShardingRules:
    """Ordered (regex, PartitionSpec) rules; first match wins.

    The spec is matched against the *trailing* dims of the param: a spec of
    (a, b) applied to a rank-3 param shards its last two dims — this makes
    the same rule table work with scan-over-layers stacked params (which
    prepend a layer dim)."""

    rules: List[Tuple[str, Sequence[Optional[str]]]] = field(default_factory=list)
    # Axes eligible for the generic largest-dim fallback rule:
    fallback_axis: Optional[str] = "fsdp"

    def spec_for(self, path: str, shape: Sequence[int], mesh: Mesh) -> P:
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for pattern, spec in self.rules:
            if re.search(pattern, path):
                spec = tuple(spec)
                if len(spec) > len(shape):
                    spec = spec[len(spec) - len(shape):]
                full = (None,) * (len(shape) - len(spec)) + tuple(spec)
                # Drop shardings that don't divide the dim (e.g. tiny test
                # models) or whose axis the mesh doesn't have (e.g. "fsdp"
                # on a ("data","pipe","tensor") pipeline mesh).
                checked = []
                for dim, ax in zip(shape, full):
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    ok = ax is not None and all(
                        a in axis_sizes and dim % axis_sizes[a] == 0 for a in axes
                    ) and np.prod([axis_sizes.get(a, 1) for a in axes]) <= dim
                    checked.append(ax if ok else None)
                return P(*checked)
        return self._fallback(shape, axis_sizes)

    def _fallback(self, shape: Sequence[int], axis_sizes) -> P:
        """Generic ZeRO-style rule: shard the largest divisible dim on fsdp."""
        ax = self.fallback_axis
        if ax is None or ax not in axis_sizes or axis_sizes[ax] == 1 or len(shape) == 0:
            return P()
        size = axis_sizes[ax]
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % size == 0 and shape[i] >= size:
                spec = [None] * len(shape)
                spec[i] = ax
                return P(*spec)
        return P()


# Rule table for our GPT-style transformer (trlx_tpu/models/transformer.py).
# Matrices: embeddings [vocab, d]; attn in-proj [d, heads*hd] column-split on
# tensor; attn out-proj [heads*hd, d] row-split; MLP up [d, ffn] column,
# down [ffn, d] row — the same layout apex encodes in Column/RowParallelLinear.
GPT_RULES = ShardingRules(
    rules=[
        (r"embed_tokens/embedding", ("tensor", "fsdp")),
        (r"embed_pos/embedding", (None, "fsdp")),
        (r"(q_proj|k_proj|v_proj|qkv_proj)/kernel", ("fsdp", "tensor")),
        (r"(q_proj|k_proj|v_proj|qkv_proj)/bias", ("tensor",)),
        (r"o_proj/kernel", ("tensor", "fsdp")),
        (r"o_proj/bias", (None,)),
        (r"(up_proj|gate_proj)/kernel", ("fsdp", "tensor")),
        (r"(up_proj|gate_proj)/bias", ("tensor",)),
        (r"down_proj/kernel", ("tensor", "fsdp")),
        (r"down_proj/bias", (None,)),
        (r"lm_head/kernel", ("fsdp", "tensor")),
        # LoRA adapters: A [in, r] row-split like its base kernel's input
        # dim; B [r, out] column-split so the adapter delta lands with the
        # same output sharding as the base projection it adds into.
        (r"\w+_lora_a", ("fsdp", None)),
        (r"\w+_lora_b", (None, "tensor")),
        # prompt/prefix-tuning adapters: tiny — replicate
        (r"soft_prompt", (None, None)),
        (r"prefix_[kv]$", (None, None, None)),
        # MoE: expert dim over `tensor` (expert parallelism); router
        # replicated so every device can gate every token.
        (r"mlp/router/kernel", (None, None)),
        # SparseMoE stacks [fan_in, experts held x fan_out]: the experts a
        # process holds are its own (cfg.moe_local_experts), so a stack is
        # ZeRO-sharded over its fan-in and never by expert (the exchange
        # across chips is not built: ROADMAP)
        (r"mlp/expert_(gate|up|down)/kernel", ("fsdp", None)),
        (r"mlp/expert_bias/bias", (None,)),
        (r"mlp/(up_proj|gate_proj)$", ("tensor", "fsdp", None)),
        (r"mlp/down_proj$", ("tensor", None, "fsdp")),
        (r"mlp/(up_bias|down_bias)$", ("tensor", None)),
        (r"(ln_\w+|norm\w*|layernorm)/(scale|bias)", (None,)),
        # value / Q heads: first layer column-split, output layer replicated
        (r"(v_head|q_head|target_q_head)\w*/dense_in/kernel", ("fsdp", "tensor")),
        (r"(v_head|q_head|target_q_head)\w*/dense_in/bias", ("tensor",)),
        (r"(v_head|q_head|target_q_head)\w*/dense_out/kernel", ("tensor", None)),
        (r"(v_head|q_head|target_q_head)\w*/dense_out/bias", (None,)),
    ]
)


def infer_param_shardings(mesh: Mesh, params, rules: ShardingRules = GPT_RULES):
    """Map a param pytree to NamedShardings via the rule table."""

    def _spec(keypath, leaf):
        path = param_path(keypath)
        shape = np.shape(leaf)
        return NamedSharding(mesh, rules.spec_for(path, shape, mesh))

    return jax.tree_util.tree_map_with_path(_spec, params)


def batch_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """Sharding for activations/batches: batch over (data, fsdp)."""
    return NamedSharding(mesh, P(("data", "fsdp")))
