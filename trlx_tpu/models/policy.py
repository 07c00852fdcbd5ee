"""Policy model wrappers: LM + value head (PPO) and LM + ILQL heads, plus
the param-pytree utilities that realize the reference's freezing/hydra
machinery functionally.

Parity map (reference -> here):
- AutoModelForCausalLMWithValueHead (modeling_ppo.py:266-382)
    -> CausalLMWithValueHead
- AutoModelForCausalLMWithHydraValueHead + per-arch ModelBranch clones
  (modeling_ppo.py:385-1222) -> `ref_param_subtree` + `forward_policy_and_ref`
  (one jit graph computes policy logits, values, and frozen-reference logits;
  no module surgery, no second full forward over the trunk)
- freeze_bottom_causal_layers (utils/modeling.py:22-38)
    -> `trainable_mask` consumed by optax.masked / stop-gradient
- AutoModelForCausalLMWithILQLHeads (modeling_ilql.py:325-412)
    -> CausalLMWithILQLHeads (Q-guided sampling lives in ops/sampling.py
       as a logit-processor hook instead of a duplicated generate loop)
"""

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.heads import ILQLHeads, MLPHead
from trlx_tpu.models.transformer import (
    Block,
    TransformerConfig,
    TransformerLM,
    make_norm,
    train_bias,
)


class ValueBranch(nn.Module):
    """Deeper value head: a trainable clone of the top `n_branch_layers`
    decoder blocks (+ final norm) ending in the scalar MLP head — the
    reference's make_value_branch / ModelBranch-with-value-lm_head
    (modeling_ppo.py:255-263). Fed the trunk activation entering block
    `n_layers - n_branch_layers`; weights start as copies of those trunk
    blocks (build_model clones them after init/load)."""

    cfg: TransformerConfig
    n_branch_layers: int

    def setup(self):
        # honor cfg.remat_blocks like the trunk (this call site never
        # passes the static use_prefix arg, so no static_argnums needed)
        block_cls = nn.remat(Block) if self.cfg.remat_blocks else Block
        first = self.cfg.n_layers - self.n_branch_layers  # the trunk blocks this branch clones
        self.blocks = [
            block_cls(self.cfg, **self.cfg.block_kwargs(first + i), name=f"block_{i}")
            for i in range(self.n_branch_layers)
        ]
        self.ln_f = make_norm(self.cfg, "ln_f")
        self.v_head = MLPHead(1, self.cfg.dtype, self.cfg.param_dtype, name="v_head")

    def __call__(self, h, attn_mask, positions):
        bias = train_bias(self.cfg, attn_mask)
        for blk in self.blocks:
            h, _ = blk(h, bias, positions, attn_mask=attn_mask)
        h = self.ln_f(h)
        return self.v_head(h)[..., 0]


class CausalLMWithValueHead(nn.Module):
    cfg: TransformerConfig
    # > 0: value = a cloned top-k-block branch instead of an MLP off the
    # final hidden state (reference num_value_layers_unfrozen,
    # modeling_ppo.py:117-134)
    num_value_layers: int = 0

    def setup(self):
        self.lm = TransformerLM(self.cfg, name="lm")
        if self.num_value_layers > 0:
            self.value_branch = ValueBranch(
                self.cfg, self.num_value_layers, name="value_branch"
            )
        else:
            self.v_head = MLPHead(1, self.cfg.dtype, self.cfg.param_dtype, name="v_head")

    def __call__(self, tokens, attn_mask, positions=None, split: int = 0):
        """Returns (logits, values, h_split). `split` is the hydra branch
        point (0 = no split; h_split is then the embedding output)."""
        logits, values, caps = self.forward(tokens, attn_mask, positions, capture=(split,))
        return logits, values, caps[split]

    def _values(self, h_final, branch_input=None):
        """How this policy makes values: the MLP head reads per-position
        final hidden states; the deeper value branch attends over the full
        sequence from `branch_input` = (the trunk activation entering its
        first block, attn_mask, positions), which only a full-width forward
        has."""
        if self.num_value_layers == 0:
            return self.v_head(h_final)[..., 0]
        if branch_input is None:
            raise NotImplementedError(
                "per-step values during decode are not supported with a "
                "value branch (values are computed in the scoring pass)"
            )
        return self.value_branch(*branch_input)

    def forward(self, x, attn_mask, positions=None, *, with_value: bool = True,
                stop=None, capture=(), window=None, **blocks):
        """`TransformerLM.forward` with the values of what comes back:
        (logits, values, caps). `with_value=False` runs the LM alone — the
        frozen-reference branch, applied with `{"lm": ref_params}`: from the
        hydra split (`start=split`, x = h_split) or, when every layer
        trains, in full with `use_prompt=False` (the reference likewise gets
        ref logits from the base model without the prompt adapter,
        modeling_ppo.py:324-327). A forward that stops short of the head
        (`stop`) has no values either and returns the LM's own (None, h,
        caps). With the live params and `start=split`, x a cached trunk
        activation, this is the trunk-cache train path: exact when the trunk
        is entirely frozen (split > 0 implies it), and the value branch's
        tap point must lie at or above `start` (the gate guarantees this)."""
        value_split = None
        if with_value and self.num_value_layers > 0 and stop is None:
            if window is not None:
                raise NotImplementedError(
                    "a windowed head with a value branch is unsupported (branch "
                    "blocks attend over the full sequence)"
                )
            value_split = self.cfg.n_layers - self.num_value_layers
            capture = (*capture, value_split)
        logits, h, caps = self.lm.forward(
            x, attn_mask, positions, stop=stop, capture=capture, window=window, **blocks
        )
        if logits is None:
            return None, h, caps
        if not with_value:
            return logits, None, caps
        if value_split is None:
            return logits, self._values(h), caps
        if positions is None:
            # the LM's position rule (ring attention offsets differ
            # from a plain cumsum) — branch blocks must see the same
            # rotary phases as the trunk blocks they were cloned from
            positions = self.lm._default_positions(x, attn_mask)
        return logits, self._values(h, (caps[value_split], attn_mask, positions)), caps

    def decode_step(self, x, cache, token_mask, is_prefill: bool = False,
                    with_value: bool = False, **step):
        """`TransformerLM.decode_step` with the value of each new position
        when `with_value`: (logits, values | None, new_cache[, h_cap])."""
        out = self.lm.decode_step(x, cache, token_mask, is_prefill, **step)
        values = None
        if with_value:
            values = self._values(out[1])
            if values is None:
                raise NotImplementedError(
                    f"{type(self).__name__} has no value head; decode with with_value=False"
                )
        return (out[0], values) + out[2:]


class CausalLMPolicy(CausalLMWithValueHead):
    """Critic-free policy: the LM alone, with NO value head anywhere in the
    param tree (GRPO/RLOO delete the critic, so the tree must too — a
    zero-init v_head would still allocate and train parameters, and the
    tests assert its absence). Subclasses CausalLMWithValueHead so the two
    bodies and `forward_policy_and_ref` work unchanged; the values slot
    holds None, and a per-step value explicitly asked for raises."""

    def setup(self):
        if self.num_value_layers > 0:
            raise ValueError(
                "CausalLMPolicy is critic-free; num_value_layers must be 0"
            )
        self.lm = TransformerLM(self.cfg, name="lm")

    def _values(self, h_final, branch_input=None):
        return None


class CausalLMWithILQLHeads(nn.Module):
    cfg: TransformerConfig
    two_qs: bool = True

    def setup(self):
        self.lm = TransformerLM(self.cfg, name="lm")
        self.ilql_heads = ILQLHeads(
            self.cfg.vocab_size, self.two_qs, self.cfg.dtype, self.cfg.param_dtype, name="ilql_heads"
        )

    def __call__(self, tokens, attn_mask, positions=None, states_ixs=None, actions_ixs=None):
        logits, _, h_final = self.lm(tokens, attn_mask, positions, 0)
        qs, target_qs, vs = self.ilql_heads(h_final, states_ixs, actions_ixs)
        return logits, qs, target_qs, vs, h_final

    def decode_step(self, x, cache, token_mask, is_prefill: bool = False, **step):
        """Cached decode returning (logits, qs, target_qs, vs, cache) at the
        new positions — feeds the beta*(Q-V) logit shift during generation.
        (The engine reads the first and the last: the ILQL advantage shift
        is a training-time sampler feature.)"""
        logits, h, new_cache = self.lm.decode_step(x, cache, token_mask, is_prefill, **step)
        qs, target_qs, vs = self.ilql_heads(h)
        return logits, qs, target_qs, vs, new_cache


# ---------------------------------------------------------------------------
# Param-tree utilities (freezing / hydra reference branch)
# ---------------------------------------------------------------------------


def refuse_over_looped_stack(cfg, what: str) -> None:
    """What assumes that a layer runs once a token is refused by name over a
    looped stack (`TransformerConfig.loop_steps` > 1): every layer runs in
    every pass, so the layers below a split are no prefix of the computation,
    and a cached plane is a (pass, layer)'s."""
    if getattr(cfg, "loop_steps", 1) > 1:
        raise NotImplementedError(
            f"{what} over a looped stack (loop_steps {cfg.loop_steps}: the layers below a split run again in every "
            "later pass, and a cached plane is a (pass, layer)'s) is not supported")


def resolve_split(cfg: TransformerConfig, num_layers_unfrozen: int) -> int:
    """Map the user-facing `num_layers_unfrozen` to the hydra split layer.
    Semantics match the reference's freeze_bottom_causal_layers
    (utils/modeling.py:22-38): -1 = everything trainable (split 0 with a
    full reference copy), 0 = whole LM frozen (heads-only training; split
    n_layers, ref branch is just the frozen unembedding), k>0 = top k
    blocks trainable.

    With LoRA adapters the branch-point trick is invalid (adapters live in
    every block, so hidden states below any split already diverge from the
    base model) — the reference likewise disables the hydra branch under
    peft and gets ref logits from an adapter-disabled pass; split 0 means
    a full reference forward (with zeroed adapters, see
    trlx_tpu/models/lora.py:zero_lora)."""
    if getattr(cfg, "lora_rank", 0) > 0:
        return 0
    if getattr(cfg, "prompt_tokens", 0) > 0 or getattr(cfg, "prefix_tokens", 0) > 0:
        # prompt/prefix adapters change every hidden state from layer 0 on,
        # so the branch-point trick is invalid — ref logits come from a full
        # adapter-free forward (`forward` with use_prompt=False)
        return 0
    if num_layers_unfrozen == -1:
        return 0
    refuse_over_looped_stack(cfg, f"num_layers_unfrozen={num_layers_unfrozen} (the hydra split, the frozen-trunk "
                                  "cache: only -1, every layer trainable, has a meaning)")
    if num_layers_unfrozen == 0:
        return cfg.n_layers
    return max(cfg.n_layers - num_layers_unfrozen, 0)


def ref_param_subtree(params: Dict, cfg: TransformerConfig, split: int) -> Dict:
    """Extract (a copy of) the params the reference branch needs.

    split > 0: blocks[split:], ln_f, and the unembedding (tied embedding or
    lm_head) — everything below the split is frozen and shared live, which
    is exactly the reference's hydra invariant (modeling_ppo.py:400-408).
    split == 0: the whole LM (a standalone frozen reference model).

    Leaves are materialized as NEW buffers (jnp.copy): the reference copy
    must not alias the live params, which get donated into the jitted train
    step and would otherwise be deleted under it.

    With LoRA the base weights are all frozen (never donated), so the
    reference is simply an adapter-disabled view: base leaves aliased,
    adapter leaves zeroed — no full model copy, same memory story as the
    reference's peft adapter-disable."""
    lm = params["lm"]
    if getattr(cfg, "lora_rank", 0) > 0:
        from trlx_tpu.models.lora import zero_lora

        return zero_lora(lm)
    if getattr(cfg, "prompt_tokens", 0) > 0 or getattr(cfg, "prefix_tokens", 0) > 0:
        # base weights are all frozen under prompt/prefix tuning (never
        # donated) — alias them. The adapter leaves are the TRAINABLE lm
        # leaves: the jitted train step donates (deletes) their buffers, so
        # they must be copies even though the ref forward (use_prompt=False)
        # never reads them (flax setup still materializes the params).
        def _copy_adapters(path_keys, leaf):
            parts = [str(getattr(k, "key", k)) for k in path_keys]
            if "soft_prompt" in parts or parts[-1] in ("prefix_k", "prefix_v"):
                return jnp.copy(leaf)
            return leaf

        return jax.tree_util.tree_map_with_path(_copy_adapters, lm)
    if split == 0:
        return jax.tree_util.tree_map(jnp.copy, lm)
    subtree = {}
    for i in range(split, cfg.n_layers):
        subtree[f"block_{i}"] = lm[f"block_{i}"]
    subtree["ln_f"] = lm["ln_f"]
    if cfg.tie_embeddings:
        subtree["embed_tokens"] = lm["embed_tokens"]
    else:
        subtree["lm_head"] = lm["lm_head"]
    return jax.tree_util.tree_map(jnp.copy, subtree)


def trainable_mask(params: Dict, cfg: TransformerConfig, num_layers_unfrozen: int) -> Dict:
    """Bool pytree: True where the param is trainable. Heads are always
    trainable; `num_layers_unfrozen` follows reference semantics
    (-1 all LM params, 0 none, k>0 top-k blocks + final norm)."""
    split = resolve_split(cfg, num_layers_unfrozen)
    lora = getattr(cfg, "lora_rank", 0) > 0
    prompt = getattr(cfg, "prompt_tokens", 0) > 0
    prefix = getattr(cfg, "prefix_tokens", 0) > 0

    def _mask(path_keys, leaf):
        parts = [getattr(k, "key", str(k)) for k in path_keys]
        if "expert_bias" in parts:
            # SparseMoE's selection bias: load balancing moves it, the
            # gradient cannot (it only steers a top-k), so it never trains
            return False
        if parts[0] != "lm":
            return True  # v_head / ilql_heads / any auxiliary head
        if prompt or prefix:
            # prompt/prefix-tuning peft semantics: only the adapter leaves
            # (+ heads above) train; every base LM weight is frozen.
            return "soft_prompt" in parts or str(parts[-1]) in ("prefix_k", "prefix_v")
        if lora:
            # peft semantics: only adapters (+ heads above) train; every
            # base LM weight is frozen regardless of num_layers_unfrozen.
            from trlx_tpu.models.lora import is_lora_path

            return is_lora_path(path_keys)
        if num_layers_unfrozen == -1:
            return True
        if num_layers_unfrozen == 0:
            return False
        name = parts[1]
        if name.startswith("block_"):
            return int(name.split("_")[1]) >= split
        # Reference freeze_bottom_causal_layers freezes embeddings + bottom
        # blocks only; final norm and an untied lm_head stay trainable.
        return name in ("ln_f", "lm_head")

    return jax.tree_util.tree_map_with_path(_mask, params)


def target_q_mask(params: Dict) -> Dict:
    """Bool pytree: True for target-Q-head params (excluded from the
    optimizer; updated only by Polyak sync)."""

    def _mask(path_keys, leaf):
        parts = [getattr(k, "key", str(k)) for k in path_keys]
        return any(str(p).startswith("target_q_head") for p in parts)

    return jax.tree_util.tree_map_with_path(_mask, params)


def apply_trainable_mask(mask: Dict, exclude: Dict) -> Dict:
    """AND a trainable mask with NOT exclude (e.g. drop target-Q heads)."""
    return jax.tree_util.tree_map(lambda m, e: bool(m) and not bool(e), mask, exclude)


def forward_policy_and_ref(
    model: CausalLMWithValueHead,
    params: Dict,
    ref_params: Dict,
    tokens: jnp.ndarray,
    attn_mask: jnp.ndarray,
    split: int,
    positions: Optional[jnp.ndarray] = None,
):
    """Policy logits + values + frozen-reference logits in ONE compiled
    graph. The trunk below `split` runs once; the reference runs only the
    cloned top branch (or, when split == 0, a full pass with the reference
    copy). The reference framework needs two or three separate module
    forwards for this (accelerate_ppo_trainer.py:414-438). The fourth
    result is the state entering block `split`, which both branches start
    from: what a PPO cycle's trunk cache holds (a caller with no use for it
    drops it, and the compiler with it)."""
    logits, values, h_split = model.apply(
        {"params": params}, tokens, attn_mask, positions, split
    )
    ref = {"params": {"lm": ref_params}}
    if split > 0:
        ref_logits, _, _ = model.apply(
            ref, jax.lax.stop_gradient(h_split), attn_mask, positions,
            start=split, with_value=False, method=type(model).forward,
        )
    else:
        ref_logits, _, _ = model.apply(
            ref, tokens, attn_mask, positions,
            use_prompt=False, with_value=False, method=type(model).forward,
        )
    return logits, values, jax.lax.stop_gradient(ref_logits), h_split
