"""TPU-native transformer language model (Flax).

This single module covers the model families the reference wraps via HF
per-architecture classes and re-implemented decoder loops
(GPTModelBranch/OPTModelBranch/LlamaModelBranch/... in
trlx/models/modeling_ppo.py:502-1222): one `TransformerLM` parameterized by
`TransformerConfig` expresses GPT-2-style (learned positions, LayerNorm,
gelu, tied embeddings) and Llama-style (rotary positions, RMSNorm, swiglu,
GQA) decoders. The per-arch "branch" classes collapse to a `start_layer`
argument: `__call__(..., split=k)` also returns the hidden state entering
block k, and `forward(h, ..., start=k)` resumes from there — applied
with a frozen copy of the top-k params this IS the reference's hydra branch
(modeling_ppo.py:385-499), but in the same jit graph as the policy pass.

Decode uses a functional KV cache (static max length, dynamic_update_slice
writes) so the sampling loop is a single compiled lax.while_loop.
"""

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class RopeSpec:
    """Rotary settings of one kind of attention layer, where a model's kinds
    differ (`TransformerConfig.rope_kinds`). `yarn_factor` > 0 is YaRN (Peng
    et al. 2023) as HF `_compute_yarn_parameters` computes it: `rope_tables`."""

    theta: float = 10000.0
    pct: float = 1.0  # share of the head width that rotates
    yarn_factor: float = 0.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: Optional[float] = None  # on cos and sin; None: 0.1 ln(factor) + 1 under YaRN


@dataclass(frozen=True)
class Multipliers:
    """Forward multipliers (Falcon-H1's muP form), applied in the forward
    where the published model applies them and never folded into a weight
    (equal in exact arithmetic, not in bfloat16). All 1 and empty for every
    other family, which then multiplies nothing."""

    embedding: float = 1.0  # on the token embedding
    lm_head: float = 1.0  # on the logits
    attention_in: float = 1.0  # on the normed input of q, k and v
    attention_out: float = 1.0  # on the attention branch's output
    key: float = 1.0  # on k, before it is rotated
    ssm_in: float = 1.0  # on the normed input of the SSM's `in_proj`
    ssm_out: float = 1.0  # on the SSM branch's output
    ssm: Tuple[float, ...] = ()  # on `in_proj`'s z, x, B, C and dt columns
    mlp: Tuple[float, ...] = ()  # on the gate's pre-activation, on the down projection's output

    def __post_init__(self):
        object.__setattr__(self, "ssm", tuple(float(m) for m in self.ssm))
        object.__setattr__(self, "mlp", tuple(float(m) for m in self.mlp))
        if len(self.ssm) not in (0, 5) or len(self.mlp) not in (0, 2):
            raise ValueError(f"multipliers: ssm names {len(self.ssm)} of 5 column groups, mlp {len(self.mlp)} of 2")


@dataclass(frozen=True)
class LatentSpec:
    """The shape of one kind of latent-attention layer, where a model's kinds
    differ (`TransformerConfig.latent_kinds`; `LatentAttention` has the
    equations). `rescale`: each normed latent times sqrt(d_model / its rank).
    `window`: query t attends keys j with 0 <= t - j < window. `index_topk` > 0:
    a learned index (`index_heads` heads of `index_head_dim`, ONE key a token)
    scores the attendable positions and the layer attends to the `index_topk`
    largest (DeepSeek-V3.2's sparse attention); such a layer keeps the index's
    key a token beside the latent."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rescale: bool = False
    window: Optional[int] = None
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    @property
    def width(self) -> int:
        """Values a token caches in the latent plane: the normed latent and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# A layer's operator. What a kind keeps between steps (planes a token, arrays a
# slot, or both) is `TransformerConfig.kind_keeps`'s to say, and every predicate
# about caches asks that: a kind is in no list of "attention" or "state" kinds.
LAYER_KINDS = ("attention", "full_attention", "sliding_attention", "latent_attention", "conv", "linear_attention",
               "ssm_attention", "sliding_latent_attention", "sparse_latent_attention")
# The routers of `SparseMoE` (`TransformerConfig.moe_router`; "softmax" is `MoEMLP`'s).
SPARSE_ROUTERS = ("sigmoid", "topk_softmax")


@dataclass(frozen=True)
class LayerKeeps:
    """What one layer keeps between steps, the one description every cache
    is built from (`init_kv_cache`, `init_paged_kv_arena`, the engine's pool,
    `observability.hbm`): `token` planes, (name, shape a token), in the
    cache's type; `slot` arrays, (name, shape a row, type or None for the
    cache's), which a row owns whole and a step overwrites. `passes`: how
    often a token's planes are kept, once a pass of a looped stack
    (`TransformerConfig.loop_steps`: the cache's unit is (pass, layer))."""

    token: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    slot: Tuple[Tuple[str, Tuple[int, ...], Any], ...] = ()
    passes: int = 1

    @property
    def slot_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _, _ in self.slot)

    def slot_arrays(self, rows: int, cache_dtype) -> Dict[str, jnp.ndarray]:
        return {name: jnp.zeros((rows, *shape), dtype or cache_dtype) for name, shape, dtype in self.slot}

    def slot_bytes(self, cache_dtype) -> int:
        """Bytes a row holds here."""
        return sum(int(np.prod(shape)) * jnp.dtype(dtype or cache_dtype).itemsize for _, shape, dtype in self.slot)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    n_kv_heads: Optional[int] = None  # GQA/MQA; None = n_heads
    max_seq_len: int = 2048
    pos_embed: str = "learned"  # "learned" | "rope" | "none"
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    activation: str = "gelu"  # "gelu" (tanh approx) | "gelu_exact" | "silu" | "relu"
    glu: bool = False  # gated MLP (llama-style)
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    layer_norm_epsilon: float = 1e-5
    use_bias: bool = True  # dense biases (gpt2 yes, llama no)
    # Per-family structure knobs covering the reference's per-arch branch
    # classes (modeling_ppo.py:502-1222) in one parameterized module:
    parallel_residual: bool = False  # h + attn(ln(h)) + mlp(·) (GPT-NeoX/GPT-J)
    shared_ln: bool = False  # parallel-residual MLP reads ln_attn's output (GPT-J)
    rotary_pct: float = 1.0  # fraction of head_dim that rotates (pythia 0.25, GPT-J 64/hd)
    alibi: bool = False  # ALiBi key-position bias instead of position embeddings (Bloom)
    pos_offset: int = 0  # learned-position lookup offset (OPT uses 2)
    embed_ln: bool = False  # LayerNorm right after token embedding (Bloom)
    attn_bias: Optional[bool] = None  # q/k/v/o bias override; None = use_bias (GPT-J: False)
    lm_head_bias: bool = False  # untied lm_head carries a bias (GPT-J)
    sliding_window: Optional[int] = None  # banded causal attention (Mistral)
    # Mixture-of-experts MLP (BEYOND the reference, whose §2.7 EP row is
    # empty): 0 = dense MLP. Experts are a leading param dim sharded over
    # the `tensor` mesh axis (expert parallelism); routing is top-k
    # token-choice with renormalized gates. Dispatch is dense (every
    # expert computes every token, non-selected contributions masked) —
    # simple, static-shaped, and collective-free; at large expert counts
    # a sorted all-to-all dispatch would trade that simplicity for FLOPs.
    moe_experts: int = 0
    moe_top_k: int = 2
    # Switch-style load-balancing coefficient: aux = coef * E * sum_e
    # (fraction routed to e) * (mean router prob of e), sown by MoEMLP and
    # added to the training loss (plain top-k routing collapses onto a
    # few experts without it).
    moe_aux_coef: float = 0.01
    # Layers of more than one kind (LFM2-style hybrids). `layer_types` names
    # each layer's operator, "attention", "conv" (the gated short
    # convolution, `ShortConv`) or "linear_attention" (`KimiDeltaAttention`,
    # below); empty = attention everywhere, and then none
    # of the fields below is read. `moe_dense_layers` leading layers keep
    # the dense MLP (width d_ff) in a model whose other layers hold experts
    # (width moe_d_ff, d_ff when None).
    layer_types: Tuple[str, ...] = ()
    # Attention layers of more than one kind (Laguna-style): `layer_types`
    # may name "full_attention" and "sliding_attention" instead of
    # "attention". Then `sliding_window` bands the sliding layers only,
    # `layer_heads` gives each layer its own number of query heads (K/V heads
    # and the head width are the model's), and `rope_kinds` maps a kind to
    # its rotary settings (a kind it leaves out rotates by `rope_theta` and
    # `rotary_pct`). None of this is read for a model that names no kinds.
    layer_heads: Tuple[int, ...] = ()
    rope_kinds: Tuple[Tuple[str, RopeSpec], ...] = ()
    # A stated head width, where it is not d_model // n_heads.
    head_width: Optional[int] = None
    # "per_head": a = sigmoid(x W_g)_h * a_h on the attention output before
    # o_proj, W_g [d_model, heads] (`gate_proj`). "elementwise": a value of
    # its own for every channel of every head, W_g [d_model, heads * head_dim]
    # (K/V attention layers; a KDA layer's output gate is `kda_gate_rank`'s).
    attn_gate: str = "none"
    conv_kernel: int = 3  # taps of the short convolution
    qk_norm: bool = False  # RMSNorm over the head width on q and k, before rotary
    # The fused sampler's prefill (`decode_step(is_prefill=True)`, each batch
    # prefilled once into an empty cache) attends within the prompt through
    # the fused kernel (attn_impl "flash") while its K/V go into the cache,
    # instead of scoring the prompt against the whole cache: [rows, heads,
    # prompt, prompt + new] float32 scores are 7.5 GB at 64 x 32 x 896 x 1024,
    # which the chip cannot hold (and its compiler does not survive, PR 29).
    # Off for the families that were there, whose programs stay as they are.
    flash_prefill: bool = False
    moe_dense_layers: int = 0
    moe_d_ff: Optional[int] = None
    # "softmax": MoEMLP below (renormalized softmax gates, auxiliary loss,
    # every expert computes every token). The two of `SPARSE_ROUTERS` are
    # `SparseMoE`'s (grouped dispatch over the experts held here, no auxiliary
    # loss): "sigmoid", float32 sigmoid scores, top-k over scores + a selection
    # bias that no gradient moves; "topk_softmax", top-k over float32 logits and
    # a softmax over the chosen, no bias (SmallThinker's).
    moe_router: str = "softmax"
    # `SparseMoE` only, what the router reads: "ffn_input", the normed input
    # of the feed-forward it routes (every family but one); "block_input", the
    # block's own input, un-normed, before its attention (SmallThinker: the
    # routing is known while the attention runs). `Block` is the one place
    # that hands the router its input.
    moe_route_on: str = "ffn_input"
    # Expert parallelism seen from one chip: of the model's `moe_experts`
    # this process holds `moe_local_experts` (0 = all), the contiguous range
    # starting at `moe_local_offset`. The router still scores all of them;
    # the layer computes only what its own experts add (ops/moe.py).
    moe_local_experts: int = 0
    moe_local_offset: int = 0
    # `SparseMoE` only: y = moe_routed_scale * (the held experts' part) +
    # S(x), S one shared expert of width `moe_shared_d_ff` (0 = none) that
    # every token meets, computed whole on every chip.
    moe_shared_d_ff: int = 0
    moe_routed_scale: float = 1.0
    # `SparseMoE` only: the tokens dispatched at once (0: all of a call's). The
    # dispatch holds every token once for each of its `moe_top_k` experts, held
    # here or not: 24,576 positions of 5,120 x 8 are 1.9 GB a copy, so a model
    # that prefills prompts that long routes them a block at a time.
    moe_token_block: int = 0
    # Latent attention (MLA, DeepSeek-V2; `layer_types` kind
    # "latent_attention", `LatentAttention` below): queries through a
    # low-rank pair of `q_lora_rank`, keys and values through one shared
    # latent of `kv_lora_rank` plus `qk_rope_head_dim` rotary dimensions that
    # all heads share; a query/key head is `qk_nope_head_dim +
    # qk_rope_head_dim` wide, a value head `v_head_dim`. Such a layer caches
    # ONE plane of `latent_width` values a token, not K and V by head:
    # `cache_planes(i)` says what layer i caches, and `kv_heads` / `head_dim`
    # describe the K/V layers only.
    # `q_lora_rank` 0 or None: one full-rank `q_proj` in place of the pair.
    q_lora_rank: Optional[int] = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Latent layers of more than one shape in one stack (dots3-note's): a kind
    # of `layer_types` ("sliding_latent_attention": banded; "sparse_latent_attention":
    # a learned index chooses the positions attended to) mapped to its own
    # `LatentSpec`, as `rope_kinds` maps a kind to its rotary settings. Empty
    # for every other family, whose "latent_attention" layers read the five
    # fields above.
    latent_kinds: Tuple[Tuple[str, LatentSpec], ...] = ()
    # a = x + N(Attn(N(x))); y = a + N(FFN(N(a))): a norm after the operator
    # and after the feed-forward too (`ln_post_attn`, `ln_post_mlp`), before
    # each residual add (openPangu's `sandwich_norm`)
    sandwich_norm: bool = False
    # Multi-token prediction blocks behind the stack (DeepSeek-V3's form,
    # `MTPBlock`): block k reads the state under the final norm and the
    # embedding of the next token and gives logits for the token after it.
    # `forward(..., mtp=True)` runs them; no cached step does.
    mtp_layers: int = 0
    # Group-limited routing (`SparseMoE`, DeepSeek-V3's `noaux_tc`): the
    # experts lie in `moe_n_group` equal groups, a group scores the sum of its
    # two largest biased scores, and a token chooses its `moe_top_k` among the
    # experts of its `moe_topk_group` best groups. 0: no groups.
    moe_n_group: int = 0
    moe_topk_group: int = 0
    # Kimi delta attention (`layer_types` kind "linear_attention",
    # `KimiDeltaAttention`): `n_heads` heads of `head_dim` for keys and values
    # alike, causal depthwise convolutions of `conv_kernel` taps on q, k and
    # v, a log-decay a key channel bounded below by `kda_lower_bound`.
    # Such a layer keeps a matrix a head and the convolutions' last inputs a
    # ROW, nothing a token (`layer_keeps`); `kda_state_dtype` is the matrix's.
    # The published forms differ in three places, each a field:
    # `kda_decay` "bounded": g = kda_lower_bound * sigmoid(exp(a_h) f), in
    # [kda_lower_bound, 0]; "softplus": g = -exp(a_h) softplus(f), unbounded
    # (Kimi Linear's own). `kda_gate_rank` 0: f = x W_f + b_dt with W_f full
    # rank and a sigmoid output gate a HEAD (`gate_proj`); r > 0: f through a
    # low-rank pair (`f_a_proj` [d, r], `f_b_proj` [r, H d]) and an
    # ELEMENTWISE output gate through another (`g_a_proj`, `g_b_proj`, with a
    # bias `g_bias`). `kda_beta_max`: beta = kda_beta_max * sigmoid(x W_b); 2
    # admits a negative eigenvalue of the transition I - beta k k^T.
    kda_lower_bound: float = -5.0
    kda_state_dtype: Any = jnp.float32
    kda_decay: str = "bounded"
    kda_gate_rank: int = 0
    kda_beta_max: float = 1.0
    # A Mamba-2 mixer beside attention in ONE block (`layer_types` kind
    # "ssm_attention", Falcon-H1: `Mamba2Mixer` and `Attention` read the same
    # normed input and their outputs are summed into one residual add).
    # `ssm_heads` heads of `ssm_head_dim` (together d_ssm), a state of
    # `ssm_state` a head channel, B and C in `ssm_groups` groups, a causal
    # depthwise convolution of `ssm_conv_kernel` taps with a bias over x, B and
    # C, chunks of `ssm_chunk` positions in the chunked form. Such a layer
    # keeps K and V by head a TOKEN and a matrix a head (`ssm_state_dtype`)
    # and the convolution's last inputs a SLOT (`kind_keeps`).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_state_dtype: Any = jnp.float32
    multipliers: Multipliers = Multipliers()
    # A looped stack (Ouro's LoopLM, `total_ut_steps`): the SAME `n_layers`
    # blocks run `loop_steps` times a token, `ln_f` after every pass and its
    # output fed on as the next pass's input (`TransformerLM.run_passes`: one
    # traced body). Every (pass, layer) keeps keys and values of its own
    # (`LayerKeeps.passes`); a token's position is the same in every pass.
    # `loop_gate`: an exit gate leaf (`exit_gate`, d_model -> 1 with a bias) read
    # on every pass's normed output (`exit_distribution`). `loop_exit_threshold`
    # is the published `early_exit_threshold`: at 1.0 every row runs every pass
    # and the logits are the last pass's; anything lower is refused.
    loop_steps: int = 1
    loop_gate: bool = False
    loop_exit_threshold: float = 1.0

    def __post_init__(self):
        if self.q_lora_rank is None:
            object.__setattr__(self, "q_lora_rank", 0)
        if not isinstance(self.multipliers, Multipliers):
            object.__setattr__(self, "multipliers", Multipliers(**dict(self.multipliers or {})))
        if self.layer_types:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, n_layers is {self.n_layers}"
                )
            unknown = set(self.layer_types) - set(LAYER_KINDS)
            if unknown:
                raise ValueError(f"unknown layer_types {sorted(unknown)}")
        if self.layer_heads:
            object.__setattr__(self, "layer_heads", tuple(int(n) for n in self.layer_heads))
            if len(self.layer_heads) != self.n_layers:
                raise ValueError(
                    f"layer_heads names {len(self.layer_heads)} layers, n_layers is {self.n_layers}"
                )
            if any(n % self.kv_heads for n in self.layer_heads):
                raise ValueError(f"layer_heads {self.layer_heads} not multiples of {self.kv_heads} K/V heads")
        if self.rope_kinds:
            object.__setattr__(self, "rope_kinds", tuple(
                (k, r if isinstance(r, RopeSpec) else RopeSpec(**r)) for k, r in self.rope_kinds))
        if self.attn_gate not in ("none", "per_head", "elementwise"):
            raise ValueError(f"attn_gate must be 'none', 'per_head' or 'elementwise', got {self.attn_gate!r}")
        if self.kda_decay not in ("bounded", "softplus"):
            raise ValueError(f"kda_decay must be 'bounded' or 'softplus', got {self.kda_decay!r}")
        if self.kda_gate_rank < 0 or not 0.0 < self.kda_beta_max <= 2.0:
            raise ValueError(f"kda_gate_rank {self.kda_gate_rank} must be >= 0 and kda_beta_max "
                             f"{self.kda_beta_max} in (0, 2]")
        if self.latent_kinds:
            object.__setattr__(self, "latent_kinds", tuple(
                (k, s if isinstance(s, LatentSpec) else LatentSpec(**s)) for k, s in self.latent_kinds))
        for kind, spec in ((k, self.latent_of(k)) for k in dict.fromkeys(self.layer_types)):
            if spec is None:
                if kind in ("sliding_latent_attention", "sparse_latent_attention"):
                    raise ValueError(f"{kind} layers need their LatentSpec in latent_kinds")
                continue
            sizes = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
            missing = [n for n in sizes if getattr(spec, n) <= 0]
            if missing or spec.q_lora_rank < 0:
                raise ValueError(f"{kind} layers need {missing or ['q_lora_rank']} "
                                 "(q_lora_rank 0 or None is a full-rank query, never a negative rank)")
            if spec.qk_rope_head_dim % 2:
                raise ValueError(f"qk_rope_head_dim {spec.qk_rope_head_dim} must be even")
            if (kind == "sliding_latent_attention") != (spec.window is not None) \
                    or (kind == "sparse_latent_attention") != (spec.index_topk > 0):
                raise ValueError(f"{kind}: a sliding latent layer names a window and no index, a sparse one an "
                                 f"index (index_topk > 0) and no window; got {spec}")
            if spec.index_topk and (min(spec.index_heads, spec.index_head_dim) <= 0 or spec.index_head_dim % 4
                                    or not spec.q_lora_rank):
                raise ValueError(f"{kind}: the index reads the query's latent (q_lora_rank > 0) through "
                                 f"index_heads > 0 heads of index_head_dim (a multiple of 4: half of it rotates)")
        if self.has_latent_layers:
            unsupported = [what for on, what in (
                (self.pos_embed != "rope", f"pos_embed={self.pos_embed!r}"), (self.alibi, "alibi"),
                (self.lora_rank > 0, "lora_rank"), (self.prefix_tokens > 0, "prefix_tokens"),
                (self.sliding_window is not None, "sliding_window"),
                (self.attn_impl in ("ring", "blockwise"), f"attn_impl={self.attn_impl!r}"),
                (self.attn_gate == "elementwise", "attn_gate='elementwise'"),
            ) if on]
            if unsupported:
                kinds = " / ".join(k for k in dict.fromkeys(self.layer_types) if self.latent_of(k) is not None)
                raise NotImplementedError(f"{kinds} layers with {', '.join(unsupported)} are not supported")
        if self.has_linear_layers:
            unsupported = [what for on, what in (
                (self.lora_rank > 0, "lora_rank"), (self.prefix_tokens > 0, "prefix_tokens"),
                (self.prompt_tokens > 0, "prompt_tokens"), (self.attn_impl == "ring", "attn_impl='ring'"),
            ) if on]
            if unsupported:
                raise NotImplementedError(f"linear_attention layers with {', '.join(unsupported)} are not supported")
        if self.has_ssm_layers:
            if min(self.ssm_heads, self.ssm_head_dim, self.ssm_state, self.ssm_groups, self.ssm_chunk) <= 0 \
                    or self.ssm_heads % self.ssm_groups or self.ssm_conv_kernel < 2:
                raise ValueError(
                    f"ssm_attention layers need ssm_heads {self.ssm_heads}, ssm_head_dim {self.ssm_head_dim}, "
                    f"ssm_state {self.ssm_state} and ssm_chunk {self.ssm_chunk} > 0, heads in whole groups of "
                    f"ssm_groups {self.ssm_groups}, and ssm_conv_kernel {self.ssm_conv_kernel} >= 2")
            unsupported = [what for on, what in (
                (self.lora_rank > 0, "lora_rank"), (self.prefix_tokens > 0, "prefix_tokens"),
                (self.prompt_tokens > 0, "prompt_tokens"), (self.attn_impl == "ring", "attn_impl='ring'"),
            ) if on]
            if unsupported:
                raise NotImplementedError(f"ssm_attention layers with {', '.join(unsupported)} are not supported")
        if bool(self.moe_n_group) != bool(self.moe_topk_group) or self.moe_n_group < 0:
            raise ValueError(f"moe_n_group {self.moe_n_group} and moe_topk_group {self.moe_topk_group} go together")
        if self.moe_n_group:
            if self.moe_router != "sigmoid":
                raise NotImplementedError("group-limited routing (moe_n_group) scores groups by biased sigmoid "
                                          "scores: it needs moe_router='sigmoid'")
            if self.moe_experts % self.moe_n_group or self.moe_topk_group > self.moe_n_group \
                    or self.moe_top_k > self.moe_topk_group * (self.moe_experts // self.moe_n_group):
                raise ValueError(
                    f"{self.moe_experts} experts in {self.moe_n_group} groups, {self.moe_topk_group} groups and "
                    f"{self.moe_top_k} experts a token do not fit")
        if self.sandwich_norm and self.parallel_residual:
            raise NotImplementedError("sandwich_norm under parallel_residual is not supported")
        if self.loop_steps < 1 or (self.loop_gate and self.loop_steps == 1):
            raise ValueError(f"loop_steps {self.loop_steps} must be >= 1, and loop_gate needs loop_steps > 1")
        if self.loop_steps > 1:
            if self.loop_exit_threshold < 1.0:
                raise NotImplementedError(
                    f"a looped stack with loop_exit_threshold {self.loop_exit_threshold} < 1 (early_exit_threshold: "
                    "a row that leaves before the last pass still owes the later passes' keys and values to the "
                    "tokens after it, and the published config does not say what they are) is not supported")
            unsupported = [what for on, what in (
                (self.slot_state_kinds or self.has_latent_layers, "layers that keep anything but K and V by head"),
                (self.moe_experts > 0, "moe_experts"), (self.mtp_layers > 0, "mtp_layers"),
                (self.prompt_tokens > 0, "prompt_tokens"), (self.prefix_tokens > 0, "prefix_tokens"),
                (self.attn_impl == "ring", "attn_impl='ring'"),
            ) if on]
            if unsupported:
                raise NotImplementedError(f"a looped stack (loop_steps > 1) with {', '.join(unsupported)} "
                                          "is not supported")
        if self.moe_router != "softmax" and self.moe_router not in SPARSE_ROUTERS:
            raise ValueError(f"moe_router must be 'softmax' or one of {SPARSE_ROUTERS}, got {self.moe_router!r}")
        if self.moe_route_on not in ("ffn_input", "block_input"):
            raise ValueError(f"moe_route_on must be 'ffn_input' or 'block_input', got {self.moe_route_on!r}")
        for on, what in (
                (self.moe_shared_d_ff or self.moe_routed_scale != 1.0, "a shared expert and a routed scale"),
                (self.moe_local_experts, "moe_local_experts (one chip's share of the experts)"),
                (self.moe_route_on != "ffn_input", f"moe_route_on={self.moe_route_on!r}")):
            if on and self.moe_router not in SPARSE_ROUTERS:
                raise NotImplementedError(
                    f"{what}: only the grouped dispatch of `SparseMoE` has it (moe_router one of {SPARSE_ROUTERS}, "
                    f"not {self.moe_router!r}: `MoEMLP` multiplies every token by every expert)")
        if self.moe_local_offset + self.experts_held > max(self.moe_experts, 0) and self.moe_experts:
            raise ValueError(
                f"experts [{self.moe_local_offset}, {self.moe_local_offset + self.experts_held}) "
                f"are not among the model's {self.moe_experts}"
            )
        if self.moe_experts > 0 and self.lora_rank > 0:
            raise NotImplementedError(
                "LoRA adapters on MoE expert weights are not supported; "
                "set moe_experts=0 or lora_rank=0"
            )
        if self.prefix_tokens > 0 and self.attn_impl != "xla":
            raise NotImplementedError(
                "prefix tuning needs the dense-bias attention path; set "
                "attn_impl='xla'"
            )
    # HF family tag recorded at conversion time so save_pretrained exports
    # the exact source layout (structure-based inference is ambiguous, e.g.
    # non-MQA GPTBigCode vs GPT-2); None = infer from structure.
    hf_family: Optional[str] = None
    # LoRA adapters (the reference's peft integration, modeling_base.py
    # from_pretrained + test_peft.py): rank 0 = disabled. Adapter params
    # live beside their base kernels as `<name>_lora_a` / `<name>_lora_b`
    # leaves — a separate trainable subtree, with the base weights frozen.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q_proj", "v_proj")
    # Prompt tuning (the reference's peft PROMPT_TUNING path,
    # modeling_ppo.py:314-327 prompt-adapter handling): > 0 prepends that
    # many trainable soft-prompt embeddings to every sequence; the base
    # weights freeze and reference logits use a prompt-free forward.
    prompt_tokens: int = 0
    # Prefix tuning (peft PREFIX_TUNING — the reference's prefix bypass,
    # modeling_ppo.py:314-327): > 0 gives every attention layer that many
    # trainable key/value prefix slots, visible to all queries; base
    # weights freeze and reference logits use a prefix-free forward.
    prefix_tokens: int = 0
    dtype: Any = jnp.bfloat16  # activation/compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32
    # Activation rematerialization per transformer block: the backward
    # recomputes each block's internals instead of banking them, so
    # activation memory drops from O(L · t · d_ff) to O(L · t · d) at
    # ~1/3 extra FLOPs (the reference's NeMo activations_checkpoint_method
    # toggles, modeling_nemo_ppo.py:788-836). Honored by TransformerLM's
    # training forward AND the GPipe stage scan — under PP this is what
    # keeps banked microbatch activations from scaling with d_ff.
    remat_blocks: bool = False
    # "xla" (einsum softmax, short seqs), "flash" (Pallas fused kernel /
    # blockwise scan, trlx_tpu/ops/attention.py), "ring" (context-parallel
    # over the "sequence" mesh axis, trlx_tpu/ops/ring_attention.py —
    # requires running inside shard_map with that axis)
    attn_impl: str = "xla"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def rotary_dim(self) -> int:
        rd = int(self.head_dim * self.rotary_pct)
        return rd - (rd % 2)

    @property
    def experts_held(self) -> int:
        return self.moe_local_experts or self.moe_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def has_conv_layers(self) -> bool:
        return "conv" in self.layer_types

    def latent_of(self, kind: Optional[str]) -> Optional[LatentSpec]:
        """The shape of a latent-attention layer of `kind`: its own
        (`latent_kinds`), the model's five fields for "latent_attention", None
        for a kind that is no latent attention."""
        spec = dict(self.latent_kinds).get(kind)
        if spec is None and kind == "latent_attention":
            spec = LatentSpec(self.n_heads, self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                              self.qk_rope_head_dim, self.v_head_dim)
        return spec

    @property
    def has_latent_layers(self) -> bool:
        return any(self.latent_of(kind) is not None for kind in set(self.layer_types))

    @property
    def has_index_layers(self) -> bool:
        """Whether some layer chooses the positions it attends to (`LatentSpec.index_topk`)."""
        return any(spec.index_topk > 0 for kind, spec in self.latent_kinds if kind in self.layer_types)

    @property
    def has_linear_layers(self) -> bool:
        return "linear_attention" in self.layer_types

    @property
    def has_ssm_layers(self) -> bool:
        return "ssm_attention" in self.layer_types

    @property
    def slot_state_kinds(self) -> Tuple[str, ...]:
        """The kinds of layer that keep arrays a slot (`LayerKeeps.slot`), in
        order of first use."""
        return tuple(k for k in dict.fromkeys(self.layer_types) if self.kind_keeps(k).slot)

    @property
    def has_slot_state(self) -> bool:
        """Whether some layer keeps a state a row (`LayerKeeps.slot`): what a
        block table cannot share, a mask bit cannot roll back and a pool must
        hold beside its arena."""
        return bool(self.slot_state_kinds)

    @property
    def kda_width(self) -> int:
        """Channels of the three short convolutions of a KDA layer together."""
        return 3 * self.n_heads * self.head_dim

    @property
    def ssm_width(self) -> int:
        """Channels of an SSM mixer's one short convolution: x, B and C side by side."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_chunk(self) -> int:
        """Positions a step of the chunked recurrence of a prompt's prefill takes
        (a KDA layer's, an SSM mixer's), 0 where no layer runs one."""
        from trlx_tpu.ops.linear_attention import CHUNK

        return self.ssm_chunk if self.has_ssm_layers else CHUNK if self.has_linear_layers else 0

    @property
    def latent_width(self) -> int:
        """Values a "latent_attention" layer caches a token (the model's own
        five fields; a kind of `latent_kinds` has its `LatentSpec.width`)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def kind_keeps(self, op: str) -> LayerKeeps:
        """What a layer of kind `op` keeps: K and V by head a token for an
        attention layer, the one latent plane a token for a latent one (of
        its own kind's width) and, where an index chooses its positions, the
        index's key a token beside it; the
        last `conv_kernel - 1` inputs a row for a `conv` layer; a matrix a head
        (`kda_state_dtype`) and the three convolutions' last inputs a row for
        a `linear_attention` one; for an `ssm_attention` one BOTH: K and V by
        head a token, and a matrix a head (`ssm_state_dtype`, state width
        before head width: `ops/ssd.py`'s layout) and the convolution's last
        inputs a row."""
        if op == "conv":
            return LayerKeeps(slot=(("conv", (self.conv_kernel - 1, self.d_model), None),))
        if op == "linear_attention":
            return LayerKeeps(slot=(
                ("state", (self.n_heads, self.head_dim, self.head_dim), self.kda_state_dtype),
                ("tails", (self.conv_kernel - 1, self.kda_width), None)))
        latent = self.latent_of(op)
        if latent is not None:
            index = (("index_k", (latent.index_head_dim,)),) if latent.index_topk else ()
            return LayerKeeps(token=(("latent", (latent.width,)),) + index)
        kv = (("k", (self.kv_heads, self.head_dim)), ("v", (self.kv_heads, self.head_dim)))
        if op == "ssm_attention":
            return LayerKeeps(token=kv, slot=(
                ("state", (self.ssm_heads, self.ssm_state, self.ssm_head_dim), self.ssm_state_dtype),
                ("tails", (self.ssm_conv_kernel - 1, self.ssm_width), None)))
        return LayerKeeps(token=kv)

    def layer_keeps(self, i: int) -> LayerKeeps:
        keeps = self.kind_keeps(self.layer_op(i))
        return keeps if self.loop_steps == 1 else replace(keeps, passes=self.loop_steps)

    def cache_planes(self, i: int) -> Tuple[int, ...]:
        """What a token caches in layer i, one width a plane (`layer_keeps`), the planes of every pass."""
        keeps = self.layer_keeps(i)
        return tuple(int(np.prod(shape)) for _, shape in keeps.token) * keeps.passes

    def slot_state_bytes_per_slot(self, cache_dtype) -> int:
        """Bytes of slot state one row holds over all layers."""
        return sum(self.layer_keeps(i).slot_bytes(cache_dtype) for i in range(self.n_layers))

    @property
    def cached_values_per_token(self) -> int:
        return sum(sum(self.cache_planes(i)) for i in range(self.n_layers))

    @property
    def sows_moe_aux(self) -> bool:
        """Whether a training forward sows an auxiliary loss that the
        trainers must collect (which forces their full-width forward)."""
        return self.moe_experts > 0 and self.moe_router == "softmax" and self.moe_aux_coef > 0

    @property
    def has_sparse_moe(self) -> bool:
        """Whether some layer is a `SparseMoE` (which sows dispatch counters),
        under either of its routers."""
        return self.moe_experts > 0 and self.moe_router in SPARSE_ROUTERS

    @property
    def blocks_read_token_mask(self) -> bool:
        """Whether a cached step must hand its blocks the validity of the
        incoming tokens: a convolution state that a masked step may not
        move, experts that masked tokens are not dispatched to."""
        return self.has_slot_state or self.has_sparse_moe

    def layer_op(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "attention"

    @property
    def attention_kinds(self) -> Tuple[str, ...]:
        """The kinds of layer that keep planes a token (`LayerKeeps.token`: what
        an attention reads, so what needs a bias) where the model names one
        beside plain "attention", in order of first use; () for every family
        whose attention layers are all alike, which then builds one bias."""
        kinds = tuple(k for k in dict.fromkeys(self.layer_types) if self.kind_keeps(k).token)
        return kinds if set(kinds) - {"attention"} else ()

    def window_of(self, kind: Optional[str]) -> Optional[int]:
        """The band of a layer of `kind`: a latent kind's own
        (`LatentSpec.window`); none on a "full_attention" layer and on a kind
        that keeps nothing a token; `sliding_window` on every other layer
        that keeps K and V by head (a model that names no kinds: all)."""
        latent = self.latent_of(kind)
        if latent is not None:
            return latent.window
        if kind == "full_attention" or (kind is not None and not self.kind_keeps(kind).token):
            return None
        return self.sliding_window

    def rope_of(self, kind: Optional[str]) -> Optional[RopeSpec]:
        return dict(self.rope_kinds).get(kind)

    def block_kwargs(self, i: int) -> Dict[str, Any]:
        """What `Block` is told of layer i beside the config."""
        return dict(op_kind=self.layer_op(i), ffn_kind=self.layer_ffn(i),
                    n_heads=self.layer_heads[i] if self.layer_heads else None)

    def layer_ffn(self, i: int) -> str:
        """"dense" | "moe" (MoEMLP) | "sparse_moe" (SparseMoE) for layer i."""
        if self.moe_experts <= 0 or i < self.moe_dense_layers:
            return "dense"
        return "sparse_moe" if self.has_sparse_moe else "moe"


def activation_fn(cfg: TransformerConfig):
    """cfg.activation -> callable (single source for MLP/MoEMLP/seq2seq)."""
    return {
        "silu": jax.nn.silu,
        "relu": jax.nn.relu,
        "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    }.get(cfg.activation, jax.nn.gelu)


def make_norm(cfg: TransformerConfig, name: str, **module_kw):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name, **module_kw)
    return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name, **module_kw)


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def rope_tables(spec: RopeSpec, head_dim: int) -> Tuple[np.ndarray, float, int]:
    """(inv_freq [rd/2], the factor on cos and sin, rd) of a `RopeSpec`: the
    first rd = pct * head_dim dimensions rotate. Under YaRN, with D = rd,
    f_i = theta^(-2i/D) and c(n) = D ln(original_max / (2 pi n)) / (2 ln theta):
    low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), D - 1),
    r_i = clip((i - low) / (high - low), 0, 1), inv_freq_i = (f_i / factor)
    r_i + f_i (1 - r_i): dimensions that turn often within the original
    context keep their frequency, the slow ones are interpolated."""
    rd = int(head_dim * spec.pct)
    rd -= rd % 2
    # in float64, rounded once at the end: at position 4,000 one float32
    # rounding of a fast dimension's frequency is 2e-4 rad
    freqs = 1.0 / (spec.theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd))
    if spec.yarn_factor <= 0:
        return freqs.astype(np.float32), float(spec.attention_factor or 1.0), rd
    turns = lambda n: rd * np.log(spec.yarn_original_max / (2 * np.pi * n)) / (2 * np.log(spec.theta))
    low = max(np.floor(turns(spec.yarn_beta_fast)), 0)
    high = min(np.ceil(turns(spec.yarn_beta_slow)), rd - 1)
    ramp = np.clip((np.arange(rd // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = freqs / spec.yarn_factor * ramp + freqs * (1.0 - ramp)
    factor = spec.attention_factor or 0.1 * np.log(spec.yarn_factor) + 1.0
    return inv_freq.astype(np.float32), float(factor), rd


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, rotary_dim: Optional[int] = None,
    spec: Optional[RopeSpec] = None,
) -> jnp.ndarray:
    """Rotary position embedding (half-split / rotate_half convention).
    x: [b, t, h, hd], positions: [b, t]. When rotary_dim < hd only the
    first rotary_dim dims rotate (pythia/GPT-J partial rotary); interleaved
    checkpoints (GPT-J) are converted to this layout at load time. A `spec`
    (a layer kind's own settings) takes the place of theta and rotary_dim."""
    hd = x.shape[-1]
    if spec is None:
        rd, factor = (hd if rotary_dim is None else rotary_dim), 1.0
        freqs = rope_frequencies(rd, theta)
    else:
        freqs, factor, rd = rope_tables(spec, hd)
    rot, rest = (x, None) if rd == hd else (x[..., :rd], x[..., rd:])
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(freqs)  # [b, t, rd/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [b, t, 1, rd/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    rotated = rotated.astype(x.dtype)
    if rest is not None:
        rotated = jnp.concatenate([rotated, rest], axis=-1)
    return rotated


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (Press et al.; matches HF Bloom)."""
    import math

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return np.asarray(pow2(n_heads), dtype=np.float32)
    closest = 2 ** math.floor(math.log2(n_heads))
    extra = pow2(2 * closest)[0::2][: n_heads - closest]
    return np.asarray(pow2(closest) + extra, dtype=np.float32)


def alibi_bias(key_mask: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """Additive ALiBi bias [b, h, 1, S] from key validity mask [b, S].
    Uses the key-position form slope·k_pos (softmax-equivalent to the
    relative form since the per-query constant cancels), exactly as HF
    Bloom builds it from the attention-mask cumsum."""
    k_pos = jnp.clip(jnp.cumsum(key_mask.astype(jnp.float32), axis=-1) - 1.0, 0.0, None)
    k_pos = k_pos * key_mask.astype(jnp.float32)
    slopes = jnp.asarray(alibi_slopes(n_heads))  # [h]
    return (slopes[None, :, None, None] * k_pos[:, None, None, :]).astype(jnp.float32)


def fused_attention_ok(cfg: TransformerConfig, seq_len: Optional[int] = None,
                       kind: Optional[str] = None, forward_only: bool = False) -> bool:
    """Whether the fused (flash/ring) kernels can express cfg's attention
    structure for a length-`seq_len` forward. Single source of truth for
    Attention, `train_bias` (TransformerLM.forward), and the GPipe stage — the
    caller's bias=None decision must match Attention's branch exactly.

    A sliding window is a static no-op when seq_len <= window, so the
    fused path stays available for the common fits-in-window case (e.g.
    Mistral's 4096 window at 2048-token training). Ring attention shards
    the sequence, so a configured window can never be proven inactive
    from the local length — reject loudly instead of silently computing
    shard-local attention. `kind` is the layer's (`cfg.window_of`): a full
    layer of a model with sliding ones has no window. `forward_only` (a
    cached prefill: nothing differentiates it) admits an active window
    under "flash", whose forward kernel takes the band; its backward does
    not, so a training forward longer than the window keeps the dense bias.
    A latent layer's value heads are narrower than its query/key heads, which
    the forward kernel takes and the backward kernels do not: the same rule."""
    window = cfg.window_of(kind)
    if cfg.attn_impl not in ("flash", "ring", "blockwise"):
        return False
    if cfg.latent_of(kind) is not None:
        return forward_only and cfg.attn_impl == "flash" and seq_len is not None
    if window is not None and cfg.attn_impl == "ring":
        raise NotImplementedError(
            "sliding_window with ring attention is not supported; use "
            "attn_impl='xla' or 'flash'"
        )
    if cfg.alibi:
        return False
    if window is not None and (seq_len is None or seq_len > window):
        return forward_only and cfg.attn_impl == "flash" and seq_len is not None
    return True


def prefill_fuses(cfg: TransformerConfig, t: int) -> bool:
    """Whether a prefill of t positions into an EMPTY cache attends within
    the block through the fused kernel on every attention layer
    (`cfg.flash_prefill`): the sampler's and the engine's one condition."""
    return (cfg.flash_prefill and cfg.prefix_tokens == 0 and cfg.prompt_tokens == 0
            and all(fused_attention_ok(cfg, t, kind, forward_only=True)
                    for kind in cfg.attention_kinds or (None,)))


def lora_dense(mod: nn.Module, cfg: TransformerConfig, feats: int, name: str, use_bias: bool):
    """A Dense layer with an optional LoRA adapter (y += x·A·B · α/r).
    Adapter leaves sit beside the base kernel in the param tree
    (`<name>_lora_a/b`), so base weights keep their HF-interop layout and
    the adapter subtree can be masked/saved/zeroed independently —
    functionally what the reference gets from peft wrapping
    (modeling_base.py:123-326).

    Multi-tenant serving threads *per-row* adapter factors through the
    `lora_rows` variable collection: when `<name>_lora_a/b` exist there
    (shapes [b, d, r] / [b, r, feats], one factor pair per batch row),
    they replace the param-tree adapter entirely — the heterogeneous
    batch applies each row's own adapter in one program, and a zero
    factor pair reproduces the base policy exactly (the delta term is a
    multiply-by-zero, bitwise 0.0 in floating point)."""
    base = nn.Dense(feats, use_bias=use_bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
    if cfg.lora_rank <= 0 or name not in cfg.lora_targets:
        return base

    def fwd(x):
        y = base(x)
        scale = cfg.lora_alpha / cfg.lora_rank
        if mod.has_variable("lora_rows", f"{name}_lora_a"):
            ar = mod.get_variable("lora_rows", f"{name}_lora_a")  # [b, d, r]
            br = mod.get_variable("lora_rows", f"{name}_lora_b")  # [b, r, feats]
            xr = jnp.einsum("b...d,bdr->b...r", x.astype(cfg.dtype), ar.astype(cfg.dtype))
            return y + jnp.einsum("b...r,brf->b...f", xr, br.astype(cfg.dtype)) * scale
        a = mod.param(
            f"{name}_lora_a",
            nn.initializers.normal(stddev=1.0 / cfg.lora_rank),
            (x.shape[-1], cfg.lora_rank),
            cfg.param_dtype,
        )
        b = mod.param(f"{name}_lora_b", nn.initializers.zeros, (cfg.lora_rank, feats), cfg.param_dtype)
        return y + (x.astype(cfg.dtype) @ a.astype(cfg.dtype)) @ b.astype(cfg.dtype) * scale

    return fwd


class Attention(nn.Module):
    cfg: TransformerConfig
    # the layer's own kind and query heads, where a model's layers differ
    # (`cfg.layer_types`, `cfg.layer_heads`); the defaults are the model's
    kind: Optional[str] = None
    n_heads: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        h: jnp.ndarray,  # [b, t, d]
        attn_bias: jnp.ndarray,  # [b, 1, t, S] additive
        positions: jnp.ndarray,  # [b, t]
        layer_cache: Optional[Dict[str, jnp.ndarray]] = None,
        cache_index: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,  # [b, t] key validity (fused paths)
        use_prefix: bool = True,
        attn_kernel: Optional[str] = None,  # paged decode: "pallas" | "interpret"; "prefill": see flash_prefill
    ):
        cfg = self.cfg
        b, t, d = h.shape
        nh, nkv, hd = self.n_heads or cfg.n_heads, cfg.kv_heads, cfg.head_dim
        window = cfg.window_of(self.kind)
        bias_flag = cfg.use_bias if cfg.attn_bias is None else cfg.attn_bias
        dense = lambda feats, name: lora_dense(self, cfg, feats, name, bias_flag)
        q = dense(nh * hd, "q_proj")(h).reshape(b, t, nh, hd)
        k = dense(nkv * hd, "k_proj")(h).reshape(b, t, nkv, hd)
        v = dense(nkv * hd, "v_proj")(h).reshape(b, t, nkv, hd)
        if cfg.multipliers.key != 1.0:
            k = k * cfg.multipliers.key

        if cfg.qk_norm:
            head_norm = lambda name: nn.RMSNorm(
                epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
            q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)
        rope = cfg.rope_of(self.kind)
        if cfg.pos_embed == "rope" and (rope is None or rope.pct > 0):  # pct 0: a kind that does not rotate (NoPE)
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim, rope)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim, rope)
        gate = None
        if cfg.attn_gate != "none":  # [b, t, nh] a head, [b, t, nh * hd] elementwise
            width = nh if cfg.attn_gate == "per_head" else nh * hd
            gate = jax.nn.sigmoid(dense(width, "gate_proj")(h).astype(jnp.float32)).astype(cfg.dtype)

        def project_out(out):  # [b, t, nh * hd] or [b, t, nh, hd]
            if cfg.attn_gate == "per_head":
                out = out.reshape(b, t, nh, hd) * gate[..., None]
            elif gate is not None:
                out = out.reshape(b, t, nh * hd) * gate
            return dense(d, "o_proj")(out.reshape(b, t, nh * hd))

        new_cache = None
        if layer_cache is not None and "table" in layer_cache:
            # Paged KV pool (inference/engine.py, kv_paging): the layer
            # cache is a global block arena shared by every slot (layout
            # owned by ops/paged_attention.py) plus a per-row block table
            # [b, n_tbl] mapping logical token columns to physical
            # blocks. This step's K/V lands at per-row columns
            # [cache_index, cache_index + t); positions with
            # attn_mask == 0 (right-pad, inactive slots) never touch the
            # arena (`paged_kv_write` drops them), so stale block tables
            # on freed rows are harmless. The read side is either the
            # fused kernel or a gather of the row's blocks back into the
            # dense [b, n_tbl*block_size, nkv, hd] layout that falls
            # through to the same einsum as the fixed pool.
            from trlx_tpu.ops import paged_attention as paged

            table = layer_cache["table"]  # [b, n_tbl] int32
            idx = cache_index if jnp.ndim(cache_index) == 1 else jnp.full(
                (b,), cache_index, jnp.int32
            )
            kernel = attn_kernel is not None and attn_kernel != "prefill"
            # a decode step's one position a row reaches the arena from the kernel itself where the
            # arena's shapes let it (`writes_in_kernel`); everything else is `paged_kv_write`'s
            in_kernel = kernel and t == 1 and paged.writes_in_kernel(layer_cache["k"])
            if not in_kernel:
                new_cache = paged.paged_kv_write(layer_cache, k, v, table, idx, attn_mask)
                new_cache["table"] = table
            if kernel:
                # Fused Pallas read side: one pass over each row's live table entries, several a
                # grid step, fetches the blocks directly: no gathered dense copy, no materialized
                # dequant, no kv-head repeat. The engine guarantees the shape is expressible
                # (t == 1, no alibi/prefix bias terms) and counts a fallback to the gather path
                # otherwise. A sliding layer hands the kernel its window: it walks the table
                # entries that hold the last `window` columns and no others.
                if t != 1:
                    raise ValueError(
                        "paged decode kernel takes single-position queries; "
                        f"got t={t} (engine should have fallen back)"
                    )
                if cfg.alibi or cfg.prefix_tokens > 0:
                    raise ValueError(
                        "paged decode kernel cannot express alibi/"
                        "prefix bias terms (engine should have fallen back)"
                    )
                # decode_bias writes exactly 0.0 on attendable columns and -1e9 elsewhere, so key
                # validity is recoverable from the bias row without widening the call signature.
                # A row with no token this step (a freed slot keeps its mask until the next
                # insert) has nothing to attend with: all-masked, so the kernel walks none of its
                # stale table, and writes nothing through it.
                key_mask = attn_bias[:, 0, 0, :] == 0.0
                if attn_mask is not None:
                    key_mask &= attn_mask > 0
                arenas = layer_cache if in_kernel else new_cache
                kernel_out = paged.paged_attention_decode(
                    q[:, 0], arenas["k"], arenas["v"], table, key_mask,
                    k_scale=arenas.get("k_scale"), v_scale=arenas.get("v_scale"),
                    out_dtype=cfg.dtype, interpret=(attn_kernel == "interpret"), window=window,
                    new_kv=(k[:, 0], v[:, 0]) if in_kernel else None,  # the step's K/V: the call writes them too,
                    column=idx if in_kernel else None,  # at this column a row
                )
                if in_kernel:
                    kernel_out, k_arena, v_arena = kernel_out
                    new_cache = {"k": k_arena, "v": v_arena, "table": table}
                return project_out(kernel_out.reshape(b, 1, nh * hd)), new_cache
            if attn_kernel != "prefill":
                # "prefill" (cfg.flash_prefill, rows that meet no cached
                # prefix): the new block is all there is to attend to, so k, v
                # stay the block and the fused branch below reads them
                k, v = paged.paged_kv_gather(new_cache, table, cfg.dtype)
        elif layer_cache is not None:
            # Write this step's K/V into the cache at cache_index, then attend
            # over the whole (static-length) cache. cache_index is a scalar
            # (every row at the same decode depth — the training sampler) or
            # a [b] vector of per-row offsets (the continuous-batching slot
            # pool, trlx_tpu/inference/engine.py, where each slot sits at
            # its own depth).
            kc = k.astype(layer_cache["k"].dtype)
            vc = v.astype(layer_cache["v"].dtype)
            if jnp.ndim(cache_index) == 1:
                row_update = jax.vmap(
                    lambda c, x, i: jax.lax.dynamic_update_slice(c, x, (i, 0, 0))
                )
                ck = row_update(layer_cache["k"], kc, cache_index)
                cv = row_update(layer_cache["v"], vc, cache_index)
            else:
                ck = jax.lax.dynamic_update_slice(layer_cache["k"], kc, (0, cache_index, 0, 0))
                cv = jax.lax.dynamic_update_slice(layer_cache["v"], vc, (0, cache_index, 0, 0))
            if attn_kernel != "prefill":
                # "prefill" (cfg.flash_prefill): the cache was empty, so the
                # new block is all there is to attend to: k, v stay the block
                # and the fused branch below reads them
                k, v = ck, cv
            new_cache = {"k": ck, "v": cv}

        if cfg.prefix_tokens > 0:
            # Prefix tuning: trainable K/V slots every query may attend to
            # (peft PREFIX_TUNING past_key_values, unrotated like a cache).
            # Params exist regardless of use_prefix (param structure must
            # not depend on call args); the ref forward skips the concat.
            P = cfg.prefix_tokens
            pk = self.param("prefix_k", nn.initializers.normal(stddev=0.02),
                            (P, nkv, hd), cfg.param_dtype)
            pv = self.param("prefix_v", nn.initializers.normal(stddev=0.02),
                            (P, nkv, hd), cfg.param_dtype)
            if use_prefix:
                k = jnp.concatenate(
                    [jnp.broadcast_to(pk[None].astype(k.dtype), (b, P, nkv, hd)), k], axis=1
                )
                v = jnp.concatenate(
                    [jnp.broadcast_to(pv[None].astype(v.dtype), (b, P, nkv, hd)), v], axis=1
                )
                # prefix columns are visible to every query
                attn_bias = jnp.concatenate(
                    [jnp.zeros(attn_bias.shape[:3] + (P,), attn_bias.dtype), attn_bias],
                    axis=-1,
                )

        if (fused_attention_ok(cfg, t, self.kind, forward_only=attn_kernel == "prefill")
                and attn_mask is not None and (layer_cache is None or attn_kernel == "prefill")):
            # Fused training/scoring path: causal + key-padding structure is
            # computed inside the kernel from `attn_mask`; `attn_bias` is
            # ignored (it encodes exactly that structure, causal_bias below).
            # K/V stay at n_kv_heads — the kernels map q-heads to kv-heads
            # per block, so GQA never inflates KV residency or ring traffic.
            if cfg.attn_impl == "ring":
                from trlx_tpu.ops.ring_attention import ring_attention

                out = ring_attention(q, k, v, mask=attn_mask, causal=True)
            elif cfg.attn_impl == "blockwise":
                # pure-XLA lax.scan flash equivalent: no Mosaic kernel, so
                # it compiles in seconds — but the scan BACKWARD banks the
                # [b, t, h, hd] carry once per kv block (O(t^2/block_k)
                # residual bytes), so training fits HBM only at moderate
                # t; its production role is the context-parallel local
                # shard (parallel/context.py), where t_local is small
                from trlx_tpu.ops.attention import blockwise_attention

                if nkv != nh:
                    k = jnp.repeat(k, nh // nkv, axis=2)
                    v = jnp.repeat(v, nh // nkv, axis=2)
                out = blockwise_attention(q, k, v, mask=attn_mask, causal=True)
            else:
                from trlx_tpu.ops.attention import flash_attention

                # a window no longer than the block bands nothing
                band = {} if window is None or t <= window else {"window": window}
                out = flash_attention(q, k, v, mask=attn_mask, causal=True, **band)
            out = out.astype(cfg.dtype)
        else:
            # `dense_attention` (at the end of this file, below every caller of a
            # kernel: a Pallas program is keyed by its callers' lines) over the
            # block's or the cache's keys and values. A decode step over a
            # `first` cache (`decode_step`): no row has a token in front of the
            # suffix chosen there, so the two products and the bias take those
            # columns and the rest of the cache is not read. One branch a width
            # of `live_widths`; the planes go in whole and each branch slices
            # them, which the compiler reads in place. (One conditional
            # hoisted round the whole decode loop re-laid every plane in every branch,
            # and its program did not load beside a trainer's state:
            # PERF.md section 6, PR 46.) The products' lines are `Attention`'s
            # of before, moved.
            if layer_cache is not None and "live" in layer_cache:
                S = k.shape[1]
                out = jax.lax.switch(layer_cache["live"], [
                    (lambda q, k, v, bias, lo=S - w: dense_attention(
                        q, k[:, lo:], v[:, lo:], bias[..., lo:], nkv, cfg.dtype))
                    for w in live_widths(S)], q, k, v, attn_bias)
            else:
                out = dense_attention(q, k, v, attn_bias, nkv, cfg.dtype)
        return project_out(out), new_cache


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA: DeepSeek-V2, as `pangu_ultra_moe`
    configures it), of the shape `cfg.latent_of(kind)` gives. With N an
    RMSNorm and no bias anywhere:

        c_q = N(x W_qa);  [q_nope_h ; q_rope_h] = c_q W_qb      (heads of dn + dr)
        [c_kv ; k_r] = x W_kva;  c = N(c_kv)                     (dc + dr)
        [k_nope_h ; v_h] = c W_kvb                               (dn + dv a head)
        q_rope_h, k_r rotated at the token's position; k_r is ONE vector for all heads
        s_h,t,j = (q_nope_h,t . k_nope_h,j + q_rope_h,t . k_r,j) / sqrt(dn + dr)
        p_h,t = softmax over j in A_t;  o_h,t = sum_j p_h,t,j v_h,j;  y = [o_1 .. o_H] W_o

    `LatentSpec.rescale` (dots3-note's `apply_mla_qkv_lora_rescale`): c_q and
    c each times sqrt(d_model / its rank), behind its norm; the cached c is
    the rescaled one. A_t is every j <= t ("latent_attention"); the band
    t - j < window ("sliding_latent_attention"); or ("sparse_latent_attention",
    DeepSeek-V3.2's sparse attention) the `index_topk` positions j <= t with
    the largest index score, all of them while t < index_topk, ties toward the
    later position (`LatentIndex`):

        I_t,j = sum_g w_t,g relu(qI_t,g . kI_j)      float32; not differentiated

    A token caches `[c ; rotated k_r]`, `LatentSpec.width` values, and in a
    sparse layer kI_j beside it (`layer_cache["latent"]`, `["index_k"]`:
    `[b, S, width]` in the dense families; in the paged arena two tokens a row
    of one plane and a plane of its own, whose layouts ops/paged_attention.py
    owns). The bodies meet the cache three ways. Without a cache, and in a
    prefill into an empty one (`attn_kernel="prefill"`), keys and values are
    DECOMPRESSED for the block at hand and go through the fused forward
    (query/key width dn + dr, value width dv) or the dense products. Every
    other cached step runs ABSORBED over the latents: with W_kvb split by
    head into W_uk,h and W_uv,h,

        q_lat_h = W_uk,h^T q_nope_h;  s_h,t = (q_lat_h . c_t + q_rope_h . k_r,t) / sqrt(dn + dr)
        o_h = W_uv,h (sum_t p_h,t c_t)

    the same numbers, never a per-head key or value in memory: all heads read
    the same latent rows (one K/V head of width dc + dr whose values are its
    first dc columns), which is what `paged_attention_latent` is built on. A
    banded or a sparse layer's prefill into an empty cache goes
    `PREFILL_QUERY_BLOCK` queries at a time (`prefill_by_query_blocks`): a
    band's block through the banded fused forward over the keys its band
    reaches, a sparse block per head under the mask of what its index chose,
    a group of heads' decompressed keys at a time: neither all heads' keys nor
    per-head queries or index scores of the whole prompt exist at once. A
    sparse layer's paged decode step scores a row's cached index keys through
    its block table (`paged_index_scores`), takes the `index_topk` largest
    and reads those latents and no others (`paged_latent_rows`)."""

    cfg: TransformerConfig
    kind: Optional[str] = None
    n_heads: Optional[int] = None

    @nn.compact
    def __call__(self, h, attn_bias, positions, layer_cache=None, cache_index=None, attn_mask=None,
                 use_prefix=True, attn_kernel=None):
        cfg = self.cfg
        spec = cfg.latent_of(self.kind)
        b, t, d = h.shape
        nh = self.n_heads or spec.n_heads
        dc, dn, dr, dv = spec.kv_lora_rank, spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        norm = lambda name: nn.RMSNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        # the float64 frequency table (`rope_tables`), whatever the kind names
        rope = cfg.rope_of(self.kind) or RopeSpec(theta=cfg.rope_theta)
        scale = 1.0 / np.sqrt(dn + dr)
        rescaled = (lambda x, rank: x * float(np.sqrt(d / rank))) if spec.rescale else (lambda x, rank: x)
        # a banded or a sparse layer's prefill into an empty cache: a block of queries at a time
        by_blocks = attn_kernel == "prefill" and (spec.window is not None or spec.index_topk > 0)

        if spec.q_lora_rank:
            c_q = rescaled(norm("q_a_norm")(dense(spec.q_lora_rank, "q_a_proj")(h)), spec.q_lora_rank)
            q_up = dense(nh * (dn + dr), "q_b_proj")
        else:
            c_q, q_up = h, dense(nh * (dn + dr), "q_proj")
        # `qk_norm`: over each head's whole query and over the shared rotary key, before
        # rotation: the cached latent holds the normed key, the absorbed form stays exact
        q_norm = norm("q_norm") if cfg.qk_norm else (lambda q: q)

        def queries(c_q, positions):  # rows of the query's latent -> each head's query, rotary part rotated
            q = q_norm(q_up(c_q).reshape(*c_q.shape[:2], nh, dn + dr))
            return q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta, spec=rope)

        if not by_blocks:
            q_nope, q_rope = queries(c_q, positions)
        kv_a = dense(dc + dr, "kv_a_proj")(h)
        c = rescaled(norm("kv_a_norm")(kv_a[..., :dc]), dc)
        k_rope = kv_a[:, :, None, dc:]  # [b, t, 1, dr]
        if cfg.qk_norm:
            k_rope = norm("k_norm")(k_rope)
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta, spec=rope)
        # W_uk and W_uv are views of this one leaf, cut where they are used
        w_kvb = _Kernel((dc, nh * (dn + dv)), cfg.param_dtype, name="kv_b_proj")()
        w_kvb = w_kvb.astype(cfg.dtype).reshape(dc, nh, dn + dv)
        gate = None
        if cfg.attn_gate == "per_head":  # as `Attention`'s: sigmoid(x W_g)_h on head h's output
            gate = jax.nn.sigmoid(dense(nh, "gate_proj")(h).astype(jnp.float32)).astype(cfg.dtype)
        o_proj = dense(d, "o_proj")

        def project_out(out, gate=gate):  # [b, n, nh, dv]
            if gate is not None:
                out = out * gate[..., None]
            return o_proj(out.reshape(*out.shape[:2], nh * dv))

        latent = jnp.concatenate([c, k_rope[:, :, 0]], axis=-1)  # [b, t, dc + dr]: what is cached
        index, index_k = None, None
        if spec.index_topk:
            index = LatentIndex(cfg, spec, rope, name="indexer")
            index_k = index.keys(h, positions)  # [b, t, index_head_dim]: cached beside the latent

        def absorbed_query(q_nope, q_rope):
            q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_kvb[..., :dn])
            return jnp.concatenate([q_lat, q_rope], axis=-1)  # [b, t, nh, dc + dr]

        def values_up(o_lat):  # [b, t, nh, dc] -> [b, t, nh, dv]
            return jnp.einsum("bthc,chv->bthv", o_lat, w_kvb[..., dn:])

        def among_chosen(bias, keys):
            """`bias` [b, 1, t, S] without the attendable positions the index
            did not choose (`keys` [b, S, index_head_dim]: the block's or the
            cache's index keys)."""
            from trlx_tpu.ops import sparse_attention as sparse

            scores = sparse.index_scores(*index.queries(c_q, h, positions), keys)  # [b, t, S] float32
            chosen = sparse.topk_mask(jnp.where(bias[:, 0] == 0.0, scores, -jnp.inf), spec.index_topk)
            return bias + jnp.where(chosen, 0.0, -1e9)[:, None].astype(bias.dtype)

        new_cache, cached = None, None
        if layer_cache is not None and "table" in layer_cache:
            # the paged latent arena: as Attention's paged branch, one plane (and the index's)
            from trlx_tpu.ops import paged_attention as paged

            table = layer_cache["table"]
            idx = cache_index if jnp.ndim(cache_index) == 1 else jnp.full((b,), cache_index, jnp.int32)
            new_cache = paged.paged_latent_write(layer_cache, latent, table, idx, attn_mask, values=dc)
            if index is not None:
                new_cache.update(paged.paged_plane_write(layer_cache, "index_k", index_k, table, idx, attn_mask))
            new_cache["table"] = table
            if attn_kernel is not None and attn_kernel != "prefill":
                if t != 1:
                    raise ValueError(
                        "paged decode kernel takes single-position queries; "
                        f"got t={t} (engine should have fallen back)"
                    )
                key_mask = attn_bias[:, 0, 0, :] == 0.0  # as Attention reads it
                if attn_mask is not None:
                    key_mask &= attn_mask > 0
                interpret = attn_kernel == "interpret"
                q_abs = absorbed_query(q_nope, q_rope)[:, 0]
                if index is not None:
                    from trlx_tpu.ops import sparse_attention as sparse

                    q_index, w_index = index.queries(c_q, h, positions)
                    scores = paged.paged_index_scores(
                        q_index[:, 0], w_index[:, 0], new_cache["index_k"], table, key_mask, interpret=interpret)
                    columns, chosen = sparse.topk_columns(scores, spec.index_topk)
                    rows = paged.paged_latent_rows(new_cache["latent"], table, columns, values=dc)
                    o_lat = sparse.attend_chosen(q_abs, rows, chosen, values=dc, scale=scale, out_dtype=cfg.dtype)
                else:
                    o_lat = paged.paged_attention_latent(
                        q_abs, new_cache["latent"], table, key_mask,
                        values=dc, scale=scale, out_dtype=cfg.dtype,
                        interpret=interpret, window=spec.window,
                    )
                return project_out(values_up(o_lat[:, None])), new_cache
            if attn_kernel != "prefill":
                cached = paged.paged_latent_gather(new_cache, table, values=dc)
                if index is not None:
                    index_k = paged.paged_plane_gather(new_cache["index_k"], table)
        elif layer_cache is not None:
            def put(plane, x):
                x = x.astype(plane.dtype)
                if jnp.ndim(cache_index) == 1:
                    return jax.vmap(lambda row, x, i: jax.lax.dynamic_update_slice(row, x, (i, 0)))(
                        plane, x, cache_index)
                return jax.lax.dynamic_update_slice(plane, x, (0, cache_index, 0))

            cached = put(layer_cache["latent"], latent)
            new_cache = {"latent": cached}
            if index is not None:
                new_cache["index_k"] = put(layer_cache["index_k"], index_k)
            if attn_kernel == "prefill":
                cached = None  # the cache was empty: the block is all there is to attend to
            elif index is not None:
                index_k = new_cache["index_k"]

        if cached is not None:
            # absorbed, over every cached latent: [b, nh, t, S] scores in f32
            if index is not None:
                attn_bias = among_chosen(attn_bias, index_k)
            scores = jnp.einsum("bthc,bsc->bhts", absorbed_query(q_nope, q_rope), cached,
                                preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(scores + attn_bias, axis=-1).astype(cfg.dtype)
            out = values_up(jnp.einsum("bhts,bsc->bthc", probs, cached[..., :dc]))
            return project_out(out), new_cache

        if by_blocks:
            out = prefill_by_query_blocks(
                spec, c_q=c_q, c=c, k_rope=k_rope, index_k=index_k, h=h, positions=positions,
                mask=attn_mask, gate=gate, w_kvb=w_kvb, queries=queries,
                project_out=project_out, index=index, scale=scale)
            return out, new_cache

        kv = jnp.einsum("btc,chm->bthm", c, w_kvb)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, nh, dr))], axis=-1)
        v = kv[..., dn:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if (fused_attention_ok(cfg, t, self.kind, forward_only=attn_kernel == "prefill")
                and attn_mask is not None):
            from trlx_tpu.ops.attention import flash_attention

            out = flash_attention(q, k, v, mask=attn_mask, causal=True).astype(cfg.dtype)
        else:
            if index is not None:
                attn_bias = among_chosen(attn_bias, index_k)
            scores = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(scores + attn_bias, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhts,bshd->bthd", probs, v)
        return project_out(out), new_cache


class LatentIndex(nn.Module):
    """The learned index of a sparse latent layer (DeepSeek-V3.2's "lightning
    indexer", without its Hadamard rotation and FP8 cast of qI and kI). With x
    the layer's normed input, c_q the query's (rescaled) latent, G =
    `index_heads` heads of D = `index_head_dim`:

        qI_t,g = c_q,t W_iq                      (`wq_b`, G heads of D)
        kI_j   = LayerNorm(x_j W_ik)             (`wk`, `k_norm` with a bias, eps 1e-6: ONE key of D a token)
        the first D / 2 dimensions rotated at the position (rotate-half, the layer's base), on both
        w_t,g  = (x_t W_w)_g * G^-1/2 * D^-1/2   (`weights_proj`, float32)
        I_t,j  = sum_g w_t,g relu(qI_t,g . kI_j)    float32 (`ops/sparse_attention.index_scores`)
    """

    cfg: TransformerConfig
    spec: LatentSpec
    rope: RopeSpec

    def setup(self):
        cfg, spec = self.cfg, self.spec
        dense = lambda feats: nn.Dense(feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.wq_b = dense(spec.index_heads * spec.index_head_dim)
        self.wk = dense(spec.index_head_dim)
        self.k_norm = nn.LayerNorm(epsilon=1e-6, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.weights_proj = dense(spec.index_heads)

    def _rotated(self, x, positions):  # [b, n, heads, D]: the first half of D rotates
        half = self.spec.index_head_dim // 2
        return jnp.concatenate(
            [apply_rope(x[..., :half], positions, self.cfg.rope_theta, spec=self.rope), x[..., half:]], axis=-1)

    def keys(self, x, positions):
        """[b, n, D]: what a token caches for the index."""
        return self._rotated(self.k_norm(self.wk(x))[:, :, None], positions)[:, :, 0]

    def queries(self, c_q, x, positions):
        """(qI [b, n, G, D], w [b, n, G] float32) of the query positions."""
        spec = self.spec
        q = self.wq_b(c_q).reshape(*c_q.shape[:2], spec.index_heads, spec.index_head_dim)
        w = self.weights_proj(x).astype(jnp.float32) * float(spec.index_heads ** -0.5 * spec.index_head_dim ** -0.5)
        return self._rotated(q, positions), w


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        dense = lambda feats, name: lora_dense(self, cfg, feats, name, cfg.use_bias)
        act = activation_fn(cfg)
        on_gate, on_out = cfg.multipliers.mlp or (1.0, 1.0)  # on the gate's pre-activation, on the output
        if cfg.multipliers.mlp and not cfg.glu:
            raise NotImplementedError("multipliers.mlp are a gated MLP's (glu=True)")
        if cfg.glu:
            gate = dense(cfg.d_ff, "gate_proj")(h)
            gated = act(gate if on_gate == 1.0 else gate * on_gate) * dense(cfg.d_ff, "up_proj")(h)
            out = dense(cfg.d_model, "down_proj")(gated)
            return out if on_out == 1.0 else out * on_out
        return dense(cfg.d_model, "down_proj")(act(dense(cfg.d_ff, "up_proj")(h)))


class MoEMLP(nn.Module):
    """Expert-parallel MLP: router -> top-k gates -> per-expert FFN mix.
    Expert params carry a leading [n_experts] dim (sharded over `tensor`
    by the rule table), so each device holds E/tp experts and XLA psums
    the masked partial outputs."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        E, k, d, f = cfg.moe_experts, cfg.moe_top_k, cfg.d_model, cfg.d_ff
        act = activation_fn(cfg)

        gate_logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype, name="router"
        )(h)  # [b, t, E] — routing in f32 for stable softmax
        probs = jax.nn.softmax(gate_logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)  # renormalize
        gates = jnp.zeros_like(probs)
        selected = jnp.zeros_like(probs)
        for j in range(k):  # static tiny loop: scatter top-k gates back to [b,t,E]
            onehot = jax.nn.one_hot(top_i[..., j], E, dtype=probs.dtype)
            gates = gates + top_w[..., j, None] * onehot
            selected = selected + onehot

        # Switch-style load-balancing signal, consumed by the trainers'
        # loss fns via mutable "intermediates" (collect_moe_aux_loss)
        frac_routed = selected.reshape(-1, E).mean(0)  # [E]
        mean_prob = probs.reshape(-1, E).mean(0)
        self.sow("intermediates", "moe_aux", E * jnp.sum(frac_routed * mean_prob))

        # batch_axis keeps fan_in = d per expert (a plain 3D lecun_normal
        # would divide variance by E*d, starting experts sqrt(E) too small)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
        )
        up = self.param("up_proj", init, (E, d, f), cfg.param_dtype)
        down = self.param("down_proj", init, (E, f, d), cfg.param_dtype)
        h_c = h.astype(cfg.dtype)
        hidden = jnp.einsum("btd,edf->btef", h_c, up.astype(cfg.dtype))
        if cfg.use_bias:
            up_b = self.param("up_bias", nn.initializers.zeros, (E, f), cfg.param_dtype)
            hidden = hidden + up_b.astype(cfg.dtype)[None, None]
        if cfg.glu:
            gate_w = self.param("gate_proj", init, (E, d, f), cfg.param_dtype)
            hidden = act(jnp.einsum("btd,edf->btef", h_c, gate_w.astype(cfg.dtype))) * hidden
        else:
            hidden = act(hidden)
        out = jnp.einsum("btef,efd->bted", hidden, down.astype(cfg.dtype))
        if cfg.use_bias:
            down_b = self.param("down_bias", nn.initializers.zeros, (E, d), cfg.param_dtype)
            out = out + down_b.astype(cfg.dtype)[None, None]
        return jnp.einsum("bte,bted->btd", gates.astype(cfg.dtype), out)


class ShortConv(nn.Module):
    """Gated short convolution (LFM2's `conv` operator):

        B, C, u = split(x W_in, 3);  z = B * u
        c_t = sum_j w[j] * z_{t - (K-1) + j}      (depthwise, causal, K taps)
        y = (C * c) W_out

    no activation, no bias. A position whose mask bit is 0 is zeroed before
    `W_in`, so its z is 0 and left padding reads as no history. Decode
    state: the last K-1 values of z per channel, `layer_cache["conv"]`
    [b, K-1, d]; a row whose step is masked keeps its state."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, layer_cache=None, token_mask=None, cache_index=None):
        cfg = self.cfg
        b, t, d = h.shape
        taps = cfg.conv_kernel
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        # [K, d]: the fan-in (the taps) leads, like every `kernel`
        w = self.param("kernel", nn.initializers.lecun_normal(), (taps, d), cfg.param_dtype)
        w = w.astype(cfg.dtype)
        if token_mask is not None:
            h = h * token_mask[..., None].astype(h.dtype)
        gate_b, gate_c, u = jnp.split(dense(3 * d, "in_proj")(h), 3, axis=-1)
        z = gate_b * u
        history = (jnp.zeros((b, taps - 1, d), z.dtype) if layer_cache is None
                   else layer_cache["conv"].astype(z.dtype))
        padded = jnp.concatenate([history, z], axis=1)  # [b, K-1+t, d]
        conv = sum(w[j] * jax.lax.dynamic_slice_in_dim(padded, j, t, axis=1) for j in range(taps))
        new_cache = None
        if layer_cache is not None:
            if t > 1 and jnp.ndim(cache_index) == 1:
                # a per-row cache's prefill is RIGHT-padded: a row's tail ends at its own last token
                state = tail_inputs(padded, token_mask, taps).astype(layer_cache["conv"].dtype)
            else:
                state = padded[:, t:].astype(layer_cache["conv"].dtype)  # the last K-1 of history + z
                if t == 1 and token_mask is not None:
                    state = jnp.where(token_mask[:, :1, None] > 0, state, layer_cache["conv"])
            new_cache = {"conv": state}
        return dense(d, "out_proj")(gate_c * conv), new_cache


def tail_inputs(padded, token_mask, taps: int):
    """The `taps - 1` inputs that end at each row's last real position:
    `padded` [b, taps - 1 + t, w] is the row's history followed by the
    block's inputs (0 at masked positions), `token_mask` [b, t] or None (all
    real). Left padding, right padding and a row with no token at all (its
    history is kept) are one rule: the slice starts `end` entries in, `end`
    the number of positions up to the last real one."""
    t = padded.shape[1] - (taps - 1)
    if token_mask is None:
        return padded[:, t:]
    end = ((token_mask > 0) * (jnp.arange(t) + 1)).max(-1)
    return jax.vmap(lambda row, e: jax.lax.dynamic_slice_in_dim(row, e, taps - 1, axis=0))(padded, end)


class KimiDeltaAttention(nn.Module):
    """Kimi delta attention (KDA, `ops/linear_attention.py`). With x the
    normed input, H heads of d = `cfg.head_dim` and no bias on any product:

        q~, k~, v~ = x W_q, x W_k, x W_v
        q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))      depthwise, causal, `conv_kernel` taps
        q^ = q / |q|_2 * d^-1/2,  k^ = k / |k|_2                       a head
        f = x W_f + b_dt   (`kda_gate_rank` r > 0: x W_fa W_fb + b_dt, through r)     a key channel
        g = kda_lower_bound * sigmoid(exp(a_h) * f)                    `kda_decay` "bounded", in [kda_lower_bound, 0]
        g = -exp(a_h) * softplus(f)                                    `kda_decay` "softplus", in (-inf, 0]
        beta = kda_beta_max * sigmoid(x W_b)                           a head, in (0, 1) or (0, 2)
        S_t = (I - beta k^ k^^T) Diag(exp(g)) S_{t-1} + beta k^ v^T;  o = S_t^T q^
        y = W_o [ RMSNorm(o_h) * sigmoid(x W_g)_h ]                    r = 0: the gate a head
        y = W_o [ RMSNorm(o_h) * sigmoid(x W_ga W_gb + b_g) ]          r > 0: elementwise, through r

    A position whose mask bit is 0 is the identity: its input is zeroed
    before the products (so its convolution input is 0) and its beta and g
    are 0. The layer keeps `state` [b, H, d, d] and `tails` [b, taps - 1, 3 H d]
    (the last inputs of the three convolutions side by side) a ROW, and nothing a
    token (`cfg.layer_keeps`). A block of positions goes through the chunked
    form from the row's state; one position a row with a kernel asked for
    (`attn_kernel` "pallas" | "interpret") through `kda_decode`, in place."""

    cfg: TransformerConfig
    kind: Optional[str] = None
    n_heads: Optional[int] = None

    @nn.compact
    def __call__(self, h, attn_bias, positions, layer_cache=None, cache_index=None, attn_mask=None,
                 use_prefix=True, attn_kernel=None):
        from trlx_tpu.ops import linear_attention as kda

        cfg = self.cfg
        b, t, d = h.shape
        nh, hd, taps, rank = cfg.n_heads, cfg.head_dim, cfg.conv_kernel, cfg.kda_gate_rank
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        bias = lambda width, name: _Bias((width,), cfg.param_dtype, name=name)().astype(jnp.float32)
        # x W, or x W_a W_b through `rank`
        through = lambda feats, name: (dense(feats, f"{name}_proj")(h) if not rank else
                                       dense(feats, f"{name}_b_proj")(dense(rank, f"{name}_a_proj")(h)))
        valid = None if attn_mask is None else (attn_mask > 0)
        if valid is not None:
            h = h * valid[..., None].astype(h.dtype)
        z = jnp.concatenate([dense(nh * hd, n)(h) for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
        # [taps, channels] each, the fan-in leading like every `kernel`. From here to the
        # gate everything is float32 (elementwise over 3 H d channels): the recurrence
        # takes its inputs in float32 anyway, and the tails keep the products' own type
        w = jnp.concatenate([_Kernel((taps, nh * hd), cfg.param_dtype, name=n)()
                             for n in ("q_conv", "k_conv", "v_conv")], axis=-1).astype(jnp.float32)
        history = (jnp.zeros((b, taps - 1, 3 * nh * hd), z.dtype) if layer_cache is None
                   else layer_cache["tails"].astype(z.dtype))
        padded = jnp.concatenate([history, z], axis=1)
        conv = sum(w[j] * jax.lax.dynamic_slice_in_dim(padded, j, t, axis=1).astype(jnp.float32)
                   for j in range(taps))
        q, k, v = (x.reshape(b, t, nh, hd) for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
        unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)
        q, k = unit(q) * hd ** -0.5, unit(k)
        a = bias(nh, "a_log")
        f = (through(nh * hd, "f").astype(jnp.float32) + bias(nh * hd, "dt_bias")).reshape(b, t, nh, hd)
        rate = jnp.exp(a)[:, None]
        if cfg.kda_decay == "softplus":
            g = -rate * jax.nn.softplus(f)
        else:
            g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * f)
        beta = jax.nn.sigmoid(dense(nh, "b_proj")(h).astype(jnp.float32))
        if cfg.kda_beta_max != 1.0:
            beta = cfg.kda_beta_max * beta
        if valid is not None:
            g, beta = g * valid[..., None, None], beta * valid[..., None]

        state = None if layer_cache is None else layer_cache["state"]
        if state is not None and t == 1:
            live = jnp.ones((b,), jnp.int32) if valid is None else valid[:, 0].astype(jnp.int32)
            o, new_state = kda.kda_decode_step(
                state.astype(jnp.float32), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live,
                attn_kernel if attn_kernel in ("pallas", "interpret") else None)
            o = o[:, None]
        else:
            o, new_state = kda.kda_chunked(q, k, v, g, beta, state, forward_only=state is not None)
        new_cache = None
        if layer_cache is not None:
            new_cache = {"state": new_state.astype(state.dtype),
                         "tails": tail_inputs(padded, valid, taps).astype(layer_cache["tails"].dtype)}
        scale = _Scale((hd,), cfg.param_dtype, name="o_norm")().astype(jnp.float32)
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.layer_norm_epsilon) * scale
        if rank:
            gate = jax.nn.sigmoid((through(nh * hd, "g").astype(jnp.float32) + bias(nh * hd, "g_bias"))
                                  .reshape(b, t, nh, hd))
        else:
            gate = jax.nn.sigmoid(dense(nh, "gate_proj")(h).astype(jnp.float32))[..., None]
        out = (o * gate).astype(cfg.dtype).reshape(b, t, nh * hd)
        return dense(d, "o_proj")(out), new_cache


class Mamba2Mixer(nn.Module):
    """The Mamba-2 (SSD, `ops/ssd.py`) mixer of an `ssm_attention` block, as
    Falcon-H1 publishes it. With u the block's normed input, H = `ssm_heads`
    heads of P = `ssm_head_dim` (d_ssm = H P), a state of N = `ssm_state`, g =
    `ssm_groups` groups, m = `cfg.multipliers` and no bias on a dense product:

        [z ; x ; B ; C ; dt] = ((u * m.ssm_in) W_in) * m.ssm       W_in: d -> d_ssm + d_ssm + g N + g N + H;
                                                                   m.ssm's five factors spread over the five column groups
        [x ; B ; C] = SiLU(conv([x ; B ; C]) + b_conv)             depthwise, causal, `ssm_conv_kernel` taps
        dt = softplus(dt + dt_bias),  A = -exp(a_log)              a head, float32, no clamp
        h_t = exp(dt_t A) h_{t-1} + B_t (dt_t x_t)^T               h in R^{N x P} a head; head i reads group i // (H / g)
        y_t = h_t^T C_t + D x_t                                    D a head
        y = RMSNorm_grouped(y * SiLU(z))                           the gate FIRST, then a norm over each of g groups
                                                                   of d_ssm / g channels, one scale d_ssm wide
        out = (y W_out) * m.ssm_out

    A position whose mask bit is 0 is the identity on what the layer keeps a
    slot: its input is zeroed before `W_in` (so its convolution input is 0),
    its dt is 0 (decay 1, write 0), and the tails end at the row's last real
    position (`tail_inputs`). The mixer keeps `state` [b, H, N, P] and `tails`
    [b, taps - 1, d_ssm + 2 g N] (the convolution's last inputs) a ROW; the
    block's K and V are `Attention`'s. A block of positions goes through the
    chunked form from the row's state; one position a row with a kernel asked
    for (`attn_kernel` "pallas" | "interpret") through `ssd_decode`, in place."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u, layer_cache=None, token_mask=None, attn_kernel=None):
        from trlx_tpu.ops import ssd

        cfg, m = self.cfg, self.cfg.multipliers
        b, t, d = u.shape
        nh, hd, N, g, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_kernel
        d_ssm, width = nh * hd, cfg.ssm_width
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        head = lambda cls, name: cls((nh,), cfg.param_dtype, name=name)().astype(jnp.float32)
        valid = None if token_mask is None else (token_mask > 0)
        if valid is not None:
            u = u * valid[..., None].astype(u.dtype)
        if m.ssm_in != 1.0:
            u = u * m.ssm_in
        p = dense(2 * d_ssm + 2 * g * N + nh, "in_proj")(u)
        if m.ssm:
            p = p * jnp.asarray(np.repeat(np.asarray(m.ssm, np.float32), (d_ssm, d_ssm, g * N, g * N, nh)), p.dtype)
        z, xbc, dt = p[..., :d_ssm], p[..., d_ssm:d_ssm + width], p[..., d_ssm + width:]
        # [taps, channels], the fan-in leading like every `kernel`. From here to the gate
        # everything is float32; the tails keep the product's own type
        conv = _ConvLeaves((taps, width), cfg.param_dtype, name="conv1d")
        w, b_conv = (leaf.astype(jnp.float32) for leaf in conv())
        history = (jnp.zeros((b, taps - 1, width), xbc.dtype) if layer_cache is None
                   else layer_cache["tails"].astype(xbc.dtype))
        padded = jnp.concatenate([history, xbc], axis=1)
        mixed = jax.nn.silu(b_conv + sum(
            w[j] * jax.lax.dynamic_slice_in_dim(padded, j, t, axis=1).astype(jnp.float32) for j in range(taps)))
        x = mixed[..., :d_ssm].reshape(b, t, nh, hd)
        B, C = (mixed[..., lo:lo + g * N].reshape(b, t, g, N) for lo in (d_ssm, d_ssm + g * N))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + head(_Bias, "dt_bias"))
        if valid is not None:
            dt = dt * valid[..., None]
        A = -jnp.exp(head(_Bias, "a_log"))

        state = None if layer_cache is None else layer_cache["state"]
        if state is not None and t == 1:
            live = jnp.ones((b,), jnp.int32) if valid is None else valid[:, 0].astype(jnp.int32)
            y, new_state = ssd.ssd_decode_step(
                state.astype(jnp.float32), x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], live,
                attn_kernel if attn_kernel in ("pallas", "interpret") else None)
            y = y[:, None]
        else:
            y, new_state = ssd.ssd_chunked(x, dt, A, B, C, state, chunk=cfg.ssm_chunk)
        new_cache = None
        if layer_cache is not None:
            new_cache = {"state": new_state.astype(state.dtype),
                         "tails": tail_inputs(padded, valid, taps).astype(layer_cache["tails"].dtype)}
        y = (y + head(_Scale, "d")[:, None] * x).reshape(b, t, d_ssm) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(b, t, g, d_ssm // g)
        y = (y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.layer_norm_epsilon)).reshape(b, t, d_ssm)
        y = y * _Scale((d_ssm,), cfg.param_dtype, name="norm")().astype(jnp.float32)
        out = dense(d, "out_proj")(y.astype(cfg.dtype))
        return (out if m.ssm_out == 1.0 else out * m.ssm_out), new_cache


class _ConvLeaves(nn.Module):
    """A depthwise convolution's `kernel` [taps, channels] and `bias` [channels]."""

    shape: Tuple[int, ...]
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return (self.param("kernel", _fan_in_normal, self.shape, self.param_dtype),
                self.param("bias", nn.initializers.zeros, self.shape[1:], self.param_dtype))


def _fan_in_normal(key, shape, dtype):
    """Normal, 1 / sqrt(fan-in), the fan-in the leading dimension."""
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])).astype(dtype)


class _Kernel(nn.Module):
    """One `kernel` leaf under its own name: the expert stacks keep the
    leaf name (and the fan-in-first shape) that every weight rule knows."""

    shape: Tuple[int, ...]
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", _fan_in_normal, self.shape, self.param_dtype)


class _Bias(nn.Module):
    shape: Tuple[int, ...]
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("bias", nn.initializers.zeros, self.shape, self.param_dtype)


class _Scale(nn.Module):
    shape: Tuple[int, ...]
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, self.shape, self.param_dtype)


def _routed_block(x, token_mask, routing, router, bias, w_gate, w_up, w_down, *, router_kind, top_k, offset, act,
                  n_group, topk_group, count_met):
    """The experts held over one block of a call's tokens: routed here over `x` itself
    (`routing` None: `ops.moe.route`), or by the `routing` the caller made elsewhere."""
    from trlx_tpu.ops import moe

    if routing is None:
        routing = moe.route(x, router, bias, top_k, router_kind, n_group, topk_group)
    return moe.routed_experts(x, *routing, w_gate, w_up, w_down, offset=offset, act=act, token_mask=token_mask,
                              count_met=count_met)


# one jitted function for the blocks of a long call (`moe_token_block`): blocks of one shape (all
# but a ragged last, in every layer and every program of the process) are traced once and lowered
# once a program
_routed_block_jit = jax.jit(_routed_block, static_argnames=(
    "router_kind", "top_k", "offset", "act", "n_group", "topk_group", "count_met"))


class SparseMoE(nn.Module):
    """Routed experts with grouped dispatch (LFM2's expert ffn; SmallThinker's):

        "sigmoid":       s = sigmoid(x W_r) in float32;  sel = top_k(s + b);  w = s[sel];  w /= sum(w) + 1e-6
        "topk_softmax":  r = x W_r in float32;  sel = top_k(r);  w = softmax(r[sel])
        y = sum_{e in sel, e held here} w_e W2_e(act(W1_e n) * W3_e n)

    `b` (`expert_bias/bias`, the sigmoid router's only) steers the selection
    and nothing else: load balancing moves it, the gradient never does
    (stop_gradient in `route_sigmoid`, frozen by `policy.trainable_mask`). `n`
    is the layer's input; `x`, what the router reads, is `n` too unless the
    caller routed elsewhere (`route`, then `__call__(..., routing=)`: `Block`
    under `cfg.moe_route_on`). The layer holds `cfg.experts_held` of the
    model's `cfg.moe_experts`, scores all of them, and adds up what its own
    experts give (ops/moe.py); nothing stands in for the absent ones.
    A stack holds its experts' matrices side by side, `[fan_in, experts
    held * fan_out]` (expert g is column block g): the kernels read a
    block of it and write a block of its gradient where they lie, so no
    step re-lays a stack. Positions whose mask bit is 0 are dispatched
    nowhere and get 0. A layer that holds every expert also counts the
    experts a call met (`ops.moe.MET_STATS`)."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        E, G, d, f = cfg.moe_experts, cfg.experts_held, cfg.d_model, cfg.expert_d_ff
        self.router = _Kernel((d, E), cfg.param_dtype)
        if cfg.moe_router == "sigmoid":
            self.expert_bias = _Bias((E,), cfg.param_dtype)
        self.expert_gate = _Kernel((d, G * f), cfg.param_dtype)
        self.expert_up = _Kernel((d, G * f), cfg.param_dtype)
        self.expert_down = _Kernel((f, G * d), cfg.param_dtype)
        if cfg.moe_shared_d_ff:
            dense = lambda feats: nn.Dense(feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
            self.shared_gate, self.shared_up = dense(cfg.moe_shared_d_ff), dense(cfg.moe_shared_d_ff)
            self.shared_down = dense(d)

    def _router_leaves(self):
        return self.router(), self.expert_bias() if self.cfg.moe_router == "sigmoid" else None

    def route(self, x):
        """(top_i, top_w), each [b * t, k], for x [b, t, d]: the routing of a
        later `__call__(h, token_mask, routing)` over an input of the caller's
        choosing."""
        from trlx_tpu.ops import moe

        cfg = self.cfg
        return moe.route(x.reshape(-1, cfg.d_model).astype(cfg.dtype), *self._router_leaves(), cfg.moe_top_k,
                         cfg.moe_router, cfg.moe_n_group, cfg.moe_topk_group)

    def __call__(self, h, token_mask=None, routing=None):
        cfg = self.cfg
        d = cfg.d_model
        b, t, _ = h.shape
        router, bias = self._router_leaves()
        flat = h.reshape(b * t, d).astype(cfg.dtype)
        stacks = tuple(stack().astype(cfg.dtype) for stack in (self.expert_gate, self.expert_up, self.expert_down))
        flat_mask = None if token_mask is None else token_mask.reshape(b * t)
        how = dict(router_kind=cfg.moe_router, top_k=cfg.moe_top_k, offset=cfg.moe_local_offset,
                   act=activation_fn(cfg), n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
                   count_met=cfg.experts_held == cfg.moe_experts)
        block = cfg.moe_token_block
        if block and b * t > block:  # `moe_token_block` tokens at a time; the counters are the blocks' mean
            rows = lambda x, i: None if x is None else x[i:i + block]
            parts = [_routed_block_jit(flat[i:i + block], rows(flat_mask, i),
                                       None if routing is None else tuple(rows(r, i) for r in routing),
                                       router, bias, *stacks, **how)
                     for i in range(0, b * t, block)]
            out = jnp.concatenate([y for y, _ in parts])
            stats = jax.tree_util.tree_map(lambda *xs: sum(xs) / len(xs), *(st for _, st in parts))
        else:
            out, stats = _routed_block(flat, flat_mask, routing, router, bias, *stacks, **how)
        self.sow("moe_stats", "stats", stats)
        out = out.reshape(b, t, d)
        if cfg.moe_routed_scale != 1.0:
            out = out * cfg.moe_routed_scale
        if cfg.moe_shared_d_ff:
            out = out + self.shared_down(activation_fn(cfg)(self.shared_gate(h)) * self.shared_up(h))
        return out


def moe_stats_from_state(state) -> Dict[str, jnp.ndarray]:
    """The dispatch counters every `SparseMoE` layer sowed during a
    mutable=['moe_stats'] apply, reduced over layers: the mean share of
    assignments that met an expert held here, the worst layer's most
    loaded expert over the mean, the sum of dropped tokens (0 by
    construction). Empty when nothing was sown."""
    per_layer = [s for s in jax.tree_util.tree_leaves(
        state.get("moe_stats", {}), is_leaf=lambda x: isinstance(x, dict) and "dropped_tokens" in x)
        if isinstance(s, dict)]
    if not per_layer:
        return {}
    stack = lambda name: jnp.stack([s[name] for s in per_layer])
    stats = {
        "local_assignment_share": stack("local_assignment_share").mean(),
        "tokens_per_expert_max_over_mean": stack("tokens_per_expert_max_over_mean").max(),
        "dropped_tokens": stack("dropped_tokens").sum(),
    }
    if all("experts_met" in s for s in per_layer):  # layers that hold every expert: the mean layer's count
        stats.update(experts_met=stack("experts_met").mean(), experts_held=stack("experts_held").mean())
    return stats


def exit_early_from_state(state) -> jnp.ndarray:
    """The one scalar a looped stack's cached step sowed during a
    mutable=['loop_stats'] apply (`TransformerLM.decode_step`): the mean share
    of a live position that the exit gate would have let leave before the last pass."""
    return jax.tree_util.tree_leaves(state["loop_stats"])[0]


def moe_aux_from_intermediates(state) -> jnp.ndarray:
    """Sum the moe_aux scalars sown by every MoEMLP during a
    mutable=['intermediates'] apply; 0 when nothing was sown."""
    leaves = jax.tree_util.tree_leaves(state.get("intermediates", {}))
    return sum(leaves) if leaves else jnp.asarray(0.0, jnp.float32)


class Block(nn.Module):
    cfg: TransformerConfig
    # the layer's operator and ffn, for a model whose layers differ
    # (`cfg.layer_op(i)` / `cfg.layer_ffn(i)`); the defaults are the one
    # kind every other family has
    op_kind: str = "attention"
    ffn_kind: Optional[str] = None  # None: MoEMLP if cfg.moe_experts else MLP
    n_heads: Optional[int] = None  # query heads of this layer; None: cfg.n_heads

    @nn.compact
    def __call__(self, h, attn_bias, positions, layer_cache=None, cache_index=None, attn_mask=None,
                 use_prefix=True, attn_kernel=None):
        cfg = self.cfg
        moe, routing = (SparseMoE(cfg, name="mlp") if self.ffn_kind == "sparse_moe" else None), None
        if moe is not None and cfg.moe_route_on == "block_input":
            # the ONE place a router is handed another input than its feed-forward's: the block's
            # own, un-normed, before the attention (under a name of its own in the device trace)
            with jax.named_scope("moe_route_block_input"):
                routing = moe.route(h)
        h_ln = make_norm(cfg, "ln_attn")(h)
        if self.op_kind == "conv":
            attn_out, new_cache = ShortConv(cfg, name="conv")(h_ln, layer_cache, attn_mask, cache_index)
        else:
            if isinstance(attn_bias, dict):  # a bias for each kind of attention layer
                attn_bias = attn_bias.get(self.op_kind)
            attn_cls = (LatentAttention if cfg.latent_of(self.op_kind) is not None
                        else KimiDeltaAttention if self.op_kind == "linear_attention" else Attention)
            m = cfg.multipliers
            attn_out, new_cache = attn_cls(cfg, kind=self.op_kind, n_heads=self.n_heads, name="attn")(
                h_ln if m.attention_in == 1.0 else h_ln * m.attention_in,
                attn_bias, positions, layer_cache, cache_index, attn_mask, use_prefix,
                attn_kernel,
            )
            if m.attention_out != 1.0:
                attn_out = attn_out * m.attention_out
            if self.op_kind == "ssm_attention":
                # both mixers read `ln_attn`'s output; their sum is the one residual add. The
                # layer's cache holds K/V planes (and a table) AND the slot's arrays: each
                # mixer moves its own
                ssm_out, slot = Mamba2Mixer(cfg, name="ssm")(h_ln, layer_cache, attn_mask, attn_kernel)
                attn_out = attn_out + ssm_out
                if layer_cache is not None:
                    new_cache = {**new_cache, **slot}
        # the sandwich: a norm on what each operator gives, before its residual add
        post = (lambda name, x: make_norm(cfg, name)(x)) if cfg.sandwich_norm else (lambda name, x: x)
        if moe is not None:
            mlp = lambda x: moe(x, attn_mask, routing)
        elif self.ffn_kind == "dense" or (self.ffn_kind is None and cfg.moe_experts <= 0):
            mlp = MLP(cfg, name="mlp")
        else:
            mlp = MoEMLP(cfg, name="mlp")
        if cfg.parallel_residual:
            # GPT-NeoX: x + attn(ln1(x)) + mlp(ln2(x)); GPT-J shares ln1.
            mlp_in = h_ln if cfg.shared_ln else make_norm(cfg, "ln_mlp")(h)
            h = h + attn_out + mlp(mlp_in)
        else:
            h = h + post("ln_post_attn", attn_out)
            h = h + post("ln_post_mlp", mlp(make_norm(cfg, "ln_mlp")(h)))
        return h, new_cache


class MTPBlock(nn.Module):
    """One multi-token-prediction block (DeepSeek-V3's form): with h_t the
    state under the model's final norm and x_t+1 the next token,

        h'_t = W_eh [N_e(Emb(x_t+1)) ; N_h(h_t)]      (2 d_model -> d_model)

    then one block of the stack's last kind over h'; the model's own final
    norm and head read its output as logits for position t + 2."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, next_embed, attn_bias, positions, attn_mask):
        cfg = self.cfg
        joined = jnp.concatenate(
            [make_norm(cfg, "enorm")(next_embed), make_norm(cfg, "hnorm")(h)], axis=-1)
        x = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="eh_proj")(joined)
        out, _ = Block(cfg, **cfg.block_kwargs(cfg.n_layers - 1), name="block")(
            x, attn_bias, positions, attn_mask=attn_mask)
        return out


def causal_bias(attn_mask: jnp.ndarray, sliding_window: Optional[int] = None) -> jnp.ndarray:
    """Additive attention bias for training: causal + key-padding, plus
    the sliding-window band when set (Mistral: query i attends keys in
    (i - window, i]). attn_mask: [b, t] (1 = real token). Returns
    [b, 1, t, t] f32."""
    t = attn_mask.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    if sliding_window is not None:
        ids = jnp.arange(t)
        causal = causal & ((ids[:, None] - ids[None, :]) < sliding_window)
    keymask = attn_mask[:, None, None, :].astype(bool)
    allowed = causal[None, None, :, :] & keymask
    return jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)


def by_attention_kind(cfg: TransformerConfig, make):
    """`make(kind)` for every kind of attention layer the model names, as
    {kind: bias} (`Block` picks its own), or `make(None)`, the one bias of a
    model whose attention layers are all alike."""
    kinds = cfg.attention_kinds
    return {kind: make(kind) for kind in kinds} if kinds else make(None)


def train_bias(cfg: TransformerConfig, attn_mask: jnp.ndarray, kind: Optional[str] = None):
    """Additive bias for a no-cache forward, or None when a fused kernel
    builds the structure itself (fused paths cover plain causal only —
    ALiBi and active sliding windows need the dense bias). The single
    bias-construction policy for TransformerLM and the GPipe stage. For a
    model with attention layers of several kinds (and no `kind` asked for):
    a dict, one entry a kind."""
    if kind is None and cfg.attention_kinds:
        return by_attention_kind(cfg, lambda k: train_bias(cfg, attn_mask, k))
    if fused_attention_ok(cfg, attn_mask.shape[-1], kind):
        return None
    bias = causal_bias(attn_mask, cfg.window_of(kind))
    if cfg.alibi:
        bias = bias + alibi_bias(attn_mask, cfg.n_heads)
    return bias


def window_bias(q_positions: jnp.ndarray, key_mask: jnp.ndarray, window: int) -> jnp.ndarray:
    """Additive sliding-window term for cached decode: forbid keys whose
    position trails the query by >= window. q_positions: [b, t];
    key_mask: [b, S] validity. Returns [b, 1, t, S] f32."""
    k_pos = jnp.clip(jnp.cumsum(key_mask.astype(jnp.int32), axis=-1) - 1, 0, None)
    delta = q_positions[:, :, None] - k_pos[:, None, :]  # [b, t, S]
    return jnp.where(delta >= window, -1e9, 0.0)[:, None].astype(jnp.float32)


def decode_bias(cache_mask: jnp.ndarray) -> jnp.ndarray:
    """Bias during cached decode: attend to every valid cache slot.
    cache_mask: [b, S] validity of cache slots (already includes the tokens
    being written this step). For t>1 prefill the causal structure within
    the new block is `cached_bias`'s to add."""
    allowed = cache_mask[:, None, None, :].astype(bool)
    return jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)


def cached_bias(cfg: TransformerConfig, new_mask: jnp.ndarray, positions: jnp.ndarray,
                block_start=None, kind: Optional[str] = None):
    """The additive bias of a cached step, [b, 1, t, S] f32 (one a kind, as
    `train_bias`, where the model's attention layers differ): every valid
    cache column (`new_mask` already holds this step's positions), ALiBi,
    the sliding window, and, for a block of new positions that starts at
    column `block_start` (one scalar, or one offset per row), the causal
    structure within it: query j may not see the keys written for queries
    > j. A column forbidden twice goes to -2e9, still exactly 0 after the
    softmax."""
    if kind is None and cfg.attention_kinds:
        return by_attention_kind(cfg, lambda k: cached_bias(cfg, new_mask, positions, block_start, k))
    bias = decode_bias(new_mask)
    if cfg.alibi:
        bias = bias + alibi_bias(new_mask, cfg.n_heads)
    if cfg.window_of(kind) is not None:
        bias = bias + window_bias(positions, new_mask, cfg.window_of(kind))
    if block_start is not None:
        t, S = positions.shape[-1], new_mask.shape[-1]
        if jnp.ndim(block_start) == 0:
            q_ids = jnp.arange(t)[:, None]
            k_ids = jnp.arange(S)[None, :]
            within = (k_ids < block_start + t) & (k_ids >= block_start) & (k_ids - block_start > q_ids)
            within = within[None, None]
        else:
            q_ids = jnp.arange(t)[None, :, None]
            k_ids = jnp.arange(S)[None, None, :]
            first = block_start[:, None, None]
            within = ((k_ids >= first) & (k_ids - first > q_ids))[:, None]  # [b, 1, t, S]
        bias = bias + jnp.where(within, -1e9, 0.0).astype(jnp.float32)
    return bias


@functools.lru_cache(maxsize=None)
def _block_step(cfg: TransformerConfig, kwargs: Tuple, use_prefix: bool, attn_kernel: Optional[str]):
    """One block of a looped stack as ONE jitted function of its leaves, `(variables, h, attn_bias,
    positions, layer_cache, cache_index, attn_mask) -> (h, new layer cache)`: the layers of a kind
    share it, so a program traces and lowers a block once and calls it a layer (`run_passes`)."""
    block = Block(cfg, **dict(kwargs), parent=None)  # no module of the caller's: a pure function of its leaves

    def step(variables, h, attn_bias, positions, layer_cache, cache_index, attn_mask):
        return block.apply(variables, h, attn_bias, positions, layer_cache, cache_index, attn_mask, use_prefix,
                           attn_kernel)

    return jax.jit(jax.checkpoint(step) if cfg.remat_blocks else step)


@functools.lru_cache(maxsize=None)
def _pass_end(cfg: TransformerConfig):
    """What ends a pass of a looped stack, `(leaves, h) -> (ln_f(h), the exit gate's logit on it
    [b, t] float32)`: the final norm after EVERY pass, the gate read on the normed output."""
    norm = make_norm(cfg, None, parent=None)
    gate = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.param_dtype, parent=None)

    def end(leaves, h):
        h = norm.apply(leaves["ln_f"], h)
        return h, gate.apply(leaves["gate"], h)[..., 0] if cfg.loop_gate else jnp.zeros(h.shape[:2], jnp.float32)

    return jax.jit(end)


class TransformerLM(nn.Module):
    """Decoder-only LM. Returns logits and (optionally) the hidden state at
    a static split layer for the hydra reference branch."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.embed_tokens = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed_tokens"
        )
        if cfg.pos_embed == "learned":
            self.embed_pos = nn.Embed(
                cfg.max_seq_len + cfg.pos_offset, cfg.d_model,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed_pos"
            )
        if cfg.embed_ln:
            self.ln_embed = make_norm(cfg, "ln_embed")
        if cfg.prompt_tokens > 0:
            if cfg.attn_impl == "ring":
                raise NotImplementedError(
                    "prompt tuning under ring attention is not supported "
                    "(the soft prompt would need its own sequence shard)"
                )
            self.soft_prompt = self.param(
                "soft_prompt", nn.initializers.normal(stddev=0.02),
                (cfg.prompt_tokens, cfg.d_model), cfg.param_dtype,
            )
        # use_prefix (arg 7 counting the module) and attn_kernel (arg 8)
        # are static python values
        block_cls = nn.remat(Block, static_argnums=(7, 8)) if cfg.remat_blocks else Block
        self.blocks = [
            block_cls(cfg, **cfg.block_kwargs(i), name=f"block_{i}") for i in range(cfg.n_layers)
        ]
        self.ln_f = make_norm(cfg, "ln_f")
        if cfg.loop_gate:  # read in float32 on every pass's normed output (`run_passes`)
            self.exit_gate = nn.Dense(1, dtype=jnp.float32, param_dtype=cfg.param_dtype, name="exit_gate")
        if not cfg.tie_embeddings:
            self.lm_head = nn.Dense(
                cfg.vocab_size, use_bias=cfg.lm_head_bias,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="lm_head"
            )
        self.mtp = [MTPBlock(cfg, name=f"mtp_{k}") for k in range(cfg.mtp_layers)]

    def embed(self, tokens, positions):
        h = self.embed_tokens(tokens)
        if self.cfg.multipliers.embedding != 1.0:
            h = h * self.cfg.multipliers.embedding
        if self.cfg.pos_embed == "learned":
            h = h + self.embed_pos(positions + self.cfg.pos_offset)
        if self.cfg.embed_ln:
            h = self.ln_embed(h)
        return h

    def unembed(self, h, normed: bool = False):
        """Final norm + output projection. Returns (logits, h_final) so the
        value head can reuse the normed hidden state. `normed`: `h` is under
        the final norm already (a looped stack's last pass: `run_passes`)."""
        h_final = h if normed else self.ln_f(h)
        if self.cfg.tie_embeddings:
            logits = self.embed_tokens.attend(h_final)
        else:
            logits = self.lm_head(h_final)
        if self.cfg.multipliers.lm_head != 1.0:
            logits = logits * self.cfg.multipliers.lm_head
        return logits, h_final

    def _default_positions(self, tokens_or_h, attn_mask):
        """Position ids when the caller didn't supply them. Under ring
        attention the model runs inside shard_map with the sequence dim
        sharded, so a local cumsum would restart at 0 on every shard —
        instead use the shard's global offset (assumes right-padded
        batches, which long-context training uses). Other impls keep the
        left-padding-robust cumsum."""
        if self.cfg.attn_impl == "ring":
            try:
                offset = jax.lax.axis_index("sequence")
            except NameError:
                # Axis unbound (e.g. flax param init outside shard_map) —
                # single-shard case: the sequence is unsharded, so the
                # left-padding-robust cumsum is exact (ring_attention
                # likewise degrades to plain blockwise attention).
                return position_ids(attn_mask)
            t = attn_mask.shape[-1]
            return offset * t + jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None, :], attn_mask.shape
            )
        return position_ids(attn_mask)

    def run_blocks(self, h, attn_bias, positions, start: int, stop: int, cache=None, cache_index=None, attn_mask=None, use_prefix: bool = True, attn_kernel: Optional[str] = None):
        new_layers = [] if cache is not None else None
        for i in range(start, stop):
            layer_cache = cache[i] if cache is not None else None
            h, new_cache = self.blocks[i](h, attn_bias, positions, layer_cache, cache_index, attn_mask, use_prefix, attn_kernel)
            if cache is not None:
                new_layers.append(new_cache)
        return h, new_layers

    def run_passes(self, h, attn_bias, positions, cache=None, cache_index=None, attn_mask=None,
                   use_prefix: bool = True, attn_kernel: Optional[str] = None):
        """A looped stack (`cfg.loop_steps` > 1): every block, then `ln_f`, `loop_steps` times over,
        each pass's normed output the next one's input. Returns (the last pass's normed output, the
        cache's new layers or None, the exit gate's logits `[passes, b, t]` float32: zeros without
        a gate).

        The passes are ONE traced body under `jax.lax.scan`, and a block is ONE jitted function of
        its leaves (`_block_step`: the layers differ in their leaves, not in their shapes), so a
        program costs one block to trace and one pass to compile, whatever the layers and the
        passes (tracing is most of a warm set-up: PERF.md section 5). The leaves are read from the
        bound modules here and handed to pure functions inside the loop.

        The cache's unit is (pass, layer). A dense layer's planes lie `[passes, b, S, ...]`
        (`init_kv_cache`) and pass t is handed plane t and gives it back; a paged layer's arena is
        `passes` pools laid end to end (`init_paged_kv_arena`), carried through the passes and
        patched where it lies, and pass t reads and writes through the row's ONE block table shifted
        by t pools (`ops.paged_attention.pass_table`): one allocation and one table a row serve every
        pass, and the kernels, the block pool and the write are what an un-looped model runs."""
        from trlx_tpu.ops.paged_attention import pass_table

        cfg = self.cfg
        if self.is_initializing():
            # `init`: one walk through the modules makes every leaf; what it returns beside them is not read
            h, _ = self.run_blocks(h, attn_bias, positions, 0, cfg.n_layers, attn_mask=attn_mask, use_prefix=use_prefix)
            h = self.ln_f(h)
            gate = self.exit_gate(h)[..., 0] if cfg.loop_gate else jnp.zeros(h.shape[:2], jnp.float32)
            return h, None, jnp.broadcast_to(gate, (cfg.loop_steps, *h.shape[:2]))
        leaves = [block.variables for block in self.blocks]
        steps = [_block_step(cfg, tuple(sorted(cfg.block_kwargs(i).items())), use_prefix, attn_kernel)
                 for i in range(cfg.n_layers)]
        end_leaves = {"ln_f": self.ln_f.variables, **({"gate": self.exit_gate.variables} if cfg.loop_gate else {})}
        paged = cache is not None and "table" in cache[0]
        bare = lambda layers: [{k: v for k, v in layer.items() if k != "table"} for layer in layers]  # the arena's own
        kv = lambda layers: [{k: layer[k] for k in ("k", "v")} for layer in layers]  # a dense layer's planes
        arena = planes = None
        if paged:
            table, arena = cache[0]["table"], bare(cache)
        elif cache is not None:
            planes = kv(cache)

        def one_pass(carry, xs):
            h, arena = carry
            t, planes = xs
            layers = [None] * cfg.n_layers
            if paged:
                shifted = pass_table(table, t, cfg.loop_steps, arena[0]["k"].shape[0])
                layers = [{**layer, "table": shifted} for layer in arena]
            elif planes is not None:
                layers = [{**layer, **plane} for layer, plane in zip(cache, planes)]
            new = []
            for step, block, layer in zip(steps, leaves, layers):
                h, kept = step(block, h, attn_bias, positions, layer, cache_index, attn_mask)
                new.append(kept)
            h, gate = _pass_end(cfg)(end_leaves, h)
            if paged:
                arena = bare(new)
            elif planes is not None:
                planes = kv(new)
            return (h, arena), (planes, gate)

        (h, arena), (planes, gates) = jax.lax.scan(one_pass, (h, arena), (jnp.arange(cfg.loop_steps), planes))
        if paged:
            return h, [{**layer, "table": table} for layer in arena], gates
        return h, planes, gates

    def __call__(
        self,
        tokens: jnp.ndarray,  # [b, t]
        attn_mask: jnp.ndarray,  # [b, t]
        positions: Optional[jnp.ndarray] = None,
        split: int = 0,
        use_prompt: bool = True,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Training/scoring forward (no cache). Returns (logits, h_split,
        h_final) where h_split is the activation entering block `split`."""
        logits, h_final, caps = self.forward(
            tokens, attn_mask, positions, capture=(split,), use_prompt=use_prompt
        )
        return logits, caps[split], h_final

    def _embed_soft_prompt(self, b, positions_virt):
        """Soft-prompt rows as embeddings, with the same positional/LN
        treatment real token embeddings get."""
        h = jnp.broadcast_to(
            self.soft_prompt[None].astype(self.cfg.dtype),
            (b,) + tuple(self.soft_prompt.shape),
        )
        if self.cfg.pos_embed == "learned":
            h = h + self.embed_pos(positions_virt + self.cfg.pos_offset)
        if self.cfg.embed_ln:
            h = self.ln_embed(h)
        return h

    def forward(
        self,
        x: jnp.ndarray,  # [b, t] token ids; [b, t, d] the state entering block `start` when start > 0
        attn_mask: jnp.ndarray,  # [b, t]
        positions: Optional[jnp.ndarray] = None,
        *,
        start: int = 0,
        stop: Optional[int] = None,
        capture: Tuple[int, ...] = (),
        window: Optional[Tuple[Any, int]] = None,
        use_prompt: bool = True,
        mtp: bool = False,
        exit_pdf: bool = False,
    ):
        """The one forward without a cache: embed (start == 0) or take the
        hidden state entering block `start` (the hydra frozen branch,
        reference forward_hydra, modeling_ppo.py:410-453, when applied with
        reference params; the trunk-cache train path with the live ones),
        run blocks [start, stop) over the full width, unembed. Returns
        (logits, h_final, caps):

        - `caps[i]`, for each layer in `capture`, is the activation ENTERING
          block i (start <= i <= stop): the hydra split point, the input of
          the deeper value branch (reference make_value_branch feeds
          hidden_states[-(k+1)], modeling_ppo.py:255-263, 344-346).
        - `stop` given (n_layers too: a model whose blocks are all frozen)
          runs no head: logits is None and the second slot holds the raw
          state entering block `stop` (the frozen-prefix pass that feeds the
          PPO trunk cache).
        - `window=(first, length)` puts the final norm and the head over
          positions [first, first + length) only. The 2·d·V head matmul is
          the single largest matmul in the model; a PPO train step only
          reads the response window of it (~40 of ~1100 positions at bench
          shapes), so computing it full-width and slicing after —
          especially through the fused-CE kernel, which is opaque to XLA's
          slice-through-matmul fusion — wastes ~27x the useful head FLOPs
          (r5 phase breakdown, VERDICT r4 weak #1).
        - `use_prompt=False` skips the soft prompt (the adapter-disabled
          reference forward under prompt tuning). With it, the soft prompt
          is prepended internally and sliced back off before the
          unembedding, so logits/h_final keep the caller's sequence length;
          captured activations carry the extended length (their consumers
          force split == 0 under prompt tuning).
        - `mtp=True` (a whole forward of token ids, `cfg.mtp_layers` > 0) also
          runs the multi-token-prediction blocks: `caps["mtp"]` is a list, one
          `[b, t, vocab]` a block, in which block k's position i holds the
          logits for token i + k + 2 (its last k + 1 positions have no next
          token to read and are not to be used).
        - `exit_pdf=True` (a looped stack, `cfg.loop_steps` > 1) also gives
          `caps["exit_pdf"]`, `[b, t, passes]` float32: the share of a position
          that the exit gate would let leave after each pass
          (`exit_distribution`). The logits are the last pass's whatever it reads."""
        cfg = self.cfg
        to_head, stop = stop is None, cfg.n_layers if stop is None else stop
        looped = cfg.loop_steps > 1
        if looped and (start > 0 or not to_head or set(capture) - {0}):
            raise NotImplementedError(
                "a looped stack (loop_steps > 1) runs whole: a forward from, to or capturing a layer inside it (the "
                "hydra split, the frozen-trunk cache, num_layers_unfrozen) is not supported: the top layers run in "
                "every pass, so the layers below a split are no prefix of the computation")
        if exit_pdf and not looped:
            raise ValueError("exit_pdf=True needs a looped stack (loop_steps > 1)")
        if cfg.mtp_layers and self.is_initializing() and start == 0 and to_head and window is None:
            mtp = True  # `init` walks the multi-token blocks too, or they get no leaves
        if mtp and (start > 0 or not to_head or window is not None or cfg.prompt_tokens > 0
                    or not cfg.mtp_layers):
            raise NotImplementedError(
                "mtp=True takes a whole forward of token ids (no start, stop, window or "
                "soft prompt) of a model with mtp_layers > 0")
        if cfg.prompt_tokens > 0 and (window is not None or not to_head):
            raise NotImplementedError(
                "a windowed head or a forward that stops short of it is unsupported "
                "under prompt tuning: the soft prompt shifts every position and widens "
                "the captured rows (resolve_split gates the trunk cache off)"
            )
        P = cfg.prompt_tokens if use_prompt and start == 0 else 0
        if P > 0:
            b = x.shape[0]
            attn_mask = jnp.concatenate(
                [jnp.ones((b, P), attn_mask.dtype), attn_mask], axis=1
            )
            if positions is None:
                positions = position_ids(attn_mask)
            else:
                virt = jnp.broadcast_to(jnp.arange(P, dtype=positions.dtype), (b, P))
                positions = jnp.concatenate([virt, positions + P], axis=1)
            h = jnp.concatenate(
                [self._embed_soft_prompt(b, positions[:, :P]),
                 self.embed(x, positions[:, P:])],
                axis=1,
            )
        else:
            if positions is None:
                positions = self._default_positions(x, attn_mask)
            h = self.embed(x, positions) if start == 0 else x
        bias = train_bias(cfg, attn_mask)
        bounds = sorted({start, stop, *capture})
        if bounds[0] < start or bounds[-1] > stop:
            raise ValueError(f"capture {capture} outside the blocks run, [{start}, {stop}]")
        caps = {}
        if looped:
            caps[0] = h
            h, _, gates = self.run_passes(h, bias, positions, attn_mask=attn_mask, use_prefix=use_prompt)
        else:
            for s, e in zip(bounds, bounds[1:]):
                caps[s] = h
                h, _ = self.run_blocks(h, bias, positions, s, e, attn_mask=attn_mask,
                                       use_prefix=use_prompt)
            caps[stop] = h
        caps = {i: caps[i] for i in capture}
        if not to_head:
            return None, h, caps
        if P > 0:
            h = h[:, P:]
        if window is not None:
            h = jax.lax.dynamic_slice_in_dim(h, window[0], window[1], axis=1)
        logits, h_final = self.unembed(h, normed=looped)
        if exit_pdf:
            pdf = exit_distribution(gates)
            caps["exit_pdf"] = pdf if window is None else jax.lax.dynamic_slice_in_dim(pdf, window[0], window[1], axis=1)
        if mtp:
            caps["mtp"] = []
            tokens, mask, state = x, attn_mask, h
            for block in self.mtp:
                # position i reads token i + 1: shift left, the last column masked
                tokens = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
                mask = mask * jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
                state = block(state, self.embed(tokens, positions), train_bias(cfg, mask), positions, mask)
                caps["mtp"].append(self.unembed(state)[0])
        return logits, h_final, caps

    def decode_step(
        self,
        x: jnp.ndarray,  # [b, t] token ids
        cache: Dict[str, Any],
        token_mask: Optional[jnp.ndarray],  # [b, t] validity of these positions
        is_prefill: bool = False,
        *,
        capture_split: Optional[int] = None,
        attn_kernel: Optional[str] = None,  # paged read path: None | "pallas" | "interpret"
        head_at: Optional[jnp.ndarray] = None,  # [b] the one of the t new columns a row reads
    ):
        """The one cached step: every block over t new positions against the cache, which
        is moved on. Returns (logits, h_final, new_cache), and the activation ENTERING block
        `capture_split` (the same hydra split point as __call__'s h_split) as a fourth when that is
        given. A caller that reads one position a row (an admission: the prompt's
        last token) says which with `head_at`: the final norm and the head then run over that
        position alone, and logits and h_final come back one position wide.

        The cache pytree says which family it is. Both carry mask [b, S], pos [b] (next position id
        per row) and layers (K/V tables for an attention layer, the convolution's last inputs for a
        `conv` one):

        - **`index`** (a scalar write offset; `init_kv_cache`): every row writes at the same
          column, the fused sampler's cache. With `is_prefill` the block is a left-padded prompt
          (positions from its own mask, causal within the block); under prompt tuning the prefill
          prepends the soft prompt into the cache (init_kv_cache reserves the extra slots) and
          logits keep the caller's sequence length. With a scalar **`first`** beside it (the
          sampler's prefill by blocks): `live_widths`.
        - **`row_index`** ([b]): every row carries its OWN write offset — the continuous-batching
          slot pool and the paged arena (trlx_tpu/inference/engine.py). Rows
          sit at different depths, which the shared scalar cannot express; for a live row the
          computation is bit-identical to the scalar one on an aligned batch, because masked
          cache columns contribute exactly 0.0 to every softmax sum wherever they sit (exp(-1e9)
          == 0.0 in f32). t == 1 is a decode step: a row whose token_mask is 0 writes a 0 into
          the mask at its current column — a value-level no-op — and does not advance. t > 1 is a
          RIGHT-padded prefill: row r's valid tokens occupy columns [row_index_r, row_index_r +
          len_r), a nonzero row_index resumes behind a shared prefix already resident in the
          cache (prefix-cache hit) whose mask bits the caller seeds, and the pad positions write
          nothing the model can see (mask bit 0; paged arena writes are dropped via the mask)."""
        cfg = self.cfg
        b, t = x.shape[:2]
        per_row = "row_index" in cache
        looped = cfg.loop_steps > 1
        if looped and capture_split is not None:
            raise NotImplementedError(
                "a looped stack (loop_steps > 1) runs whole: a cached step capturing a layer inside it "
                "(the hydra split) is not supported")
        if per_row and (cfg.prompt_tokens > 0 or cfg.prefix_tokens > 0):
            raise NotImplementedError(
                "a per-row cache (slot pool, paged insert) under prompt/prefix tuning is unsupported")
        if capture_split is not None and cfg.prompt_tokens > 0:
            raise NotImplementedError(
                "split-activation capture under prompt tuning is unsupported "
                "(the soft prompt widens the captured rows)"
            )
        P = cfg.prompt_tokens if is_prefill else 0
        if P > 0:
            token_mask = jnp.concatenate(
                [jnp.ones((b, P), token_mask.dtype), token_mask], axis=1
            )
            t = t + P

        # the one place that moves the cache on: the mask and where the new
        # positions are here (the bias reads both), the counters at the end
        mask_dtype = cache["mask"].dtype
        block_start = None
        if not per_row:
            offset = cache["index"]
            if is_prefill:
                positions = position_ids(token_mask)
                next_pos = token_mask.sum(-1).astype(jnp.int32)
                if "first" in cache:  # a block behind others: as the per-row prefill below
                    positions, next_pos = cache["pos"][:, None] + positions, cache["pos"] + next_pos
                block_start = offset
            else:
                positions = cache["pos"][:, None]
                next_pos = cache["pos"] + token_mask[:, 0].astype(jnp.int32)
            new_mask = jax.lax.dynamic_update_slice(
                cache["mask"], token_mask.astype(mask_dtype), (0, offset)
            )
        elif t == 1:
            offset = cache["row_index"]
            positions = cache["pos"][:, None]
            advance = token_mask[:, 0].astype(jnp.int32)
            new_mask = cache["mask"].at[jnp.arange(b), offset].set(
                token_mask[:, 0].astype(mask_dtype)
            )
        else:
            offset = block_start = cache["row_index"]
            advance = token_mask.sum(-1).astype(jnp.int32)
            positions = cache["pos"][:, None] + position_ids(token_mask)
            S = cache["mask"].shape[-1]
            cols = offset[:, None] + jnp.arange(t)[None, :]  # [b, t]
            # pad columns land on already-zero cells (or clip to S-1, also
            # zero until decode begins), so the scatter of their 0 is a no-op
            new_mask = cache["mask"].at[
                jnp.arange(b)[:, None], jnp.clip(cols, 0, S - 1)
            ].set(token_mask.astype(mask_dtype))
        bias = cached_bias(cfg, new_mask, positions, block_start)
        layers = cache["layers"]
        if "first" in cache and not is_prefill and len(live_widths(new_mask.shape[-1])) > 1:
            live = live_width_index(cache["first"], new_mask.shape[-1])
            layers = [{**layer, "live": live} for layer in layers]

        if P > 0:
            h = jnp.concatenate(
                [self._embed_soft_prompt(b, positions[:, :P]),
                 self.embed(x, positions[:, P:])],
                axis=1,
            )
        else:
            h = self.embed(x, positions)
        if per_row:
            # the mask gates PAGED arena writes (inactive rows scatter out
            # of bounds and are dropped); the dense cached path never reads
            # it, so fixed-pool graphs are unchanged
            step_mask = token_mask
        else:
            # the dense-cache attention never reads it; convolution state
            # and sparse experts do
            step_mask = token_mask if cfg.blocks_read_token_mask else None
            if is_prefill and prefill_fuses(cfg, t):
                step_mask, attn_kernel = token_mask, "prefill"
        # cache layer indices are absolute, so the segments' new layers
        # concatenate exactly
        bounds = () if looped else sorted({0, cfg.n_layers, capture_split} - {None})  # a looped stack runs whole
        new_layers, h_cap = [], None
        if looped:
            h, new_layers, gates = self.run_passes(h, bias, positions, cache=layers, cache_index=offset,
                                                   attn_mask=step_mask, attn_kernel=attn_kernel)
            # the mean share of a live position that the gate would have let leave before the last pass
            live = jnp.ones((b, t), jnp.float32) if token_mask is None else token_mask.astype(jnp.float32)
            early = 1.0 - exit_distribution(gates)[..., -1]
            self.sow("loop_stats", "exit_early", (early * live).sum() / jnp.maximum(live.sum(), 1.0))
        for s, e in zip(bounds, bounds[1:]):
            if s == capture_split:
                h_cap = h
            h, segment = self.run_blocks(
                h, bias, positions, s, e, cache=layers,
                cache_index=offset, attn_mask=step_mask, attn_kernel=attn_kernel,
            )
            new_layers += segment
        if cfg.n_layers == capture_split:
            h_cap = h
        h = h[:, P:] if P > 0 else h
        if head_at is not None:
            h = jnp.take_along_axis(h, head_at[:, None, None], axis=1)
        logits, h = self.unembed(h, normed=looped)
        if not per_row:
            counters = {"index": offset + t, "pos": next_pos}
            if "first" in cache:
                counters["first"] = cache["first"]
        else:
            counters = {"row_index": cache["row_index"] + advance, "pos": cache["pos"] + advance}
        new_cache = {**counters, "mask": new_mask, "layers": new_layers}
        if capture_split is not None:
            return logits, h, new_cache, h_cap
        return logits, h, new_cache


def exit_distribution(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """A looped stack's exit distribution, `[passes, ...]` gate logits -> `[..., passes]` float32:
    with lam_t = sigmoid(logit_t), p_t = lam_t prod_{j<t}(1 - lam_j) before the last pass and
    p_last = prod_{j<last}(1 - lam_j), what no earlier pass let go (the last pass's own gate
    decides nothing). The shares sum to 1. The one place the rule is written."""
    lam = jax.nn.sigmoid(gate_logits.astype(jnp.float32))[:-1]
    stays = jnp.cumprod(1.0 - lam, axis=0)  # [passes - 1, ...]: still there after pass t
    before = jnp.concatenate([jnp.ones_like(stays[:1]), stays[:-1]])
    return jnp.moveaxis(jnp.concatenate([lam * before, stays[-1:]]), 0, -1)


def position_ids(attn_mask: jnp.ndarray) -> jnp.ndarray:
    """Position ids robust to left padding: cumsum of the mask - 1, clipped
    (mirrors the reference's position_ids computation,
    accelerate_ppo_trainer.py:176-180)."""
    return jnp.clip(jnp.cumsum(attn_mask.astype(jnp.int32), axis=-1) - 1, 0, None)


def dense_attention(q, k, v, attn_bias, nkv: int, dtype):
    """Softmax attention as two plain products: q [b, t, nh, hd] over k, v
    [b, S, nkv, hd] under an additive bias [b, 1 | nh, t, S] -> [b, t, nh, hd]
    (`Attention`'s path wherever no fused kernel runs)."""
    b, t, nh, hd = q.shape
    qk, pv = "bthd,bshd->bhts", "bhts,bshd->bthd"
    if nkv != nh:
        # GQA/MQA: K/V stay at n_kv_heads — query head h reads kv
        # head h // g, so the g query heads of a kv head are one more
        # axis of the same two products and the cache is read once
        # for all of them, never repeated to n_heads.
        g = nh // nkv
        q = q.reshape(b, t, nkv, g, hd)
        qk, pv = "btkgd,bskd->bkgts", "bkgts,bskd->btkgd"
        # [b, 1, t, S] broadcasts over both head axes; a per-head
        # bias (ALiBi's [b, nh, 1, S]) splits its heads as q did
        attn_bias = (
            attn_bias[:, :, None] if attn_bias.shape[1] == 1
            else attn_bias.reshape(attn_bias.shape[0], nkv, g, *attn_bias.shape[2:]))
    scale = 1.0 / np.sqrt(hd)
    # [b, h, t, S] ([b, nkv, g, t, S]) — accumulate scores in f32 for stability.
    scores = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32) * scale
    scores = scores + attn_bias  # bias is f32, -inf on masked
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum(pv, probs, v)


def prefill_by_blocks(cfg: TransformerConfig, t: int, block: int) -> bool:
    """Whether the sampler prefills a left-padded prompt of t columns a block
    of `block` columns at a time, from the first block that holds a token
    (`ops/sampling.py`): the prompt is at least two blocks, a block's step
    scores against the cache (the fused prefill attends within the block
    into an EMPTY cache), and every layer keeps columns of keys and values
    and nothing a row (a convolution's or a recurrence's state is carried
    through padding by its own rule), with no soft prompt or prefix in front."""
    return (t >= 2 * block and not prefill_fuses(cfg, t) and not prefill_fuses(cfg, block)
            and not cfg.has_slot_state and not cfg.has_latent_layers
            and cfg.prompt_tokens == 0 and cfg.prefix_tokens == 0)


def live_widths(columns: int) -> Tuple[int, ...]:
    """The suffix widths a decode step over a `first` cache of that many
    columns chooses from, ascending: 2, 3, 4, 6 and 8 eighths of it in whole
    groups of 8 columns (256 / 384 / 512 / 768 / 1,024 of 1,024). Each is a
    branch of every attention layer's conditional, so they are few, and no
    step reads more than 1.5 times what it needs.

    A `first` cache is a scalar-`index` cache (`decode_step`) that carries
    `first`, a scalar: no row has a token in a column before it (the
    sampler's prefill by blocks, `prefill_by_blocks`). It may start at any
    column up to `first`; a prefill block appended behind others continues
    each row's positions from `pos`; and a decode step attends over the
    narrowest of these widths that starts at or before `first` and no other
    column (none chosen where there is one width): the columns in front hold
    no token, so the result is the same and the cache is read that much less."""
    return tuple(sorted({min(-(-columns * n // 64) * 8, columns) for n in (2, 3, 4, 6, 8)}))


def live_width_index(first, columns: int):
    """Index into `live_widths(columns)` of the narrowest suffix that starts
    at or before column `first` (a host integer, or a traced scalar): the
    count of the widths that start behind it, which are the narrower ones."""
    return sum((columns - w > first) * 1 for w in live_widths(columns)[:-1])


# Queries a banded or a sparse latent layer's prefill into an empty cache takes
# at once (`prefill_by_query_blocks`): a block's per-head queries (128 heads of
# 192: 100 MB) and its index scores against a prompt of 24,576 (200 MB) are
# what exists at once, where the whole prompt's would be 1.2 and 2.4 GB. A
# sparse block attends `PREFILL_HEAD_GROUP` heads at a time over their keys and
# values of the whole prompt (16 heads of 128 + 128 over 24,576: 201 MB).
PREFILL_QUERY_BLOCK = 2048
PREFILL_HEAD_GROUP = 16


def prefill_by_query_blocks(spec: LatentSpec, *, c_q, c, k_rope, index_k, h, positions, mask, gate,
                            w_kvb, queries, project_out, index, scale):
    """`LatentAttention`'s prefill into an empty cache for a banded or a
    sparse layer, `PREFILL_QUERY_BLOCK` queries at a time over the prompt's
    latents (`c`, `k_rope`, `index_k`: whole, they are small). A banded block
    decompresses keys and values for the columns its band reaches (the
    block's own and `window - 1` in front, from a tile's edge) and runs the
    banded fused forward over them; a sparse block scores the prompt with the
    index, takes the chosen (`chosen_in_block`) and attends PER HEAD under
    their mask (`masked_latent_attention_by_groups`), `PREFILL_HEAD_GROUP`
    heads at a time over keys and values decompressed from the prompt's
    latents for that group: a (query, key, head) pair costs 2 x (dn + dr + dv)
    operations and not the absorbed form's 2 x (2 dc + dr), and no key or
    value of all heads exists at once. Causal structure goes by column, which
    is position in a prompt without holes.

    Every block has the same shapes (a band's reach is cut from a prompt
    padded by that reach in front; a sparse block is handed the whole
    prompt's index keys and latents and where it stands, and its kernels
    pass over the tiles behind it), so the blocks are ONE traced body run in
    turn (`jax.lax.map`), and so are a sparse block's groups of heads: a
    prompt of 12 blocks traces, lowers and compiles what a prompt of one
    does."""
    from trlx_tpu.ops import sparse_attention as sparse
    from trlx_tpu.ops.attention import flash_attention

    t = c.shape[1]
    dn = spec.qk_nope_head_dim
    block = min(PREFILL_QUERY_BLOCK, t)
    n_blocks = -(-t // block)
    # columns in front of a band's block: its reach, from a tile's edge where the block is made of tiles
    reach = 0 if spec.window is None else spec.window - 1
    front = -(-reach // 128) * 128 if block % 128 == 0 else reach
    ends = (front, n_blocks * block - t)
    padded = lambda x: x if x is None or ends == (0, 0) else jnp.pad(x, ((0, 0), ends) + ((0, 0),) * (x.ndim - 2))
    cut = lambda x, s, n: jax.lax.dynamic_slice_in_dim(x, s, n, axis=1)
    c_q, positions, mask, gate, c, k_rope = (padded(x) for x in (c_q, positions, mask, gate, c, k_rope))

    if spec.window is not None:
        def attend(s):  # the block's columns s .. s + block of the prompt lie `front` further in the padded arrays
            span = lambda x: cut(x, s, front + block)
            q_nope, q_rope = queries(span(c_q), span(positions))
            kv = jnp.einsum("btc,chm->bthm", span(c), w_kvb)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(span(k_rope), (*q_rope.shape[:3], k_rope.shape[-1]))], axis=-1)
            return flash_attention(jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., dn:], mask=span(mask),
                                   causal=True, window=spec.window)[:, front:]
    else:
        h, index_k = padded(h), padded(index_k)
        nh = w_kvb.shape[1]
        group = PREFILL_HEAD_GROUP if nh % PREFILL_HEAD_GROUP == 0 else nh

        def attend(s):
            rows = lambda x: cut(x, s, block)
            allow = sparse.chosen_in_block(*index.queries(rows(c_q), rows(h), rows(positions)), index_k, mask,
                                           first=s, topk=spec.index_topk)
            return sparse.masked_latent_attention_by_groups(
                *queries(rows(c_q), rows(positions)), c, k_rope[:, :, 0], w_kvb, allow, group=group, scale=scale,
                first=s)

    def one(j):
        s = j * block
        return project_out(attend(s).astype(c.dtype), None if gate is None else cut(gate, front + s, block))

    if n_blocks == 1:
        return one(jnp.int32(0))[:, :t]
    outs = jax.lax.map(one, jnp.arange(n_blocks, dtype=jnp.int32))  # [blocks, b, block, d]
    return jnp.moveaxis(outs, 0, 1).reshape(outs.shape[1], n_blocks * block, -1)[:, :t]


def slot_state_of(cfg) -> str:
    """What a refusal over slot state names, from what the layers keep
    (`LayerKeeps.slot`): "conv / linear_attention layers keep conv, state, tails a slot"."""
    kinds = getattr(cfg, "slot_state_kinds", ())
    names = dict.fromkeys(name for kind in kinds for name in cfg.kind_keeps(kind).slot_names)
    return f"{' / '.join(kinds)} layers keep {', '.join(names)} a slot"


def init_kv_cache(cfg: TransformerConfig, batch_size: int, max_len: int, dtype=None):
    """Allocate an empty functional cache from what each layer keeps
    (`cfg.layer_keeps`): its planes a token, `[batch, max_len, *shape]`, and
    its arrays a row, `[batch, *shape]`. Under prompt tuning the soft prompt
    occupies the first cfg.prompt_tokens cache slots (written by the
    prefill), so the cache is allocated that much longer."""
    dtype = dtype or cfg.dtype
    max_len = max_len + getattr(cfg, "prompt_tokens", 0)

    def layer(i):
        keeps = cfg.layer_keeps(i)
        passes = () if keeps.passes == 1 else (keeps.passes,)  # a looped stack: a plane a pass, `[passes, batch, ...]`
        planes = {name: jnp.zeros((*passes, batch_size, max_len, *shape), dtype=dtype) for name, shape in keeps.token}
        return {**planes, **keeps.slot_arrays(batch_size, dtype)}

    return {
        "index": jnp.asarray(0, dtype=jnp.int32),
        "mask": jnp.zeros((batch_size, max_len), dtype=jnp.int32),
        "pos": jnp.zeros((batch_size,), dtype=jnp.int32),
        "layers": [layer(i) for i in range(cfg.n_layers)],
    }


def init_paged_kv_arena(
    cfg: TransformerConfig, num_blocks: int, block_size: int, dtype=None, num_slots: int = 0
):
    """Allocate what each layer of a paged pool keeps (`cfg.layer_keeps`):
    for its planes a token, an arena of `num_blocks` blocks of `block_size`
    token columns shared by every slot through per-row block tables
    (Attention's paged branch; a latent layer's is one plane of its kind's
    `LatentSpec.width` values a token, and a sparse one's index keys in a
    plane beside it, in a floating type only); for its
    arrays a row, `[num_slots, *shape]`, a slot's own. Block 0 is reserved by the
    engine as a permanent zero block backing padding table entries, so it
    is never allocated to a request. int8 arenas carry f32 scale planes
    (per token per kv head, ops/quant.quantize_kv)."""
    dtype = dtype or cfg.dtype
    if getattr(cfg, "prompt_tokens", 0) or getattr(cfg, "prefix_tokens", 0):
        raise NotImplementedError(
            "paged KV cache under prompt/prefix tuning is unsupported"
        )
    if cfg.has_slot_state and (num_slots <= 0 or jnp.dtype(dtype) == jnp.int8):
        raise NotImplementedError(
            f"a paged pool over slot state ({slot_state_of(cfg)}) needs its number of slots "
            "and a floating cache type (an int8 arena would hold the rows' state in int8 too)")
    from trlx_tpu.ops.paged_attention import init_paged_latent_layer, init_paged_layer, init_paged_plane

    def layer(i):
        keeps, latent = cfg.layer_keeps(i), cfg.latent_of(cfg.layer_op(i))
        if latent is not None:
            # the latent plane (two tokens a row), and any other plane the kind keeps a token, plain
            arena = init_paged_latent_layer(num_blocks, block_size, latent.width, dtype)
            arena.update({name: init_paged_plane(num_blocks, block_size, *shape, dtype)
                          for name, shape in keeps.token if name != "latent"})
        elif keeps.token:  # a looped stack's layer: a pool a pass, end to end, under ONE table (`pass_table`)
            arena = init_paged_layer(num_blocks * keeps.passes, block_size, cfg.kv_heads, cfg.head_dim, dtype)
        else:
            arena = {}
        return {**arena, **keeps.slot_arrays(num_slots, dtype)}

    return [layer(i) for i in range(cfg.n_layers)]


# ---------------------------------------------------------------------------
# Model family presets
# ---------------------------------------------------------------------------

# Falcon-H1-34B-Instruct's published forward multipliers (its test preset keeps them)
_FALCON_H1_34B_MULTIPLIERS = Multipliers(
    embedding=5.656854249492381, lm_head=0.0078125, attention_in=1.0, attention_out=0.0375,
    key=0.011048543456039804, ssm_in=0.25, ssm_out=0.08838834764831845,
    ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738),
    mlp=(0.1767766952966369, 0.011160714285714284))

PRESETS: Dict[str, Dict[str, Any]] = {
    # tiny from-scratch models for tests/benchmarks ("random:" prefix)
    "gpt2-tiny": dict(d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256),
    "gpt2-small": dict(d_model=768, n_layers=12, n_heads=12, d_ff=3072, max_seq_len=1024),
    "gpt2-medium": dict(d_model=1024, n_layers=24, n_heads=16, d_ff=4096, max_seq_len=1024),
    "gpt2-large": dict(d_model=1280, n_layers=36, n_heads=20, d_ff=5120, max_seq_len=1024),
    "gpt2-xl": dict(d_model=1600, n_layers=48, n_heads=25, d_ff=6400, max_seq_len=1024),
    "llama-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=256,
        pos_embed="rope", norm="rmsnorm", activation="silu", glu=True,
        tie_embeddings=False, use_bias=False,
    ),
    "llama-7b": dict(
        d_model=4096, n_layers=32, n_heads=32, d_ff=11008, max_seq_len=4096,
        pos_embed="rope", norm="rmsnorm", activation="silu", glu=True,
        tie_embeddings=False, use_bias=False,
    ),
    # GPT-NeoX / pythia family (HH-RLHF suite, examples/hh/ppo_hh.py:71-107)
    "neox-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256,
        pos_embed="rope", rotary_pct=0.25, activation="gelu_exact",
        parallel_residual=True, tie_embeddings=False,
    ),
    "pythia-160m": dict(
        d_model=768, n_layers=12, n_heads=12, d_ff=3072, max_seq_len=2048,
        pos_embed="rope", rotary_pct=0.25, activation="gelu_exact",
        parallel_residual=True, tie_embeddings=False,
    ),
    "pythia-1.4b": dict(
        d_model=2048, n_layers=24, n_heads=16, d_ff=8192, max_seq_len=2048,
        pos_embed="rope", rotary_pct=0.25, activation="gelu_exact",
        parallel_residual=True, tie_embeddings=False,
    ),
    "pythia-6.9b": dict(
        d_model=4096, n_layers=32, n_heads=32, d_ff=16384, max_seq_len=2048,
        pos_embed="rope", rotary_pct=0.25, activation="gelu_exact",
        parallel_residual=True, tie_embeddings=False,
    ),
    # GPT-J-6B (HH examples default model, examples/hh/ppo_hh.py:96-100)
    "gptj-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256,
        pos_embed="rope", rotary_pct=0.5, parallel_residual=True, shared_ln=True,
        tie_embeddings=False, attn_bias=False, lm_head_bias=True,
    ),
    "gptj-6b": dict(
        d_model=4096, n_layers=28, n_heads=16, d_ff=16384, max_seq_len=2048,
        pos_embed="rope", rotary_pct=0.25, parallel_residual=True, shared_ln=True,
        tie_embeddings=False, attn_bias=False, lm_head_bias=True,
    ),
    # OPT family (OPTModelBranch, modeling_ppo.py:689-813)
    "opt-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256,
        activation="relu", pos_offset=2,
    ),
    "opt-125m": dict(
        d_model=768, n_layers=12, n_heads=12, d_ff=3072, max_seq_len=2048,
        activation="relu", pos_offset=2,
    ),
    # Bloom family (BloomModelBranch, modeling_ppo.py:816-929)
    "bloom-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256,
        pos_embed="none", alibi=True, embed_ln=True,
    ),
    "bloom-560m": dict(
        d_model=1024, n_layers=24, n_heads=16, d_ff=4096, max_seq_len=2048,
        pos_embed="none", alibi=True, embed_ln=True,
    ),
    # GPTBigCode / starcoder (MQA, GPTBigCodeModelBranch, modeling_ppo.py:1079-1222)
    "bigcode-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=1, d_ff=256, max_seq_len=256,
    ),
    # LFM2-8B-A1B (LiquidAI, `lfm2_moe`): gated short convolutions and GQA
    # attention with QK-norm in one stack, 2 leading dense layers, then 32
    # sigmoid-routed experts (4 a token) of width 1792. The published sizes;
    # a cut (depth, experts held here, vocabulary) arrives as
    # model_extra_configs (`n_layers` cuts `layer_types` to its first n).
    "lfm2-8b-a1b": dict(
        d_model=2048, n_layers=24, n_heads=32, n_kv_heads=8, d_ff=7168, max_seq_len=128000,
        pos_embed="rope", rope_theta=1e6, norm="rmsnorm", activation="silu", glu=True,
        use_bias=False, qk_norm=True, conv_kernel=3, flash_prefill=True,
        layer_types=tuple("attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)),
        moe_experts=32, moe_top_k=4, moe_d_ff=1792, moe_dense_layers=2, moe_router="sigmoid",
    ),
    # the same stack at test size: one period behind the dense layers, 4
    # experts (2 a token)
    "lfm2-tiny": dict(
        d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=1e6, norm="rmsnorm", activation="silu", glu=True,
        use_bias=False, qk_norm=True, conv_kernel=3, flash_prefill=True,
        layer_types=("conv", "conv", "attention", "conv", "conv", "conv"),
        moe_experts=4, moe_top_k=2, moe_d_ff=32, moe_dense_layers=2, moe_router="sigmoid",
    ),
    # Laguna-XS.2 (poolside, `laguna`; 33.4B parameters, ~3B active): period
    # of one full-attention layer (48 query heads; YaRN over half the head
    # width) and three sliding ones (64 query heads; window 512, plain rotary)
    # over 8 K/V heads of 128, a per-head sigmoid gate on the attention
    # output, one dense SwiGLU layer, then 256 sigmoid-routed experts (8 a
    # token, scaled 2.5) beside one shared expert. The published sizes; a cut
    # (depth, experts held here) arrives as model_extra_configs.
    "laguna-xs.2": dict(
        d_model=2048, n_layers=40, n_heads=48, n_kv_heads=8, head_width=128, d_ff=8192,
        max_seq_len=262144, pos_embed="rope", norm="rmsnorm", layer_norm_epsilon=1e-6,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        sliding_window=512, attn_gate="per_head",
        layer_types=("full_attention", "sliding_attention", "sliding_attention", "sliding_attention") * 10,
        layer_heads=(48, 64, 64, 64) * 10,
        rope_kinds=(
            ("full_attention", RopeSpec(theta=500000.0, pct=0.5, yarn_factor=64.0, yarn_original_max=4096,
                                        yarn_beta_fast=64.0, yarn_beta_slow=1.0,
                                        attention_factor=1.4158883083359672)),
            ("sliding_attention", RopeSpec(theta=10000.0, pct=1.0)),
        ),
        moe_experts=256, moe_top_k=8, moe_d_ff=512, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=512, moe_routed_scale=2.5,
    ),
    # the same stack at test size: one period, groups of 3 and 4 query heads
    # a K/V head, a window shorter than the test sequences, 8 experts (2 a
    # token)
    "laguna-tiny": dict(
        d_model=64, n_layers=4, n_heads=6, n_kv_heads=2, head_width=16, d_ff=128, max_seq_len=256,
        pos_embed="rope", norm="rmsnorm", layer_norm_epsilon=1e-6,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        sliding_window=8, attn_gate="per_head",
        layer_types=("full_attention", "sliding_attention", "sliding_attention", "sliding_attention"),
        layer_heads=(6, 8, 8, 8),
        rope_kinds=(
            ("full_attention", RopeSpec(theta=500000.0, pct=0.5, yarn_factor=64.0, yarn_original_max=16,
                                        yarn_beta_fast=64.0, yarn_beta_slow=1.0,
                                        attention_factor=1.4158883083359672)),
            ("sliding_attention", RopeSpec(theta=10000.0, pct=1.0)),
        ),
        moe_experts=8, moe_top_k=2, moe_d_ff=32, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=32, moe_routed_scale=2.5,
    ),
    # openPangu-Ultra-MoE-718B (`pangu_ultra_moe`; 718B parameters, ~39B
    # active): latent attention in every layer (128 heads of 128 + 64 against
    # values of 128, a latent of 512 + 64 rotary dimensions a token), a norm
    # after attention and after the feed-forward too, 3 dense SwiGLU layers,
    # then 256 sigmoid-routed experts (8 a token, normalised, scaled 2.5)
    # beside one shared expert, one multi-token-prediction block. The
    # published sizes; a cut (depth, dense layers, experts held here,
    # vocabulary, the multi-token block) arrives as model_extra_configs.
    "openpangu-ultra-moe-718b": dict(
        d_model=7680, n_layers=61, n_heads=128, d_ff=18432, max_seq_len=131072,
        pos_embed="rope", rope_theta=25600000.0, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("latent_attention",) * 61, sandwich_norm=True, mtp_layers=1,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_experts=256, moe_top_k=8, moe_d_ff=2048, moe_dense_layers=3, moe_router="sigmoid",
        moe_shared_d_ff=2048, moe_routed_scale=2.5,
    ),
    # the same stack at test size: one dense and three expert layers, 8
    # experts (2 a token) beside a shared one, 4 heads, widths that keep
    # qk_nope != qk_rope != v; no multi-token block unless a test asks
    "openpangu-ultra-moe-tiny": dict(
        d_model=64, n_layers=4, n_heads=4, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=25600000.0, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("latent_attention",) * 4, sandwich_norm=True,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        moe_experts=8, moe_top_k=2, moe_d_ff=32, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=32, moe_routed_scale=2.5,
    ),
    # Ling-3.0-flash-VL's language model (inclusionAI; ~125B parameters, ~5.5B
    # active): periods of five Kimi-delta linear-attention layers (32 heads of
    # 128, convolutions of 4 taps, a log-decay a key channel bounded below by
    # -5) and one latent-attention layer (a full-rank query of 128 + 64, a
    # latent of 512 + 64 rotary dimensions a token, RMSNorm on query and
    # rotary key), a per-head sigmoid gate on both; 2 dense SwiGLU layers, then
    # 512 sigmoid-routed experts in 8 groups (4 groups and 8 experts a token,
    # normalised, scaled 2.5) beside one shared expert. The published sizes; a
    # cut (depth, dense layers, experts held here, vocabulary) arrives as
    # model_extra_configs. The vision tower is not part of this preset.
    "ling-3.0-flash-vl": dict(
        d_model=2560, n_layers=42, n_heads=32, head_width=128, d_ff=6144, max_seq_len=131072,
        pos_embed="rope", rope_theta=6000000.0, norm="rmsnorm", layer_norm_epsilon=1e-6,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=tuple("latent_attention" if (i + 1) % 6 == 0 else "linear_attention" for i in range(42)),
        q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        qk_norm=True, attn_gate="per_head", conv_kernel=4, kda_lower_bound=-5.0,
        moe_experts=512, moe_top_k=8, moe_d_ff=768, moe_dense_layers=2, moe_router="sigmoid",
        moe_shared_d_ff=768, moe_routed_scale=2.5, moe_n_group=8, moe_topk_group=4,
    ),
    # the same stack at test size: one period of three (two linear layers, one
    # latent), one dense layer, 16 experts in 4 groups (2 groups and 2 experts a
    # token) beside a shared one, 4 heads of 16
    "ling-flash-tiny": dict(
        d_model=64, n_layers=3, n_heads=4, head_width=16, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=6000000.0, norm="rmsnorm", layer_norm_epsilon=1e-6,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("linear_attention", "linear_attention", "latent_attention"),
        q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        qk_norm=True, attn_gate="per_head", conv_kernel=4, kda_lower_bound=-5.0,
        moe_experts=16, moe_top_k=2, moe_d_ff=32, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=32, moe_routed_scale=2.5, moe_n_group=4, moe_topk_group=2,
    ),
    # Solar-Open2-250B (upstage, `solar_open2`; 250B parameters, ~15B active):
    # periods of one GQA layer (64 query heads over 8 K/V heads of 128, NO
    # positions of any kind, an elementwise sigmoid gate on the attention
    # output) and three Kimi-delta layers in the published Kimi Linear form (64
    # heads of 128, convolutions of 4 taps, an unbounded softplus log-decay
    # through a low-rank pair, write strengths up to 2, an elementwise low-rank
    # output gate); experts in every layer: 320 sigmoid-routed (8 a token,
    # normalised, no groups) beside one shared expert. The published sizes; a
    # cut (depth, experts held here, vocabulary) arrives as model_extra_configs.
    "solar-open2-250b": dict(
        d_model=4096, n_layers=48, n_heads=64, n_kv_heads=8, head_width=128, d_ff=10240, max_seq_len=1048576,
        pos_embed="none", norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=tuple("attention" if i % 4 == 0 else "linear_attention" for i in range(48)),
        attn_gate="elementwise", conv_kernel=4, kda_decay="softplus", kda_gate_rank=128, kda_beta_max=2.0,
        moe_experts=320, moe_top_k=8, moe_d_ff=1280, moe_dense_layers=0, moe_router="sigmoid",
        moe_shared_d_ff=1280, moe_routed_scale=1.0,
    ),
    # the same stack at test size: one period, 4 query heads over 2 K/V heads
    # of 16, 16 experts (2 a token) beside a shared one
    "solar-open2-tiny": dict(
        d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_width=16, d_ff=128, max_seq_len=256,
        pos_embed="none", norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("attention", "linear_attention", "linear_attention", "linear_attention"),
        attn_gate="elementwise", conv_kernel=4, kda_decay="softplus", kda_gate_rank=16, kda_beta_max=2.0,
        moe_experts=16, moe_top_k=2, moe_d_ff=32, moe_dense_layers=0, moe_router="sigmoid",
        moe_shared_d_ff=32, moe_routed_scale=1.0,
    ),
    # Falcon-H1-34B-Instruct (tiiuae, `falcon_h1`; 33.6B parameters, dense): in
    # EVERY block a Mamba-2 mixer (32 heads of 128, a state of 256, B and C in 2
    # groups, a convolution of 4 taps with a bias) beside GQA attention (20 query
    # heads over 4 K/V heads of 128: a query width of 2,560 against d_model 5,120,
    # a group of 5; rotate-half RoPE, theta 1e11), their outputs summed into one
    # residual add; a SwiGLU of 21,504; muP forward multipliers on the embedding,
    # both branches' inputs and outputs, the keys, the SSM projection's five
    # column groups, the MLP's gate and output, and the logits. The published
    # sizes; a cut in depth arrives as model_extra_configs.
    "falcon-h1-34b": dict(
        d_model=5120, n_layers=72, n_heads=20, n_kv_heads=4, head_width=128, d_ff=21504, max_seq_len=262144,
        pos_embed="rope", rope_theta=1e11, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("ssm_attention",) * 72,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_conv_kernel=4, ssm_chunk=128,
        multipliers=_FALCON_H1_34B_MULTIPLIERS,
    ),
    # the same stack at test size: two blocks, 10 query heads over 2 K/V heads of 8
    # (a group of 5, a query width of 80 against d_model 64), 4 SSM heads of 8 (d_ssm
    # 32) with a state of 16 in 2 groups, chunks of 16 so that a test's prompt crosses
    # chunk edges, the published multipliers
    "falcon-h1-tiny": dict(
        d_model=64, n_layers=2, n_heads=10, n_kv_heads=2, head_width=8, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=1e11, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("ssm_attention",) * 2,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv_kernel=4, ssm_chunk=16,
        multipliers=_FALCON_H1_34B_MULTIPLIERS,
    ),
    # dots3-note-prev's language model (dots-studio, `dots3_note`; 288B parameters,
    # ~17B active): latent attention of TWO shapes in one stack, 13 full layers
    # (128 heads of 128 + 64 over a latent of 512, theta 8e7) on which a learned
    # index (64 heads of 128, one key a token) chooses the 2,048 positions
    # attended to, and 33 banded ones (64 heads of 192 + 64 over a latent of
    # 1,024, window 513, theta 5e4), both latents rescaled, a sigmoid gate a
    # head on both; one dense SwiGLU layer, then 256 sigmoid-routed experts (8 a
    # token, no groups, normalised, scaled 1) beside one shared expert. The
    # published sizes; a cut (depth and its kinds, experts held here, vocabulary)
    # arrives as model_extra_configs. The towers and the multi-token block are
    # not part of this preset.
    "dots3-note-prev": dict(
        d_model=5120, n_layers=46, n_heads=128, d_ff=13824, max_seq_len=524288,
        pos_embed="rope", rope_theta=80000000.0, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=tuple("sparse_latent_attention" if i < 2 or i % 4 == 1 else "sliding_latent_attention"
                          for i in range(46)),
        latent_kinds=(
            ("sparse_latent_attention", LatentSpec(128, 1024, 512, 128, 64, 128, rescale=True,
                                                   index_heads=64, index_head_dim=128, index_topk=2048)),
            ("sliding_latent_attention", LatentSpec(64, 1024, 1024, 192, 64, 128, rescale=True, window=513)),
        ),
        rope_kinds=(("sparse_latent_attention", RopeSpec(theta=80000000.0)),
                    ("sliding_latent_attention", RopeSpec(theta=50000.0))),
        q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        attn_gate="per_head",
        moe_experts=256, moe_top_k=8, moe_d_ff=1536, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=1536, moe_routed_scale=1.0, moe_token_block=4096,
    ),
    # the same stack at test size: the leading dense layer and one period behind
    # it, a window of 5 and an index that keeps 6, both shorter than a test's
    # prompt; 8 experts (2 a token) beside a shared one
    "dots3-note-tiny": dict(
        d_model=64, n_layers=5, n_heads=4, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=80000000.0, norm="rmsnorm", layer_norm_epsilon=1e-5,
        activation="silu", glu=True, tie_embeddings=False, use_bias=False, flash_prefill=True,
        layer_types=("sparse_latent_attention",) + ("sliding_latent_attention",) * 3 + ("sparse_latent_attention",),
        latent_kinds=(
            ("sparse_latent_attention", LatentSpec(4, 24, 32, 16, 8, 12, rescale=True,
                                                   index_heads=4, index_head_dim=16, index_topk=6)),
            ("sliding_latent_attention", LatentSpec(2, 24, 48, 24, 8, 12, rescale=True, window=5)),
        ),
        rope_kinds=(("sparse_latent_attention", RopeSpec(theta=80000000.0)),
                    ("sliding_latent_attention", RopeSpec(theta=50000.0))),
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        attn_gate="per_head",
        moe_experts=8, moe_top_k=2, moe_d_ff=32, moe_dense_layers=1, moe_router="sigmoid",
        moe_shared_d_ff=32, moe_routed_scale=1.0,
    ),
    # SmallThinker-21BA3B-Instruct (PowerInfer, `smallthinker`; 21.5B parameters,
    # 3.3B active): period of one full-attention layer that rotates nothing (NoPE)
    # and three sliding ones (window 4,096, rotary over the whole head) over 4
    # K/V heads of 128 under 28 query heads; in every layer 64 experts of width
    # 768 (6 a token), gated by ReLU, routed by a softmax over the 6 largest
    # logits of the block's INPUT (`moe_route_on`), no bias, no shared expert, no
    # dense layer. The published sizes; a cut in depth arrives as model_extra_configs.
    "smallthinker-21b-a3b": dict(
        d_model=2560, n_layers=52, n_heads=28, n_kv_heads=4, head_width=128, d_ff=768, max_seq_len=16384,
        pos_embed="rope", norm="rmsnorm", layer_norm_epsilon=1e-6, activation="relu", glu=True,
        tie_embeddings=False, use_bias=False, flash_prefill=True, sliding_window=4096,
        layer_types=("full_attention", "sliding_attention", "sliding_attention", "sliding_attention") * 13,
        rope_kinds=(("full_attention", RopeSpec(pct=0.0)), ("sliding_attention", RopeSpec(theta=1500000.0))),
        moe_experts=64, moe_top_k=6, moe_d_ff=768, moe_router="topk_softmax", moe_route_on="block_input",
        moe_token_block=4096,
    ),
    # the same stack at test size: one period, a group of 3 query heads a K/V
    # head, a window shorter than the test sequences, 16 experts (3 a token)
    "smallthinker-tiny": dict(
        d_model=64, n_layers=4, n_heads=6, n_kv_heads=2, head_width=16, d_ff=32, max_seq_len=256,
        pos_embed="rope", norm="rmsnorm", layer_norm_epsilon=1e-6, activation="relu", glu=True,
        tie_embeddings=False, use_bias=False, flash_prefill=True, sliding_window=8,
        layer_types=("full_attention", "sliding_attention", "sliding_attention", "sliding_attention"),
        rope_kinds=(("full_attention", RopeSpec(pct=0.0)), ("sliding_attention", RopeSpec(theta=1500000.0))),
        moe_experts=16, moe_top_k=3, moe_d_ff=32, moe_router="topk_softmax", moe_route_on="block_input",
    ),
    # Ouro LoopLM (ByteDance/Ouro-2.6B `config.json`, `model_type` "ouro"): ONE stack of 48 llama-style
    # layers under sandwich norms, run `total_ut_steps` = 4 times a token over the same weights
    # (`loop_steps`), the final norm after every pass, an exit gate on each pass's output
    "ouro-2.6b": dict(
        d_model=2048, n_layers=48, n_heads=16, n_kv_heads=16, head_width=128, d_ff=5632, max_seq_len=65536,
        pos_embed="rope", rope_theta=1000000.0, norm="rmsnorm", layer_norm_epsilon=1e-6, activation="silu",
        glu=True, tie_embeddings=False, use_bias=False, sandwich_norm=True, loop_steps=4, loop_gate=True,
    ),
    "ouro-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, head_width=16, d_ff=128, max_seq_len=256,
        pos_embed="rope", rope_theta=1000000.0, norm="rmsnorm", layer_norm_epsilon=1e-6, activation="silu",
        glu=True, tie_embeddings=False, use_bias=False, sandwich_norm=True, loop_steps=4, loop_gate=True,
    ),
    # Mixture-of-experts (beyond the reference): experts shard over `tensor`
    "moe-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=256, max_seq_len=256,
        moe_experts=4, moe_top_k=2,
    ),
}


def config_from_preset(name: str, vocab_size: int, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise ValueError(f"Unknown model preset '{name}'. Available: {sorted(PRESETS)}")
    kwargs = dict(PRESETS[name])
    kwargs.update(overrides)
    return TransformerConfig(vocab_size=vocab_size, **cut_to_depth(kwargs, overrides))


def cut_to_depth(kwargs: Dict[str, Any], overrides: Dict[str, Any]) -> Dict[str, Any]:
    """A cut in depth (`n_layers` overridden) keeps the leading layers of the
    published per-layer lists, unless the caller gives a list of its own."""
    for per_layer in ("layer_types", "layer_heads"):
        if kwargs.get(per_layer) and per_layer not in overrides:
            kwargs[per_layer] = tuple(kwargs[per_layer])[: kwargs["n_layers"]]
    return kwargs
