"""HF checkpoint interop: build a TransformerConfig from an HF config.json
and convert torch state dicts into our Flax param pytrees (and back, for
`save_pretrained` export).

Parity: the reference's PreTrainedModelWrapper.from_pretrained /
save_pretrained (trlx/models/modeling_base.py:44-374) and its per-arch
branch classes' weight layouts (trlx/models/modeling_ppo.py:502-1222,
hf_get_branch_class :1598-1637). Conversion runs on torch-cpu; this
environment has no network egress, so only local directories / cached
checkpoints work.

Supported HF architectures: GPT2LMHeadModel, LlamaForCausalLM,
GPTNeoXForCausalLM (pythia), GPTJForCausalLM, OPTForCausalLM,
BloomForCausalLM, GPTBigCodeForCausalLM, and T5ForConditionalGeneration
(t5 v1.0/v1.1, flan-t5, mt5 -> Seq2SeqConfig/Seq2SeqLM).

Rotary conventions: our kernel uses the half-split ("rotate_half") layout.
GPT-J checkpoints use the interleaved ("rotate_every_two") layout, so their
q/k projection columns are permuted within the rotary dims at load time
(and inverse-permuted on export) — numerically exact, no runtime cost.
"""

import json
import os
from typing import Callable, Dict, Tuple

import numpy as np

from trlx_tpu.models.transformer import LatentSpec, Multipliers, RopeSpec, TransformerConfig, cut_to_depth
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _read_hf_config(path: str) -> Dict:
    cfg_path = os.path.join(path, "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            return json.load(f)
    # Fall back to transformers' resolution (hub cache) if available.
    from transformers import AutoConfig

    return AutoConfig.from_pretrained(path).to_dict()


def _family_of(hf: Dict) -> str:
    arch = ((hf.get("architectures") or [""])[0] or "").lower()
    mt = hf.get("model_type", "")
    # exact matches only: UMT5 (per-layer bias tables) and LongT5
    # (local/transient-global attention) have different layouts and would
    # load silently-wrong through the plain-T5 converter
    if mt in ("t5", "mt5") or arch in (
        "t5forconditionalgeneration", "mt5forconditionalgeneration"
    ):
        return "t5"
    if mt == "lfm2_moe" or arch == "lfm2moeforcausallm":
        return "lfm2_moe"
    if mt == "laguna":
        return "laguna"
    if mt == "smallthinker" or ("moe_num_primary_experts" in hf and "sliding_window_layout" in hf):
        return "smallthinker"
    if mt == "pangu_ultra_moe":
        return "pangu_ultra_moe"
    if mt == "ouro" or "total_ut_steps" in hf:
        return "ouro"
    if mt == "dots3_note":
        return "dots3_note"
    if "layer_group_size" in hf and "kda_lower_bound" in hf:  # the published config names no model_type here
        return "ling_flash"
    if mt == "solar_open2":
        return "solar_open2"
    if mt == "falcon_h1":
        return "falcon_h1"
    for fam, keys in (
        ("gpt_bigcode", ("bigcode",)),
        ("gpt_neox", ("neox",)),
        ("gptj", ("gptj",)),
        ("gpt2", ("gpt2",)),
        ("llama", ("llama", "mistral")),
        ("opt", ("optfor",)),
        ("bloom", ("bloom",)),
    ):
        if any(k in arch for k in keys) or mt == fam:
            return fam
    raise ValueError(f"Unsupported HF architecture for conversion: {arch or mt}")


# ---------------------------------------------------------------------------
# Config conversion
# ---------------------------------------------------------------------------


def config_from_hf(path: str, **overrides):
    """Returns a TransformerConfig, or a Seq2SeqConfig for encoder-decoder
    (t5/mt5/flan-t5) checkpoints — callers dispatch on `cfg.is_seq2seq`."""
    hf = _read_hf_config(path)
    fam = _family_of(hf)
    if fam == "t5":
        return _seq2seq_config_from_hf(hf, **overrides)
    if fam == "gpt2":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["n_embd"], n_layers=hf["n_layer"],
            n_heads=hf["n_head"], d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
            max_seq_len=hf["n_positions"], pos_embed="learned", norm="layernorm",
            activation="gelu", glu=False,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            use_bias=True,
            layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        )
    elif fam == "llama":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
            n_kv_heads=hf.get("num_key_value_heads"), d_ff=hf["intermediate_size"],
            max_seq_len=hf.get("max_position_embeddings", 4096), pos_embed="rope",
            norm="rmsnorm", activation="silu", glu=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False,
            rope_theta=hf.get("rope_theta", 10000.0),
            layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6),
            # Mistral: banded causal attention; plain Llama leaves it None
            sliding_window=hf.get("sliding_window"),
        )
    elif fam == "gpt_neox":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
            d_ff=hf["intermediate_size"], max_seq_len=hf["max_position_embeddings"],
            pos_embed="rope", rotary_pct=hf.get("rotary_pct", 1.0),
            rope_theta=hf.get("rotary_emb_base", 10000.0),
            norm="layernorm", activation="gelu_exact" if hf.get("hidden_act", "gelu") == "gelu" else "gelu",
            parallel_residual=bool(hf.get("use_parallel_residual", True)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=True,
            layer_norm_epsilon=hf.get("layer_norm_eps", 1e-5),
        )
    elif fam == "gptj":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["n_embd"], n_layers=hf["n_layer"],
            n_heads=hf["n_head"], d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
            max_seq_len=hf["n_positions"], pos_embed="rope",
            rotary_pct=(hf.get("rotary_dim") or (hf["n_embd"] // hf["n_head"]))
            / (hf["n_embd"] // hf["n_head"]),
            norm="layernorm", activation="gelu",
            parallel_residual=True, shared_ln=True,
            tie_embeddings=False, attn_bias=False, lm_head_bias=True, use_bias=True,
            layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        )
    elif fam == "opt":
        if not hf.get("do_layer_norm_before", True):
            raise ValueError("OPT variants with do_layer_norm_before=False (350m) are unsupported")
        if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
            raise ValueError("OPT word_embed_proj_dim != hidden_size is unsupported")
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
            d_ff=hf["ffn_dim"], max_seq_len=hf["max_position_embeddings"],
            pos_embed="learned", pos_offset=2, norm="layernorm",
            activation="relu" if hf.get("activation_function", "relu") == "relu" else "gelu",
            tie_embeddings=True, use_bias=True,
            layer_norm_epsilon=1e-5,
        )
    elif fam == "bloom":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
            n_layers=hf["n_layer"], n_heads=hf["n_head"], d_ff=4 * hf["hidden_size"],
            max_seq_len=2048, pos_embed="none", alibi=True, embed_ln=True,
            norm="layernorm", activation="gelu", tie_embeddings=True, use_bias=True,
            layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        )
    elif fam == "gpt_bigcode":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["n_embd"], n_layers=hf["n_layer"],
            n_heads=hf["n_head"], n_kv_heads=1 if hf.get("multi_query", True) else None,
            d_ff=hf.get("n_inner") or 4 * hf["n_embd"], max_seq_len=hf["n_positions"],
            pos_embed="learned", norm="layernorm", activation="gelu",
            tie_embeddings=True, use_bias=True,
            layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        )
    elif fam == "lfm2_moe":
        kwargs = dict(
            vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
            n_kv_heads=hf.get("num_key_value_heads"), d_ff=hf["intermediate_size"],
            max_seq_len=hf.get("max_position_embeddings", 128000), pos_embed="rope",
            rope_theta=hf.get("rope_theta", 1e6), norm="rmsnorm", activation="silu", glu=True,
            # not in every published config: the family ties head and embedding
            tie_embeddings=bool(hf.get("tie_embedding", hf.get("tie_word_embeddings", True))),
            use_bias=False, layer_norm_epsilon=hf.get("norm_eps", 1e-5), qk_norm=True, flash_prefill=True,
            conv_kernel=hf.get("conv_L_cache", 3),
            layer_types=tuple("conv" if t == "conv" else "attention" for t in hf["layer_types"]),
            moe_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
            moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=hf.get("num_dense_layers", 0),
            moe_router="sigmoid",
        )
        for key, want in (("conv_bias", False), ("use_expert_bias", True), ("norm_topk_prob", True),
                          ("routed_scaling_factor", 1)):
            if hf.get(key, want) != want:
                raise NotImplementedError(f"lfm2_moe with {key}={hf[key]!r} is not supported")
    elif fam == "laguna":
        kwargs = _laguna_kwargs(hf)
    elif fam == "smallthinker":
        kwargs = _smallthinker_kwargs(hf)
    elif fam == "pangu_ultra_moe":
        kwargs = _pangu_kwargs(hf)
    elif fam == "ouro":
        kwargs = _ouro_kwargs(hf)
    elif fam == "dots3_note":
        kwargs = _dots3_kwargs(hf)
    elif fam == "ling_flash":
        kwargs = _ling_kwargs(hf)
    elif fam == "solar_open2":
        kwargs = _solar_kwargs(hf)
    elif fam == "falcon_h1":
        kwargs = _falcon_h1_kwargs(hf)
    kwargs["hf_family"] = fam
    kwargs.update(overrides)
    return TransformerConfig(**cut_to_depth(kwargs, overrides))


def _laguna_kwargs(hf: Dict) -> Dict:
    """poolside's `laguna` config keys -> TransformerConfig fields. Config
    keys only: the checkpoint's tensor names are not in the public config and
    are not guessed, so `load_params_from_hf` and the export refuse the family
    by name. Three readings the keys do not settle are the model
    configuration's `attn_gate`, `moe_router` / `moe_routed_scale` and
    `qk_norm` (bench/reference/laguna.py has the evidence for each)."""
    def rope(entry):
        yarn = entry.get("rope_type", "default") == "yarn"
        return RopeSpec(
            theta=float(entry["rope_theta"]), pct=float(entry.get("partial_rotary_factor", 1.0)),
            **(dict(yarn_factor=float(entry["factor"]),
                    yarn_original_max=int(entry["original_max_position_embeddings"]),
                    yarn_beta_fast=float(entry.get("beta_fast", 32)), yarn_beta_slow=float(entry.get("beta_slow", 1)),
                    attention_factor=entry.get("attention_factor")) if yarn else {}))

    mlp = list(hf["mlp_layer_types"])
    dense = next((i for i, kind in enumerate(mlp) if kind != "dense"), len(mlp))
    if any(kind == "dense" for kind in mlp[dense:]):
        raise NotImplementedError("laguna with a dense ffn behind a sparse one is not supported")
    for key, want in (("attention_bias", False), ("moe_apply_router_weight_on_input", False),
                      ("moe_router_logit_softcapping", 0)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"laguna with {key}={hf[key]!r} is not supported")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"], head_width=hf["head_dim"],
        d_ff=hf["intermediate_size"], max_seq_len=hf["max_position_embeddings"], pos_embed="rope",
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        sliding_window=hf["sliding_window"], attn_gate="per_head" if hf.get("gating") else "none",
        layer_types=tuple(hf["layer_types"]), layer_heads=tuple(hf["num_attention_heads_per_layer"]),
        rope_kinds=tuple((kind, rope(entry)) for kind, entry in hf["rope_parameters"].items()
                         if isinstance(entry, dict)),
        moe_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=dense, moe_router="sigmoid",
        moe_shared_d_ff=hf.get("shared_expert_intermediate_size", 0),
        moe_routed_scale=float(hf.get("moe_routed_scaling_factor", 1.0)),
    )


def _smallthinker_kwargs(hf: Dict) -> Dict:
    """PowerInfer's `smallthinker` config keys -> TransformerConfig fields. A
    layer is full and unrotated (`sliding_window_layout` 0, `rope_layout` 0) or
    banded and rotated (1, 1): the two layouts name the same layers in every
    published config, and one that did not would need a third kind. What the
    keys do not settle (the router's input, rotate-half, ReGLU as relu * up) is
    bench/configs/smallthinker-21b-a3b.json's `assumed`."""
    window, rope = list(hf["sliding_window_layout"]), list(hf["rope_layout"])
    if window != rope:
        raise NotImplementedError("smallthinker with rope_layout != sliding_window_layout (a banded layer that "
                                  "does not rotate, or a full one that does) is not supported")
    for key, want in (("moe_primary_router_apply_softmax", True), ("norm_topk_prob", True), ("rope_scaling", None)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"smallthinker with {key}={hf[key]!r} is not supported")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"], head_width=hf["head_dim"],
        d_ff=hf["moe_ffn_hidden_size"], max_seq_len=hf["max_position_embeddings"], pos_embed="rope",
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6), activation="relu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        sliding_window=hf["sliding_window_size"],
        layer_types=tuple("sliding_attention" if banded else "full_attention" for banded in window),
        rope_kinds=(("full_attention", RopeSpec(pct=0.0)),
                    ("sliding_attention", RopeSpec(theta=float(hf["rope_theta"])))),
        moe_experts=hf["moe_num_primary_experts"], moe_top_k=hf["moe_num_active_primary_experts"],
        moe_d_ff=hf["moe_ffn_hidden_size"], moe_router="topk_softmax", moe_route_on="block_input",
    )


def _ouro_kwargs(hf: Dict) -> Dict:
    """ByteDance's `ouro` (LoopLM) config keys -> TransformerConfig fields: a
    llama-style stack under sandwich norms that runs `total_ut_steps` times a
    token. `max_window_layers` is read past while no window is on; a window
    that is on is refused by name. What the keys do not settle (the two
    further norms, the norm between passes, the gate, a cache slot a (pass,
    layer)) is bench/configs/ouro-2.6b.json's `assumed`."""
    if hf.get("use_sliding_window") or hf.get("sliding_window") is not None:
        raise NotImplementedError(
            f"ouro with use_sliding_window={hf.get('use_sliding_window')!r} / sliding_window="
            f"{hf.get('sliding_window')!r} is not supported: every published layer attends to every position")
    kinds = set(hf.get("layer_types") or ["full_attention"])
    if kinds != {"full_attention"} or hf.get("rope_scaling") is not None:
        raise NotImplementedError(f"ouro with layer_types {sorted(kinds)} or rope_scaling={hf.get('rope_scaling')!r} "
                                  "is not supported")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_width=hf.get("head_dim"), d_ff=hf["intermediate_size"], max_seq_len=hf["max_position_embeddings"],
        pos_embed="rope", rope_theta=float(hf.get("rope_theta", 10000.0)), norm="rmsnorm",
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, sandwich_norm=True,
        loop_steps=int(hf["total_ut_steps"]), loop_gate=int(hf["total_ut_steps"]) > 1,
        loop_exit_threshold=float(hf.get("early_exit_threshold", 1.0)),
    )


def _pangu_kwargs(hf: Dict) -> Dict:
    """`pangu_ultra_moe` config keys (openPangu-Ultra-MoE) -> TransformerConfig
    fields: latent attention in every layer, sandwich norms, DeepSeek-V3's
    sigmoid router without groups (the config names no scoring function;
    bench/reference/pangu_ultra_moe.py lists what is assumed)."""
    for key, want in (("attention_bias", False), ("norm_topk_prob", True), ("n_shared_experts", 1),
                      ("hidden_act", "silu"), ("rope_scaling", None)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"pangu_ultra_moe with {key}={hf[key]!r} is not supported")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], d_ff=hf["intermediate_size"],
        max_seq_len=hf["max_position_embeddings"], pos_embed="rope", rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        layer_types=("latent_attention",) * hf["num_hidden_layers"],
        sandwich_norm=bool(hf.get("sandwich_norm", False)), mtp_layers=int(hf.get("num_nextn_predict_layers", 0)),
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"], qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        moe_experts=hf["n_routed_experts"], moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=hf["first_k_dense_replace"],
        moe_router="sigmoid", moe_shared_d_ff=hf["moe_intermediate_size"] * hf.get("n_shared_experts", 1),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
    )


# dots3-note's two kinds of attention layer, as its `layer_types` name them and as this repo does
_DOTS3_KINDS = {"full_attention": "sparse_latent_attention", "sliding_attention": "sliding_latent_attention"}


def _dots3_kwargs(hf: Dict) -> Dict:
    """`dots3_note` config keys (dots-studio's dots3-note-prev, the language
    model's) -> TransformerConfig fields: latent attention of two shapes by
    `layer_types`, the full layers' under a learned index (`index_*`), the
    sliding ones' (`swa_*`) banded, both latents rescaled, a gate a head;
    DeepSeek-V3's sigmoid router without groups. What the keys leave open is
    listed in bench/reference/dots3_note.py; a key that would change a layer's
    equations from what is written there is refused by name."""
    for key, want in (("attention_bias", False), ("norm_topk_prob", True), ("n_shared_experts", 1),
                      ("hidden_act", "silu"), ("rope_scaling", None), ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"dots3_note with {key}={hf[key]!r} is not supported")
    if (hf.get("n_group") or 1) > 1:
        raise NotImplementedError(f"dots3_note with n_group={hf['n_group']!r} (group-limited routing) is not supported")
    unknown = set(hf["layer_types"]) - set(_DOTS3_KINDS)
    if unknown:
        raise NotImplementedError(f"dots3_note with layer_types {sorted(unknown)} is not supported")
    rescale = bool(hf.get("apply_mla_qkv_lora_rescale", False))
    shape = lambda pre: dict(
        n_heads=hf[pre + "num_attention_heads"], q_lora_rank=hf[pre + "q_lora_rank"],
        kv_lora_rank=hf[pre + "kv_lora_rank"], qk_nope_head_dim=hf[pre + "qk_nope_head_dim"],
        qk_rope_head_dim=hf[pre + "qk_rope_head_dim"], v_head_dim=hf[pre + "v_head_dim"], rescale=rescale)
    full = shape("")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], d_ff=hf["intermediate_size"],
        max_seq_len=hf["max_position_embeddings"], pos_embed="rope", rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        layer_types=tuple(_DOTS3_KINDS[kind] for kind in hf["layer_types"]), attn_gate="per_head",
        latent_kinds=(
            ("sparse_latent_attention", LatentSpec(**full, index_heads=hf["index_n_heads"],
                                                   index_head_dim=hf["index_head_dim"], index_topk=hf["index_topk"])),
            ("sliding_latent_attention", LatentSpec(**shape("swa_"), window=hf["sliding_window_size"])),
        ),
        rope_kinds=(("sparse_latent_attention", RopeSpec(theta=float(hf["rope_theta"]))),
                    ("sliding_latent_attention", RopeSpec(theta=float(hf["swa_rope_theta"])))),
        **{k: full[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")},
        moe_experts=hf["n_routed_experts"], moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=hf["first_k_dense_replace"],
        moe_router="sigmoid", moe_shared_d_ff=hf["moe_intermediate_size"] * hf.get("n_shared_experts", 1),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)), moe_token_block=4096,
    )


def _solar_kwargs(hf: Dict) -> Dict:
    """upstage's `solar_open2` config keys -> TransformerConfig fields: GQA
    layers without positions (`gqa_layers`) among Kimi-delta layers in Kimi
    Linear's own form (`linear_attn_config`), sigmoid-routed experts in every
    layer. Config keys only: the checkpoint's tensor names are not public, so
    `load_params_from_hf` and the export refuse the family by name. What the
    keys do not settle is listed in bench/reference/solar_open2.py. A key that
    would change a layer's equations from what is written there is refused by
    name."""
    for key, want in (("use_rope", False), ("use_gqa_gate", True), ("kda_use_full_proj", False),
                      ("norm_topk_prob", True), ("n_shared_experts", 1), ("first_k_dense_replace", 0)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"solar_open2 with {key}={hf[key]!r} is not supported")
    linear = hf["linear_attn_config"]
    if linear["num_heads"] != hf["num_attention_heads"] or linear["head_dim"] != hf["head_dim"] \
            or linear.get("num_kv_heads") not in (None, linear["num_heads"]):
        raise NotImplementedError("solar_open2 with linear-attention heads unlike the query heads is not supported")
    n = hf["num_hidden_layers"]
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"], head_width=hf["head_dim"],
        d_ff=hf["intermediate_size"], max_seq_len=hf["max_position_embeddings"], pos_embed="none",
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        layer_types=tuple("attention" if i in hf["gqa_layers"] else "linear_attention" for i in range(n)),
        attn_gate="elementwise", conv_kernel=linear["short_conv_kernel_size"], kda_decay="softplus",
        kda_gate_rank=linear["head_dim"], kda_beta_max=2.0 if hf.get("kda_allow_neg_eigval", False) else 1.0,
        moe_experts=hf["n_routed_experts"], moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=0, moe_router="sigmoid",
        moe_shared_d_ff=hf["moe_intermediate_size"] * hf.get("n_shared_experts", 1),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
    )


def _falcon_h1_kwargs(hf: Dict) -> Dict:
    """tiiuae's `falcon_h1` config keys -> TransformerConfig fields: a Mamba-2
    mixer and GQA attention side by side in every block (`ssm_attention`), the
    twelve forward multipliers as one `Multipliers`. What the keys leave open
    (where each multiplier is applied, the grouped norm behind the gate, D, no
    clamp on dt) is the family's public modelling code's and is listed in
    bench/reference/falcon_h1.py. A key that would change a layer's equations
    from what is written there is refused by name."""
    for key, want in (("mamba_rms_norm", True), ("mamba_norm_before_gate", False), ("attn_layer_indices", None),
                      ("attention_bias", False), ("mamba_proj_bias", False), ("mlp_bias", False),
                      ("projectors_bias", False), ("mamba_conv_bias", True), ("mamba_use_mlp", True),
                      ("hidden_act", "silu"), ("rope_scaling", None)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"falcon_h1 with {key}={hf[key]!r} is not supported")
    d_ssm = hf.get("mamba_d_ssm") or int(hf.get("mamba_expand", 2) * hf["hidden_size"])
    if d_ssm != hf["mamba_n_heads"] * hf["mamba_d_head"]:
        raise NotImplementedError(
            f"falcon_h1 with mamba_d_ssm={d_ssm} unlike mamba_n_heads x mamba_d_head is not supported")
    n = hf["num_hidden_layers"]
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        head_width=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], max_seq_len=hf["max_position_embeddings"], pos_embed="rope",
        rope_theta=float(hf["rope_theta"]), norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5),
        activation="silu", glu=True, tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False,
        flash_prefill=True, layer_types=("ssm_attention",) * n,
        ssm_heads=hf["mamba_n_heads"], ssm_head_dim=hf["mamba_d_head"], ssm_state=hf["mamba_d_state"],
        ssm_groups=hf["mamba_n_groups"], ssm_conv_kernel=hf["mamba_d_conv"], ssm_chunk=hf.get("mamba_chunk_size", 128),
        multipliers=Multipliers(
            embedding=hf.get("embedding_multiplier", 1.0), lm_head=hf.get("lm_head_multiplier", 1.0),
            attention_in=hf.get("attention_in_multiplier", 1.0), attention_out=hf.get("attention_out_multiplier", 1.0),
            key=hf.get("key_multiplier", 1.0), ssm_in=hf.get("ssm_in_multiplier", 1.0),
            ssm_out=hf.get("ssm_out_multiplier", 1.0), ssm=tuple(hf.get("ssm_multipliers") or ()),
            mlp=tuple(hf.get("mlp_multipliers") or ())),
    )


def _ling_kwargs(hf: Dict) -> Dict:
    """Ling-3.0-flash's config keys (the language model of Ling-3.0-flash-VL)
    -> TransformerConfig fields: periods of `layer_group_size` layers, the last
    of each latent attention and the others Kimi delta attention, group-limited
    sigmoid routing. Config keys only: the checkpoint's tensor names are not
    public, so `load_params_from_hf` and the export refuse the family by name.
    What the keys do not settle is listed in bench/reference/ling_flash.py.
    A key that would change a layer's equations from what is written here is
    refused by name, a non-zero `swiglu_limit` among them (its form is not in
    the config)."""
    for key, want in (("score_function", "sigmoid"), ("moe_router_enable_expert_bias", True),
                      ("norm_topk_prob", True), ("use_qk_norm", True), ("linear_silu", True),
                      ("gated_attention_proj_granularity_type", "head_wise"), ("group_norm_size", 1),
                      ("kda_safe_gate", True), ("no_kda_lora", True), ("use_kda_lora", False),
                      ("num_kv_heads_for_linear_attn", 0), ("use_mla_nope", False), ("use_nGPT", False),
                      ("scale_router_input", False), ("value_norm", False), ("up_proj_norm", False)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"ling_flash with {key}={hf[key]!r} is not supported")
    n = hf["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(hf.get(key, ())[:n]):
            raise NotImplementedError(
                f"ling_flash with a non-zero swiglu limit ({key}={list(hf[key][:n])}) is not supported: "
                "the clamp's form is not in the config")
    if hf["head_dim"] != hf["qk_nope_head_dim"] or hf["head_dim"] != hf["v_head_dim"]:
        raise NotImplementedError("ling_flash with a linear-attention head unlike the latent layers' is not supported")
    period = hf["layer_group_size"]
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], head_width=hf["head_dim"], d_ff=hf["intermediate_size"],
        max_seq_len=hf["max_position_embeddings"], pos_embed="rope", rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm", layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6), activation="silu", glu=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)), use_bias=False, flash_prefill=True,
        layer_types=tuple("latent_attention" if (i + 1) % period == 0 else "linear_attention" for i in range(n)),
        q_lora_rank=hf.get("q_lora_rank") or 0, kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"], qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], qk_norm=True, attn_gate="per_head",
        conv_kernel=hf["short_conv_kernel_size"], kda_lower_bound=float(hf["kda_lower_bound"]),
        moe_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        moe_d_ff=hf["moe_intermediate_size"], moe_dense_layers=hf["first_k_dense_replace"],
        moe_router="sigmoid", moe_shared_d_ff=hf.get("moe_shared_expert_intermediate_size", 0),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_n_group=hf.get("n_group", 0), moe_topk_group=hf.get("topk_group", 0),
    )


def _seq2seq_config_from_hf(hf: Dict, **overrides):
    """HF T5Config -> Seq2SeqConfig. Covers t5 v1.0 (relu MLP, tied
    embeddings, logits scaled by d_model**-0.5), v1.1/flan-t5 (gated-gelu,
    untied lm_head, no logit scaling), and mt5 (same as v1.1).

    Parity: the reference wraps these via AutoModelForSeq2SeqLM inside
    PreTrainedModelWrapper.from_pretrained (trlx/models/modeling_base.py:
    123-326); HF-T5 numerics are encoded as attention_scale=False (the
    1/sqrt(d_kv) is folded into init) and the conditional logit_scale."""
    from trlx_tpu.models.seq2seq import Seq2SeqConfig

    ffp = hf.get("feed_forward_proj", "relu")
    gated = ffp.startswith("gated-")
    act = ffp.split("-")[-1]
    # T5Config forces dense_act_fn='gelu_new' (tanh approx, our "gelu")
    # ONLY for feed_forward_proj='gated-gelu'; a plain 'gelu' runs HF's
    # exact erf GELU -> our "gelu_exact". 'gelu_new' appears directly in
    # some v1.1 configs.
    act = {
        "relu": "relu",
        "gelu": "gelu" if gated else "gelu_exact",
        "gelu_new": "gelu",
        "silu": "silu",
    }[act]
    tie = bool(hf.get("tie_word_embeddings", True))
    kwargs = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["d_model"],
        n_encoder_layers=hf["num_layers"],
        n_decoder_layers=hf.get("num_decoder_layers") or hf["num_layers"],
        n_heads=hf["num_heads"],
        d_kv=hf.get("d_kv"),
        d_ff=hf["d_ff"],
        # T5 has no absolute position cap (relative bias saturates); 512 is
        # the tokenizer's model_max_length convention, override as needed
        max_seq_len=512,
        norm="rmsnorm",
        activation=act,
        glu=gated,
        tie_embeddings=tie,
        use_bias=False,
        relative_attention=True,
        relative_attention_num_buckets=hf.get("relative_attention_num_buckets", 32),
        relative_attention_max_distance=hf.get("relative_attention_max_distance", 128),
        decoder_start_token_id=hf.get("decoder_start_token_id", 0) or 0,
        pad_token_id=hf.get("pad_token_id", 0),
        eos_token_id=hf.get("eos_token_id", 1),
        layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-6),
        attention_scale=False,
        logit_scale=hf["d_model"] ** -0.5 if tie else None,
        hf_family="t5",
    )
    kwargs.update(overrides)
    return Seq2SeqConfig(**kwargs)


# ---------------------------------------------------------------------------
# State-dict IO
# ---------------------------------------------------------------------------


def _load_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Load an HF torch checkpoint into numpy (handles sharded bins and
    safetensors)."""
    import torch

    tensors: Dict[str, np.ndarray] = {}
    st_index = os.path.join(path, "model.safetensors.index.json")
    bin_index = os.path.join(path, "pytorch_model.bin.index.json")
    files = []
    if os.path.exists(os.path.join(path, "model.safetensors")):
        files = [os.path.join(path, "model.safetensors")]
    elif os.path.exists(st_index):
        with open(st_index) as f:
            files = sorted({os.path.join(path, v) for v in json.load(f)["weight_map"].values()})
    elif os.path.exists(os.path.join(path, "pytorch_model.bin")):
        files = [os.path.join(path, "pytorch_model.bin")]
    elif os.path.exists(bin_index):
        with open(bin_index) as f:
            files = sorted({os.path.join(path, v) for v in json.load(f)["weight_map"].values()})
    else:
        raise FileNotFoundError(f"No model weights found under {path}")

    for f in files:
        if f.endswith(".safetensors"):
            from safetensors.torch import load_file

            sd = load_file(f)
        else:
            sd = torch.load(f, map_location="cpu", weights_only=True)
        for k, v in sd.items():
            tensors[k] = v.float().numpy()
    return tensors


def _strip_prefix(sd: Dict[str, np.ndarray], *prefixes: str) -> Dict[str, np.ndarray]:
    """Drop a leading wrapper prefix (e.g. 'transformer.', 'model.decoder.')
    if every relevant key carries it."""
    for p in prefixes:
        if any(k.startswith(p) for k in sd):
            return {k[len(p):] if k.startswith(p) else k: v for k, v in sd.items()}
    return sd


def _gptj_rope_perm(rd: int) -> np.ndarray:
    """Permutation mapping interleaved rotary layout -> half-split layout:
    target dim i reads source dim 2i (first half) / 2(i-rd/2)+1 (second)."""
    half = rd // 2
    return np.concatenate([np.arange(half) * 2, np.arange(half) * 2 + 1])


def _permute_rotary_cols(w: np.ndarray, cfg: TransformerConfig, n_heads: int, inverse: bool = False):
    """Permute a projection kernel's output dims ([in, heads*hd]) from the
    interleaved to the half-split rotary convention (or back)."""
    rd = cfg.rotary_dim
    perm = _gptj_rope_perm(rd)
    if inverse:
        perm = np.argsort(perm)
    hd = cfg.head_dim
    w = w.reshape(w.shape[:-1] + (n_heads, hd)).copy()
    w[..., :rd] = w[..., perm]
    return w.reshape(w.shape[:-2] + (n_heads * hd,))


def _split_fused_qkv_per_head(qkv: np.ndarray, n_heads: int, head_dim: int):
    """Split a fused [in, heads*3*hd] kernel whose output is laid out
    per-head as (q,k,v) triples (GPT-NeoX / Bloom) into separate q/k/v
    kernels of [in, heads*hd]. Also accepts 1-D biases."""
    shape = qkv.shape[:-1]
    x = qkv.reshape(shape + (n_heads, 3, head_dim))
    q, k, v = x[..., 0, :], x[..., 1, :], x[..., 2, :]
    flat = shape + (n_heads * head_dim,)
    return q.reshape(flat), k.reshape(flat), v.reshape(flat)


# ---------------------------------------------------------------------------
# Per-family load converters: HF state dict -> our "lm" subtree
# ---------------------------------------------------------------------------


def _ln(sd, prefix, bias=True):
    out = {"scale": sd[prefix + ".weight"]}
    if bias:
        out["bias"] = sd[prefix + ".bias"]
    return out


def _dense(kernel, bias=None):
    out = {"kernel": kernel}
    if bias is not None:
        out["bias"] = bias
    return out


def _load_gpt2(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "transformer.")
    lm: Dict = {
        "embed_tokens": {"embedding": sd["wte.weight"]},
        "embed_pos": {"embedding": sd["wpe.weight"]},
        "ln_f": _ln(sd, "ln_f"),
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        # GPT-2 fused qkv: c_attn.weight [d, 3d] (Conv1D layout: in x out)
        qw, kw, vw = np.split(sd[p + "attn.c_attn.weight"], 3, axis=1)
        qb, kb, vb = np.split(sd[p + "attn.c_attn.bias"], 3, axis=0)
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "ln_1"),
            "ln_mlp": _ln(sd, p + "ln_2"),
            "attn": {
                "q_proj": _dense(qw, qb), "k_proj": _dense(kw, kb), "v_proj": _dense(vw, vb),
                "o_proj": _dense(sd[p + "attn.c_proj.weight"], sd[p + "attn.c_proj.bias"]),
            },
            "mlp": {
                "up_proj": _dense(sd[p + "mlp.c_fc.weight"], sd[p + "mlp.c_fc.bias"]),
                "down_proj": _dense(sd[p + "mlp.c_proj.weight"], sd[p + "mlp.c_proj.bias"]),
            },
        }
    return lm


def _load_llama(sd: Dict, cfg: TransformerConfig) -> Dict:
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {
        "embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "ln_f": _ln(sd, f"{pre}norm", bias=False),
    }
    for i in range(cfg.n_layers):
        p = f"{pre}layers.{i}."
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "input_layernorm", bias=False),
            "ln_mlp": _ln(sd, p + "post_attention_layernorm", bias=False),
            "attn": {
                # HF stores [out, in]; our Dense kernels are [in, out]
                n: _dense(sd[p + f"self_attn.{n}.weight"].T)
                for n in ("q_proj", "k_proj", "v_proj", "o_proj")
            },
            "mlp": {
                n: _dense(sd[p + f"mlp.{n}.weight"].T)
                for n in ("gate_proj", "up_proj", "down_proj")
            },
        }
    if not cfg.tie_embeddings:
        lm["lm_head"] = _dense(sd["lm_head.weight"].T)
    return lm


# our leaf -> the `ouro` checkpoint's norm, a layer (the names bench/configs/ouro-2.6b.json `assumed`
# lists: the family's convention as recalled, unchecked against the published safetensors index)
_OURO_NORMS = (("ln_attn", "input_layernorm"), ("ln_post_attn", "input_layernorm_2"),
               ("ln_mlp", "post_attention_layernorm"), ("ln_post_mlp", "post_attention_layernorm_2"))


def _load_ouro(sd: Dict, cfg: TransformerConfig) -> Dict:
    """OuroForCausalLM: llama's names, two further norms a layer and the exit gate."""
    lm = _load_llama(sd, cfg)
    for i in range(cfg.n_layers):
        lm[f"block_{i}"].update({ours: _ln(sd, f"model.layers.{i}.{theirs}", bias=False)
                                 for ours, theirs in _OURO_NORMS})
    if cfg.loop_gate:
        lm["exit_gate"] = _dense(sd["model.early_exit_gate.weight"].T, sd["model.early_exit_gate.bias"])
    return lm


_LFM2_FFN = (("gate_proj", "w1"), ("up_proj", "w3"), ("down_proj", "w2"))


def _load_lfm2_moe(sd: Dict, cfg: TransformerConfig) -> Dict:
    """Lfm2MoeForCausalLM. The checkpoint holds every expert; the tree holds
    the matrices of experts [moe_local_offset, + experts_held) side by side
    (`SparseMoE`), so a process that holds a share loads its share."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {
        "embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "ln_f": _ln(sd, f"{pre}embedding_norm", bias=False),
    }
    held = range(cfg.moe_local_offset, cfg.moe_local_offset + cfg.experts_held)
    for i in range(cfg.n_layers):
        p = f"{pre}layers.{i}."
        block = {"ln_attn": _ln(sd, p + "operator_norm", bias=False),
                 "ln_mlp": _ln(sd, p + "ffn_norm", bias=False)}
        if cfg.layer_op(i) == "conv":
            block["conv"] = {
                "in_proj": _dense(sd[p + "conv.in_proj.weight"].T),
                "kernel": sd[p + "conv.conv.weight"][:, 0, :].T,  # [d, 1, K] -> [K, d]
                "out_proj": _dense(sd[p + "conv.out_proj.weight"].T),
            }
        else:
            block["attn"] = {n: _dense(sd[p + f"self_attn.{hf_n}.weight"].T) for n, hf_n in
                             (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"),
                              ("o_proj", "out_proj"))}
            block["attn"]["q_norm"] = _ln(sd, p + "self_attn.q_layernorm", bias=False)
            block["attn"]["k_norm"] = _ln(sd, p + "self_attn.k_layernorm", bias=False)
        if cfg.layer_ffn(i) == "dense":
            block["mlp"] = {n: _dense(sd[p + f"feed_forward.{w}.weight"].T) for n, w in _LFM2_FFN}
        else:
            block["mlp"] = {
                "router": _dense(sd[p + "feed_forward.gate.weight"].T),
                "expert_bias": {"bias": sd[p + "feed_forward.expert_bias"]},
                **{f"expert_{n.split('_')[0]}": _dense(np.concatenate(
                    [sd[p + f"feed_forward.experts.{e}.{w}.weight"].T for e in held], axis=1))
                   for n, w in _LFM2_FFN},
            }
        lm[f"block_{i}"] = block
    return lm


def _load_smallthinker(sd: Dict, cfg: TransformerConfig) -> Dict:
    """SmallThinkerForCausalLM, under the tensor names bench/configs/
    smallthinker-21b-a3b.json `assumed` lists (the family's convention as
    recalled: the published index is not in the repository). The tree holds the
    matrices of experts [moe_local_offset, + experts_held) side by side."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {"embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
                "ln_f": _ln(sd, f"{pre}norm", bias=False),
                "lm_head": _dense(sd["lm_head.weight"].T)}
    held = range(cfg.moe_local_offset, cfg.moe_local_offset + cfg.experts_held)
    for i in range(cfg.n_layers):
        p = f"{pre}layers.{i}."
        moe = p + "block_sparse_moe."
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "input_layernorm", bias=False),
            "ln_mlp": _ln(sd, p + "post_attention_layernorm", bias=False),
            "attn": {n: _dense(sd[p + f"self_attn.{n}.weight"].T) for n in ("q_proj", "k_proj", "v_proj", "o_proj")},
            "mlp": {"router": _dense(sd[moe + "primary_router.weight"].T),
                    **{f"expert_{n}": _dense(np.concatenate(
                        [sd[moe + f"experts.{e}.{n}.weight"].T for e in held], axis=1))
                       for n in ("gate", "up", "down")}},
        }
    return lm


_PANGU_ATTN = (("q_a_proj", "q_a_proj"), ("q_b_proj", "q_b_proj"), ("kv_a_proj", "kv_a_proj_with_mqa"),
               ("kv_b_proj", "kv_b_proj"), ("o_proj", "o_proj"))
_PANGU_ATTN_NORMS = (("q_a_norm", "q_a_layernorm"), ("kv_a_norm", "kv_a_layernorm"))
_PANGU_NORMS = (("ln_attn", "input_layernorm"), ("ln_post_attn", "post_attention_layernorm"),
                ("ln_mlp", "pre_mlp_layernorm"), ("ln_post_mlp", "post_mlp_layernorm"))
_GLU = ("gate_proj", "up_proj", "down_proj")


def _load_pangu_block(sd: Dict, p: str, cfg: TransformerConfig, dense_ffn: bool) -> Dict:
    block = {n: _ln(sd, p + hf_n, bias=False) for n, hf_n in _PANGU_NORMS}
    block["attn"] = {n: _dense(sd[p + f"self_attn.{hf_n}.weight"].T) for n, hf_n in _PANGU_ATTN}
    block["attn"].update({n: _ln(sd, p + f"self_attn.{hf_n}", bias=False) for n, hf_n in _PANGU_ATTN_NORMS})
    if dense_ffn:
        block["mlp"] = {n: _dense(sd[p + f"mlp.{n}.weight"].T) for n in _GLU}
        return block
    held = range(cfg.moe_local_offset, cfg.moe_local_offset + cfg.experts_held)
    block["mlp"] = {
        "router": _dense(sd[p + "mlp.gate.weight"].T),
        # the family's gate has no selection bias; the program's leaf stays, at zero
        "expert_bias": {"bias": np.zeros((cfg.moe_experts,), np.float32)},
        **{f"expert_{n.split('_')[0]}": _dense(np.concatenate(
            [sd[p + f"mlp.experts.{e}.{n}.weight"].T for e in held], axis=1)) for n in _GLU},
        **{f"shared_{n.split('_')[0]}": _dense(sd[p + f"mlp.shared_experts.{n}.weight"].T) for n in _GLU},
    }
    return block


def _load_pangu_ultra_moe(sd: Dict, cfg: TransformerConfig) -> Dict:
    """`pangu_ultra_moe` (openPangu-Ultra-MoE), in DeepSeek-V3's tensor
    names with the two further norms of `sandwich_norm`
    (`pre_mlp_layernorm`, `post_mlp_layernorm`); a multi-token block is
    layer `num_hidden_layers + k` with `enorm`, `hnorm`, `eh_proj` (its
    `shared_head` is the model's own norm and head, which are used). The
    rotary dimensions are taken as they lie (the half-split layout is
    ASSUMED; an interleaved checkpoint would need `_permute_rotary_cols` on
    q_b_proj's and kv_a_proj_with_mqa's rotary columns). The tree holds
    experts [moe_local_offset, + experts_held) side by side. UNCHECKED against
    the published weights: no checkpoint of the family was at hand, the
    round trip in tests/test_pangu_mla.py is over a random state dict."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {
        "embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "ln_f": _ln(sd, f"{pre}norm", bias=False),
        "lm_head": _dense(sd["lm_head.weight"].T),
    }
    for i in range(cfg.n_layers):
        lm[f"block_{i}"] = _load_pangu_block(sd, f"{pre}layers.{i}.", cfg, cfg.layer_ffn(i) == "dense")
    for k in range(cfg.mtp_layers):
        p = f"{pre}layers.{cfg.n_layers + k}."
        lm[f"mtp_{k}"] = {
            "enorm": _ln(sd, p + "enorm", bias=False), "hnorm": _ln(sd, p + "hnorm", bias=False),
            "eh_proj": _dense(sd[p + "eh_proj.weight"].T),
            "block": _load_pangu_block(sd, p, cfg, cfg.layer_ffn(cfg.n_layers - 1) == "dense"),
        }
    return lm


_DOTS3_NORMS = (("ln_attn", "input_layernorm"), ("ln_mlp", "post_attention_layernorm"))
_DOTS3_ATTN = _PANGU_ATTN + (("gate_proj", "gate_proj"),)
_DOTS3_INDEX = ("wq_b", "wk", "weights_proj")


def _load_dots3_note(sd: Dict, cfg: TransformerConfig) -> Dict:
    """`dots3_note` (dots3-note-prev's language model), in DeepSeek-V3's
    tensor names: `self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}`, the full layers'
    `self_attn.indexer.{wq_b, wk, k_norm, weights_proj}` (DeepSeek-V3.2's),
    `mlp.gate` with its `e_score_correction_bias`, `mlp.experts.N.*`,
    `mlp.shared_experts.*`. The gate a head is taken as `self_attn.gate_proj`
    and the rotary dimensions as they lie (half-split): both ASSUMED, no
    published file settles them. The tree holds experts [moe_local_offset,
    + experts_held) side by side. UNCHECKED against the published weights: no
    checkpoint of the family was at hand, the round trip in
    tests/test_dots3_note.py is over a random state dict."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {
        "embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "ln_f": _ln(sd, f"{pre}norm", bias=False),
        "lm_head": _dense(sd["lm_head.weight"].T),
    }
    held = range(cfg.moe_local_offset, cfg.moe_local_offset + cfg.experts_held)
    for i in range(cfg.n_layers):
        p = f"{pre}layers.{i}."
        block = {n: _ln(sd, p + hf_n, bias=False) for n, hf_n in _DOTS3_NORMS}
        attn = {n: _dense(sd[p + f"self_attn.{hf_n}.weight"].T) for n, hf_n in _DOTS3_ATTN}
        attn.update({n: _ln(sd, p + f"self_attn.{hf_n}", bias=False) for n, hf_n in _PANGU_ATTN_NORMS})
        if cfg.latent_of(cfg.layer_op(i)).index_topk:
            attn["indexer"] = {n: _dense(sd[p + f"self_attn.indexer.{n}.weight"].T) for n in _DOTS3_INDEX}
            attn["indexer"]["k_norm"] = _ln(sd, p + "self_attn.indexer.k_norm")
        block["attn"] = attn
        if cfg.layer_ffn(i) == "dense":
            block["mlp"] = {n: _dense(sd[p + f"mlp.{n}.weight"].T) for n in _GLU}
        else:
            block["mlp"] = {
                "router": _dense(sd[p + "mlp.gate.weight"].T),
                "expert_bias": {"bias": sd[p + "mlp.gate.e_score_correction_bias"]},
                **{f"expert_{n.split('_')[0]}": _dense(np.concatenate(
                    [sd[p + f"mlp.experts.{e}.{n}.weight"].T for e in held], axis=1)) for n in _GLU},
                **{f"shared_{n.split('_')[0]}": _dense(sd[p + f"mlp.shared_experts.{n}.weight"].T) for n in _GLU},
            }
        lm[f"block_{i}"] = block
    return lm


_FALCON_NORMS = (("ln_attn", "input_layernorm"), ("ln_mlp", "pre_ff_layernorm"))
_FALCON_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
# (leaf, its name under `mamba.`): the heads' three vectors are bare parameters there
_FALCON_HEAD = ((("a_log", "bias"), "A_log"), (("d", "scale"), "D"), (("dt_bias", "bias"), "dt_bias"))


def _load_falcon_h1(sd: Dict, cfg: TransformerConfig) -> Dict:
    """`falcon_h1` (tiiuae Falcon-H1), in the family's checkpoint names: a
    block's `mamba.*`, `self_attn.*`, `feed_forward.*`, `input_layernorm`,
    `pre_ff_layernorm`; `final_layernorm` and an untied `lm_head`. The
    convolution's weight [channels, 1, taps] becomes [taps, channels] (tap j
    meets the input taps - 1 - j back on both sides); `A_log`, `D` and
    `dt_bias` are copied as they are (A = -exp(A_log) in both). UNCHECKED
    against the published weights: no checkpoint of the family was at hand, the
    round trip in tests/test_falcon_h1.py is over a random state dict."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lm: Dict = {
        "embed_tokens": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "ln_f": _ln(sd, f"{pre}final_layernorm", bias=False),
    }
    if not cfg.tie_embeddings:
        lm["lm_head"] = _dense(sd["lm_head.weight"].T)
    for i in range(cfg.n_layers):
        p = f"{pre}layers.{i}."
        lm[f"block_{i}"] = {
            **{n: _ln(sd, p + hf_n, bias=False) for n, hf_n in _FALCON_NORMS},
            "attn": {n: _dense(sd[p + f"self_attn.{n}.weight"].T) for n in _FALCON_ATTN},
            "mlp": {n: _dense(sd[p + f"feed_forward.{n}.weight"].T) for n in _GLU},
            "ssm": {
                "in_proj": _dense(sd[p + "mamba.in_proj.weight"].T),
                "out_proj": _dense(sd[p + "mamba.out_proj.weight"].T),
                "conv1d": _dense(sd[p + "mamba.conv1d.weight"][:, 0, :].T, sd[p + "mamba.conv1d.bias"]),
                "norm": _ln(sd, p + "mamba.norm", bias=False),
                **{leaf[0]: {leaf[1]: sd[p + f"mamba.{hf_n}"]} for leaf, hf_n in _FALCON_HEAD},
            },
        }
    return lm


def _load_gpt_neox(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "gpt_neox.")
    lm: Dict = {
        "embed_tokens": {"embedding": sd["embed_in.weight"]},
        "ln_f": _ln(sd, "final_layer_norm"),
    }
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        qw, kw, vw = _split_fused_qkv_per_head(
            sd[p + "attention.query_key_value.weight"].T, cfg.n_heads, cfg.head_dim
        )
        qb, kb, vb = _split_fused_qkv_per_head(
            sd[p + "attention.query_key_value.bias"], cfg.n_heads, cfg.head_dim
        )
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "input_layernorm"),
            "ln_mlp": _ln(sd, p + "post_attention_layernorm"),
            "attn": {
                "q_proj": _dense(qw, qb), "k_proj": _dense(kw, kb), "v_proj": _dense(vw, vb),
                "o_proj": _dense(sd[p + "attention.dense.weight"].T, sd[p + "attention.dense.bias"]),
            },
            "mlp": {
                "up_proj": _dense(sd[p + "mlp.dense_h_to_4h.weight"].T, sd[p + "mlp.dense_h_to_4h.bias"]),
                "down_proj": _dense(sd[p + "mlp.dense_4h_to_h.weight"].T, sd[p + "mlp.dense_4h_to_h.bias"]),
            },
        }
    lm["lm_head"] = _dense(sd["embed_out.weight"].T)
    return lm


def _load_gptj(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "transformer.")
    lm: Dict = {
        "embed_tokens": {"embedding": sd["wte.weight"]},
        "ln_f": _ln(sd, "ln_f"),
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qw = _permute_rotary_cols(sd[p + "attn.q_proj.weight"].T, cfg, cfg.n_heads)
        kw = _permute_rotary_cols(sd[p + "attn.k_proj.weight"].T, cfg, cfg.kv_heads)
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "ln_1"),
            "attn": {
                "q_proj": _dense(qw), "k_proj": _dense(kw),
                "v_proj": _dense(sd[p + "attn.v_proj.weight"].T),
                "o_proj": _dense(sd[p + "attn.out_proj.weight"].T),
            },
            "mlp": {
                "up_proj": _dense(sd[p + "mlp.fc_in.weight"].T, sd[p + "mlp.fc_in.bias"]),
                "down_proj": _dense(sd[p + "mlp.fc_out.weight"].T, sd[p + "mlp.fc_out.bias"]),
            },
        }
    lm["lm_head"] = _dense(sd["lm_head.weight"].T, sd["lm_head.bias"])
    return lm


def _load_opt(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "model.decoder.", "decoder.")
    lm: Dict = {
        "embed_tokens": {"embedding": sd["embed_tokens.weight"]},
        "embed_pos": {"embedding": sd["embed_positions.weight"]},
        "ln_f": _ln(sd, "final_layer_norm"),
    }
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "self_attn_layer_norm"),
            "ln_mlp": _ln(sd, p + "final_layer_norm"),
            "attn": {
                our: _dense(sd[p + f"self_attn.{hf}.weight"].T, sd[p + f"self_attn.{hf}.bias"])
                for our, hf in (
                    ("q_proj", "q_proj"), ("k_proj", "k_proj"),
                    ("v_proj", "v_proj"), ("o_proj", "out_proj"),
                )
            },
            "mlp": {
                "up_proj": _dense(sd[p + "fc1.weight"].T, sd[p + "fc1.bias"]),
                "down_proj": _dense(sd[p + "fc2.weight"].T, sd[p + "fc2.bias"]),
            },
        }
    return lm


def _load_bloom(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "transformer.")
    lm: Dict = {
        "embed_tokens": {"embedding": sd["word_embeddings.weight"]},
        "ln_embed": _ln(sd, "word_embeddings_layernorm"),
        "ln_f": _ln(sd, "ln_f"),
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qw, kw, vw = _split_fused_qkv_per_head(
            sd[p + "self_attention.query_key_value.weight"].T, cfg.n_heads, cfg.head_dim
        )
        qb, kb, vb = _split_fused_qkv_per_head(
            sd[p + "self_attention.query_key_value.bias"], cfg.n_heads, cfg.head_dim
        )
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "input_layernorm"),
            "ln_mlp": _ln(sd, p + "post_attention_layernorm"),
            "attn": {
                "q_proj": _dense(qw, qb), "k_proj": _dense(kw, kb), "v_proj": _dense(vw, vb),
                "o_proj": _dense(sd[p + "self_attention.dense.weight"].T, sd[p + "self_attention.dense.bias"]),
            },
            "mlp": {
                "up_proj": _dense(sd[p + "mlp.dense_h_to_4h.weight"].T, sd[p + "mlp.dense_h_to_4h.bias"]),
                "down_proj": _dense(sd[p + "mlp.dense_4h_to_h.weight"].T, sd[p + "mlp.dense_4h_to_h.bias"]),
            },
        }
    return lm


def _load_gpt_bigcode(sd: Dict, cfg: TransformerConfig) -> Dict:
    sd = _strip_prefix(sd, "transformer.")
    d, kv_dim = cfg.d_model, cfg.kv_heads * cfg.head_dim
    lm: Dict = {
        "embed_tokens": {"embedding": sd["wte.weight"]},
        "embed_pos": {"embedding": sd["wpe.weight"]},
        "ln_f": _ln(sd, "ln_f"),
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        # torch Linear layout [out, in]; fused output = [q(d), k(kv), v(kv)]
        w = sd[p + "attn.c_attn.weight"].T
        b = sd[p + "attn.c_attn.bias"]
        qw, kw, vw = w[:, :d], w[:, d:d + kv_dim], w[:, d + kv_dim:]
        qb, kb, vb = b[:d], b[d:d + kv_dim], b[d + kv_dim:]
        lm[f"block_{i}"] = {
            "ln_attn": _ln(sd, p + "ln_1"),
            "ln_mlp": _ln(sd, p + "ln_2"),
            "attn": {
                "q_proj": _dense(qw, qb), "k_proj": _dense(kw, kb), "v_proj": _dense(vw, vb),
                "o_proj": _dense(sd[p + "attn.c_proj.weight"].T, sd[p + "attn.c_proj.bias"]),
            },
            "mlp": {
                "up_proj": _dense(sd[p + "mlp.c_fc.weight"].T, sd[p + "mlp.c_fc.bias"]),
                "down_proj": _dense(sd[p + "mlp.c_proj.weight"].T, sd[p + "mlp.c_proj.bias"]),
            },
        }
    return lm


def _t5_attn(sd: Dict, p: str) -> Dict:
    """T5Attention / EncDecAttention ({q,k,v,o}.weight, torch [out, in]) ->
    our S2SAttention kernels ([in, out])."""
    return {
        "q_proj": _dense(sd[p + ".q.weight"].T),
        "k_proj": _dense(sd[p + ".k.weight"].T),
        "v_proj": _dense(sd[p + ".v.weight"].T),
        "o_proj": _dense(sd[p + ".o.weight"].T),
    }


def _t5_mlp(sd: Dict, p: str, glu: bool) -> Dict:
    if glu:  # v1.1/flan gated act: wi_0 = gate, wi_1 = up
        return {
            "gate_proj": _dense(sd[p + ".wi_0.weight"].T),
            "up_proj": _dense(sd[p + ".wi_1.weight"].T),
            "down_proj": _dense(sd[p + ".wo.weight"].T),
        }
    return {
        "up_proj": _dense(sd[p + ".wi.weight"].T),
        "down_proj": _dense(sd[p + ".wo.weight"].T),
    }


def _load_t5(sd: Dict, cfg) -> Dict:
    """T5ForConditionalGeneration state dict -> our Seq2SeqLM subtree.
    The per-stack relative-bias table lives in block 0's self-attention
    (HF computes it there and shares); we store it once per stack
    (enc_rel_bias / dec_rel_bias), same math."""
    lm: Dict = {
        "embed_tokens": {"embedding": sd["shared.weight"]},
        "enc_ln_f": {"scale": sd["encoder.final_layer_norm.weight"]},
        "dec_ln_f": {"scale": sd["decoder.final_layer_norm.weight"]},
        "enc_rel_bias": {"embedding": {"embedding": sd[
            "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
        ]}},
        "dec_rel_bias": {"embedding": {"embedding": sd[
            "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
        ]}},
    }
    for i in range(cfg.n_encoder_layers):
        p = f"encoder.block.{i}."
        lm[f"enc_block_{i}"] = {
            "attn": _t5_attn(sd, p + "layer.0.SelfAttention"),
            "ln_attn": {"scale": sd[p + "layer.0.layer_norm.weight"]},
            "mlp": _t5_mlp(sd, p + "layer.1.DenseReluDense", cfg.glu),
            "ln_mlp": {"scale": sd[p + "layer.1.layer_norm.weight"]},
        }
    for i in range(cfg.n_decoder_layers):
        p = f"decoder.block.{i}."
        lm[f"dec_block_{i}"] = {
            "attn": _t5_attn(sd, p + "layer.0.SelfAttention"),
            "ln_attn": {"scale": sd[p + "layer.0.layer_norm.weight"]},
            "cross_attn": _t5_attn(sd, p + "layer.1.EncDecAttention"),
            "ln_cross": {"scale": sd[p + "layer.1.layer_norm.weight"]},
            "mlp": _t5_mlp(sd, p + "layer.2.DenseReluDense", cfg.glu),
            "ln_mlp": {"scale": sd[p + "layer.2.layer_norm.weight"]},
        }
    if not cfg.tie_embeddings:
        lm["lm_head"] = _dense(sd["lm_head.weight"].T)
    return lm


_LOADERS: Dict[str, Callable] = {
    "t5": _load_t5,
    "gpt2": _load_gpt2,
    "llama": _load_llama,
    "gpt_neox": _load_gpt_neox,
    "gptj": _load_gptj,
    "opt": _load_opt,
    "bloom": _load_bloom,
    "gpt_bigcode": _load_gpt_bigcode,
    "lfm2_moe": _load_lfm2_moe,
    "pangu_ultra_moe": _load_pangu_ultra_moe,
    "ouro": _load_ouro,
    "dots3_note": _load_dots3_note,
    "falcon_h1": _load_falcon_h1,
    "smallthinker": _load_smallthinker,
}


def load_params_from_hf(path: str, cfg: TransformerConfig, params_template: Dict) -> Dict:
    """Convert an HF state dict into our param pytree, using the template's
    structure/dtypes."""
    hf = _read_hf_config(path)
    fam = _family_of(hf)
    if fam not in _LOADERS:
        raise NotImplementedError(
            f"no tensor-name mapping for HF family {fam!r}: its config keys convert "
            "(config_from_hf), its checkpoint's tensor names are not public")
    sd = _load_state_dict(path)
    lm = _LOADERS[fam](sd, cfg)

    import jax
    from flax import traverse_util

    def dt(template_leaf, arr):
        a = np.asarray(arr, dtype=np.dtype(template_leaf.dtype))
        if a.shape != template_leaf.shape:
            raise ValueError(
                f"Converted weight shape {a.shape} != expected {template_leaf.shape}"
            )
        return a

    # Adapter leaves (LoRA matrices, the prompt-tuning soft prompt) exist
    # only in the template (freshly initialized, not in the HF checkpoint)
    # — split them out, map the base weights, then re-attach them.
    from trlx_tpu.models.lora import split_lora

    lora_leaves, base_flat = split_lora(params_template["lm"])
    adapter_leaves = dict(lora_leaves)
    for key in list(base_flat):
        if "soft_prompt" in key or key[-1] in ("prefix_k", "prefix_v"):
            adapter_leaves[key] = base_flat.pop(key)
    base_tpl = traverse_util.unflatten_dict(base_flat)
    mapped = jax.tree_util.tree_map(dt, base_tpl, lm)
    new_lm = traverse_util.unflatten_dict(
        {**traverse_util.flatten_dict(mapped), **adapter_leaves}
    )

    new_params = dict(params_template)
    new_params["lm"] = new_lm
    logger.info(f"Loaded HF weights ({fam}) from {path}")
    return new_params


# ---------------------------------------------------------------------------
# Export: our params -> HF-layout state dict (save_pretrained interop)
# ---------------------------------------------------------------------------


def _f32(x):
    return np.asarray(x, np.float32)


def _export_gpt2(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "transformer.wte.weight": _f32(lm["embed_tokens"]["embedding"]),
        "transformer.wpe.weight": _f32(lm["embed_pos"]["embedding"]),
        "transformer.ln_f.weight": _f32(lm["ln_f"]["scale"]),
        "transformer.ln_f.bias": _f32(lm["ln_f"]["bias"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "ln_1.bias"] = _f32(b["ln_attn"]["bias"])
        sd[p + "ln_2.weight"] = _f32(b["ln_mlp"]["scale"])
        sd[p + "ln_2.bias"] = _f32(b["ln_mlp"]["bias"])
        sd[p + "attn.c_attn.weight"] = np.concatenate(
            [_f32(b["attn"][n]["kernel"]) for n in ("q_proj", "k_proj", "v_proj")], axis=1
        )
        sd[p + "attn.c_attn.bias"] = np.concatenate(
            [_f32(b["attn"][n]["bias"]) for n in ("q_proj", "k_proj", "v_proj")], axis=0
        )
        sd[p + "attn.c_proj.weight"] = _f32(b["attn"]["o_proj"]["kernel"])
        sd[p + "attn.c_proj.bias"] = _f32(b["attn"]["o_proj"]["bias"])
        sd[p + "mlp.c_fc.weight"] = _f32(b["mlp"]["up_proj"]["kernel"])
        sd[p + "mlp.c_fc.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "mlp.c_proj.weight"] = _f32(b["mlp"]["down_proj"]["kernel"])
        sd[p + "mlp.c_proj.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    sd["lm_head.weight"] = sd["transformer.wte.weight"]
    return sd


def _export_llama(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.norm.weight": _f32(lm["ln_f"]["scale"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "post_attention_layernorm.weight"] = _f32(b["ln_mlp"]["scale"])
        for n in ("q_proj", "k_proj", "v_proj"):
            sd[p + f"self_attn.{n}.weight"] = _f32(b["attn"][n]["kernel"]).T
        sd[p + "self_attn.o_proj.weight"] = _f32(b["attn"]["o_proj"]["kernel"]).T
        for n in ("gate_proj", "up_proj", "down_proj"):
            sd[p + f"mlp.{n}.weight"] = _f32(b["mlp"][n]["kernel"]).T
    if "lm_head" in lm:
        sd["lm_head.weight"] = _f32(lm["lm_head"]["kernel"]).T
    else:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    return sd


def _export_ouro(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_ouro`."""
    sd = _export_llama(lm, cfg)
    for i in range(cfg.n_layers):
        for ours, theirs in _OURO_NORMS:
            sd[f"model.layers.{i}.{theirs}.weight"] = _f32(lm[f"block_{i}"][ours]["scale"])
    if cfg.loop_gate:
        sd["model.early_exit_gate.weight"] = _f32(lm["exit_gate"]["kernel"]).T
        sd["model.early_exit_gate.bias"] = _f32(lm["exit_gate"]["bias"])
    return sd


def _export_lfm2_moe(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_lfm2_moe`: the experts held go out under their
    indices in the whole model."""
    sd = {
        "model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.embedding_norm.weight": _f32(lm["ln_f"]["scale"]),
    }
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.layers.{i}."
        sd[p + "operator_norm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "ffn_norm.weight"] = _f32(b["ln_mlp"]["scale"])
        if "conv" in b:
            sd[p + "conv.in_proj.weight"] = _f32(b["conv"]["in_proj"]["kernel"]).T
            sd[p + "conv.conv.weight"] = _f32(b["conv"]["kernel"]).T[:, None, :]
            sd[p + "conv.out_proj.weight"] = _f32(b["conv"]["out_proj"]["kernel"]).T
        else:
            for n, hf_n in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"),
                            ("o_proj", "out_proj")):
                sd[p + f"self_attn.{hf_n}.weight"] = _f32(b["attn"][n]["kernel"]).T
            sd[p + "self_attn.q_layernorm.weight"] = _f32(b["attn"]["q_norm"]["scale"])
            sd[p + "self_attn.k_layernorm.weight"] = _f32(b["attn"]["k_norm"]["scale"])
        if "router" not in b["mlp"]:
            for n, w in _LFM2_FFN:
                sd[p + f"feed_forward.{w}.weight"] = _f32(b["mlp"][n]["kernel"]).T
            continue
        sd[p + "feed_forward.gate.weight"] = _f32(b["mlp"]["router"]["kernel"]).T
        sd[p + "feed_forward.expert_bias"] = _f32(b["mlp"]["expert_bias"]["bias"])
        for n, w in _LFM2_FFN:
            stack = _f32(b["mlp"][f"expert_{n.split('_')[0]}"]["kernel"])
            for g, mat in enumerate(np.split(stack, cfg.experts_held, axis=1)):
                sd[p + f"feed_forward.experts.{cfg.moe_local_offset + g}.{w}.weight"] = mat.T
    return sd


def _export_smallthinker(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_smallthinker`: the experts held go out under their
    indices in the whole model."""
    sd = {"model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
          "model.norm.weight": _f32(lm["ln_f"]["scale"]),
          "lm_head.weight": _f32(lm["lm_head"]["kernel"]).T}
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "post_attention_layernorm.weight"] = _f32(b["ln_mlp"]["scale"])
        for n in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[p + f"self_attn.{n}.weight"] = _f32(b["attn"][n]["kernel"]).T
        moe = p + "block_sparse_moe."
        sd[moe + "primary_router.weight"] = _f32(b["mlp"]["router"]["kernel"]).T
        for n in ("gate", "up", "down"):
            for g, mat in enumerate(np.split(_f32(b["mlp"][f"expert_{n}"]["kernel"]), cfg.experts_held, axis=1)):
                sd[moe + f"experts.{cfg.moe_local_offset + g}.{n}.weight"] = mat.T
    return sd


def _export_pangu_block(b: Dict, p: str, cfg: TransformerConfig, sd: Dict) -> None:
    for n, hf_n in _PANGU_NORMS:
        sd[p + f"{hf_n}.weight"] = _f32(b[n]["scale"])
    for n, hf_n in _PANGU_ATTN:
        sd[p + f"self_attn.{hf_n}.weight"] = _f32(b["attn"][n]["kernel"]).T
    for n, hf_n in _PANGU_ATTN_NORMS:
        sd[p + f"self_attn.{hf_n}.weight"] = _f32(b["attn"][n]["scale"])
    if "router" not in b["mlp"]:
        for n in _GLU:
            sd[p + f"mlp.{n}.weight"] = _f32(b["mlp"][n]["kernel"]).T
        return
    if np.any(_f32(b["mlp"]["expert_bias"]["bias"]) != 0):
        raise NotImplementedError("pangu_ultra_moe has no selection bias: a nonzero expert_bias cannot be exported")
    sd[p + "mlp.gate.weight"] = _f32(b["mlp"]["router"]["kernel"]).T
    for n in _GLU:
        stack = _f32(b["mlp"][f"expert_{n.split('_')[0]}"]["kernel"])
        for g, mat in enumerate(np.split(stack, cfg.experts_held, axis=1)):
            sd[p + f"mlp.experts.{cfg.moe_local_offset + g}.{n}.weight"] = mat.T
        sd[p + f"mlp.shared_experts.{n}.weight"] = _f32(b["mlp"][f"shared_{n.split('_')[0]}"]["kernel"]).T


def _export_pangu_ultra_moe(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_pangu_ultra_moe` (as unchecked against the published
    weights): the experts held go out under their indices in the whole model."""
    sd = {
        "model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.norm.weight": _f32(lm["ln_f"]["scale"]),
        "lm_head.weight": _f32(lm["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.n_layers):
        _export_pangu_block(lm[f"block_{i}"], f"model.layers.{i}.", cfg, sd)
    for k in range(cfg.mtp_layers):
        m, p = lm[f"mtp_{k}"], f"model.layers.{cfg.n_layers + k}."
        sd[p + "enorm.weight"], sd[p + "hnorm.weight"] = _f32(m["enorm"]["scale"]), _f32(m["hnorm"]["scale"])
        sd[p + "eh_proj.weight"] = _f32(m["eh_proj"]["kernel"]).T
        _export_pangu_block(m["block"], p, cfg, sd)
    return sd


def _export_dots3_note(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_dots3_note` (as unchecked against the published
    weights): the experts held go out under their indices in the whole model."""
    sd = {
        "model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.norm.weight": _f32(lm["ln_f"]["scale"]),
        "lm_head.weight": _f32(lm["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.layers.{i}."
        for n, hf_n in _DOTS3_NORMS:
            sd[p + f"{hf_n}.weight"] = _f32(b[n]["scale"])
        for n, hf_n in _DOTS3_ATTN:
            sd[p + f"self_attn.{hf_n}.weight"] = _f32(b["attn"][n]["kernel"]).T
        for n, hf_n in _PANGU_ATTN_NORMS:
            sd[p + f"self_attn.{hf_n}.weight"] = _f32(b["attn"][n]["scale"])
        if "indexer" in b["attn"]:
            index = b["attn"]["indexer"]
            for n in _DOTS3_INDEX:
                sd[p + f"self_attn.indexer.{n}.weight"] = _f32(index[n]["kernel"]).T
            sd[p + "self_attn.indexer.k_norm.weight"] = _f32(index["k_norm"]["scale"])
            sd[p + "self_attn.indexer.k_norm.bias"] = _f32(index["k_norm"]["bias"])
        if "router" not in b["mlp"]:
            for n in _GLU:
                sd[p + f"mlp.{n}.weight"] = _f32(b["mlp"][n]["kernel"]).T
            continue
        sd[p + "mlp.gate.weight"] = _f32(b["mlp"]["router"]["kernel"]).T
        sd[p + "mlp.gate.e_score_correction_bias"] = _f32(b["mlp"]["expert_bias"]["bias"])
        for n in _GLU:
            stack = _f32(b["mlp"][f"expert_{n.split('_')[0]}"]["kernel"])
            for g, mat in enumerate(np.split(stack, cfg.experts_held, axis=1)):
                sd[p + f"mlp.experts.{cfg.moe_local_offset + g}.{n}.weight"] = mat.T
            sd[p + f"mlp.shared_experts.{n}.weight"] = _f32(b["mlp"][f"shared_{n.split('_')[0]}"]["kernel"]).T
    return sd


def _export_falcon_h1(lm: Dict, cfg: TransformerConfig) -> Dict:
    """Inverse of `_load_falcon_h1` (as unchecked against the published weights)."""
    sd = {
        "model.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.final_layernorm.weight": _f32(lm["ln_f"]["scale"]),
    }
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = _f32(lm["lm_head"]["kernel"]).T
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.layers.{i}."
        for n, hf_n in _FALCON_NORMS:
            sd[p + f"{hf_n}.weight"] = _f32(b[n]["scale"])
        for n in _FALCON_ATTN:
            sd[p + f"self_attn.{n}.weight"] = _f32(b["attn"][n]["kernel"]).T
        for n in _GLU:
            sd[p + f"feed_forward.{n}.weight"] = _f32(b["mlp"][n]["kernel"]).T
        ssm = b["ssm"]
        sd[p + "mamba.in_proj.weight"] = _f32(ssm["in_proj"]["kernel"]).T
        sd[p + "mamba.out_proj.weight"] = _f32(ssm["out_proj"]["kernel"]).T
        sd[p + "mamba.conv1d.weight"] = _f32(ssm["conv1d"]["kernel"]).T[:, None, :]
        sd[p + "mamba.conv1d.bias"] = _f32(ssm["conv1d"]["bias"])
        sd[p + "mamba.norm.weight"] = _f32(ssm["norm"]["scale"])
        for leaf, hf_n in _FALCON_HEAD:
            sd[p + f"mamba.{hf_n}"] = _f32(ssm[leaf[0]][leaf[1]])
    return sd


def _fuse_qkv_per_head(q, k, v, n_heads, head_dim):
    """Inverse of _split_fused_qkv_per_head."""
    shape = q.shape[:-1]
    stack = np.stack(
        [x.reshape(shape + (n_heads, head_dim)) for x in (q, k, v)], axis=-2
    )  # [..., heads, 3, hd]
    return stack.reshape(shape + (n_heads * 3 * head_dim,))


def _export_gpt_neox(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "gpt_neox.embed_in.weight": _f32(lm["embed_tokens"]["embedding"]),
        "gpt_neox.final_layer_norm.weight": _f32(lm["ln_f"]["scale"]),
        "gpt_neox.final_layer_norm.bias": _f32(lm["ln_f"]["bias"]),
        "embed_out.weight": _f32(lm["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"gpt_neox.layers.{i}."
        sd[p + "input_layernorm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "input_layernorm.bias"] = _f32(b["ln_attn"]["bias"])
        sd[p + "post_attention_layernorm.weight"] = _f32(b["ln_mlp"]["scale"])
        sd[p + "post_attention_layernorm.bias"] = _f32(b["ln_mlp"]["bias"])
        sd[p + "attention.query_key_value.weight"] = _fuse_qkv_per_head(
            *( _f32(b["attn"][n]["kernel"]) for n in ("q_proj", "k_proj", "v_proj")),
            cfg.n_heads, cfg.head_dim,
        ).T
        sd[p + "attention.query_key_value.bias"] = _fuse_qkv_per_head(
            *( _f32(b["attn"][n]["bias"]) for n in ("q_proj", "k_proj", "v_proj")),
            cfg.n_heads, cfg.head_dim,
        )
        sd[p + "attention.dense.weight"] = _f32(b["attn"]["o_proj"]["kernel"]).T
        sd[p + "attention.dense.bias"] = _f32(b["attn"]["o_proj"]["bias"])
        sd[p + "mlp.dense_h_to_4h.weight"] = _f32(b["mlp"]["up_proj"]["kernel"]).T
        sd[p + "mlp.dense_h_to_4h.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "mlp.dense_4h_to_h.weight"] = _f32(b["mlp"]["down_proj"]["kernel"]).T
        sd[p + "mlp.dense_4h_to_h.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    return sd


def _export_gptj(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "transformer.wte.weight": _f32(lm["embed_tokens"]["embedding"]),
        "transformer.ln_f.weight": _f32(lm["ln_f"]["scale"]),
        "transformer.ln_f.bias": _f32(lm["ln_f"]["bias"]),
        "lm_head.weight": _f32(lm["lm_head"]["kernel"]).T,
        "lm_head.bias": _f32(lm["lm_head"]["bias"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "ln_1.bias"] = _f32(b["ln_attn"]["bias"])
        qw = _permute_rotary_cols(_f32(b["attn"]["q_proj"]["kernel"]), cfg, cfg.n_heads, inverse=True)
        kw = _permute_rotary_cols(_f32(b["attn"]["k_proj"]["kernel"]), cfg, cfg.kv_heads, inverse=True)
        sd[p + "attn.q_proj.weight"] = qw.T
        sd[p + "attn.k_proj.weight"] = kw.T
        sd[p + "attn.v_proj.weight"] = _f32(b["attn"]["v_proj"]["kernel"]).T
        sd[p + "attn.out_proj.weight"] = _f32(b["attn"]["o_proj"]["kernel"]).T
        sd[p + "mlp.fc_in.weight"] = _f32(b["mlp"]["up_proj"]["kernel"]).T
        sd[p + "mlp.fc_in.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "mlp.fc_out.weight"] = _f32(b["mlp"]["down_proj"]["kernel"]).T
        sd[p + "mlp.fc_out.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    return sd


def _export_opt(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "model.decoder.embed_tokens.weight": _f32(lm["embed_tokens"]["embedding"]),
        "model.decoder.embed_positions.weight": _f32(lm["embed_pos"]["embedding"]),
        "model.decoder.final_layer_norm.weight": _f32(lm["ln_f"]["scale"]),
        "model.decoder.final_layer_norm.bias": _f32(lm["ln_f"]["bias"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"model.decoder.layers.{i}."
        sd[p + "self_attn_layer_norm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "self_attn_layer_norm.bias"] = _f32(b["ln_attn"]["bias"])
        sd[p + "final_layer_norm.weight"] = _f32(b["ln_mlp"]["scale"])
        sd[p + "final_layer_norm.bias"] = _f32(b["ln_mlp"]["bias"])
        for our, hf in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                        ("v_proj", "v_proj"), ("o_proj", "out_proj")):
            sd[p + f"self_attn.{hf}.weight"] = _f32(b["attn"][our]["kernel"]).T
            sd[p + f"self_attn.{hf}.bias"] = _f32(b["attn"][our]["bias"])
        sd[p + "fc1.weight"] = _f32(b["mlp"]["up_proj"]["kernel"]).T
        sd[p + "fc1.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "fc2.weight"] = _f32(b["mlp"]["down_proj"]["kernel"]).T
        sd[p + "fc2.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    sd["lm_head.weight"] = sd["model.decoder.embed_tokens.weight"]
    return sd


def _export_bloom(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "transformer.word_embeddings.weight": _f32(lm["embed_tokens"]["embedding"]),
        "transformer.word_embeddings_layernorm.weight": _f32(lm["ln_embed"]["scale"]),
        "transformer.word_embeddings_layernorm.bias": _f32(lm["ln_embed"]["bias"]),
        "transformer.ln_f.weight": _f32(lm["ln_f"]["scale"]),
        "transformer.ln_f.bias": _f32(lm["ln_f"]["bias"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"transformer.h.{i}."
        sd[p + "input_layernorm.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "input_layernorm.bias"] = _f32(b["ln_attn"]["bias"])
        sd[p + "post_attention_layernorm.weight"] = _f32(b["ln_mlp"]["scale"])
        sd[p + "post_attention_layernorm.bias"] = _f32(b["ln_mlp"]["bias"])
        sd[p + "self_attention.query_key_value.weight"] = _fuse_qkv_per_head(
            *( _f32(b["attn"][n]["kernel"]) for n in ("q_proj", "k_proj", "v_proj")),
            cfg.n_heads, cfg.head_dim,
        ).T
        sd[p + "self_attention.query_key_value.bias"] = _fuse_qkv_per_head(
            *( _f32(b["attn"][n]["bias"]) for n in ("q_proj", "k_proj", "v_proj")),
            cfg.n_heads, cfg.head_dim,
        )
        sd[p + "self_attention.dense.weight"] = _f32(b["attn"]["o_proj"]["kernel"]).T
        sd[p + "self_attention.dense.bias"] = _f32(b["attn"]["o_proj"]["bias"])
        sd[p + "mlp.dense_h_to_4h.weight"] = _f32(b["mlp"]["up_proj"]["kernel"]).T
        sd[p + "mlp.dense_h_to_4h.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "mlp.dense_4h_to_h.weight"] = _f32(b["mlp"]["down_proj"]["kernel"]).T
        sd[p + "mlp.dense_4h_to_h.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    sd["lm_head.weight"] = sd["transformer.word_embeddings.weight"]
    return sd


def _export_gpt_bigcode(lm: Dict, cfg: TransformerConfig) -> Dict:
    sd = {
        "transformer.wte.weight": _f32(lm["embed_tokens"]["embedding"]),
        "transformer.wpe.weight": _f32(lm["embed_pos"]["embedding"]),
        "transformer.ln_f.weight": _f32(lm["ln_f"]["scale"]),
        "transformer.ln_f.bias": _f32(lm["ln_f"]["bias"]),
    }
    for i in range(cfg.n_layers):
        b, p = lm[f"block_{i}"], f"transformer.h.{i}."
        sd[p + "ln_1.weight"] = _f32(b["ln_attn"]["scale"])
        sd[p + "ln_1.bias"] = _f32(b["ln_attn"]["bias"])
        sd[p + "ln_2.weight"] = _f32(b["ln_mlp"]["scale"])
        sd[p + "ln_2.bias"] = _f32(b["ln_mlp"]["bias"])
        sd[p + "attn.c_attn.weight"] = np.concatenate(
            [_f32(b["attn"][n]["kernel"]) for n in ("q_proj", "k_proj", "v_proj")], axis=1
        ).T
        sd[p + "attn.c_attn.bias"] = np.concatenate(
            [_f32(b["attn"][n]["bias"]) for n in ("q_proj", "k_proj", "v_proj")], axis=0
        )
        sd[p + "attn.c_proj.weight"] = _f32(b["attn"]["o_proj"]["kernel"]).T
        sd[p + "attn.c_proj.bias"] = _f32(b["attn"]["o_proj"]["bias"])
        sd[p + "mlp.c_fc.weight"] = _f32(b["mlp"]["up_proj"]["kernel"]).T
        sd[p + "mlp.c_fc.bias"] = _f32(b["mlp"]["up_proj"]["bias"])
        sd[p + "mlp.c_proj.weight"] = _f32(b["mlp"]["down_proj"]["kernel"]).T
        sd[p + "mlp.c_proj.bias"] = _f32(b["mlp"]["down_proj"]["bias"])
    sd["lm_head.weight"] = sd["transformer.wte.weight"]
    return sd


def _export_t5(lm: Dict, cfg) -> Dict:
    """Inverse of _load_t5: Seq2SeqLM subtree -> T5ForConditionalGeneration
    state dict (incl. the per-stack embed_tokens copies HF checkpoints
    carry)."""
    def attn(b, name):
        a = b[name]
        return {
            "q.weight": _f32(a["q_proj"]["kernel"]).T,
            "k.weight": _f32(a["k_proj"]["kernel"]).T,
            "v.weight": _f32(a["v_proj"]["kernel"]).T,
            "o.weight": _f32(a["o_proj"]["kernel"]).T,
        }

    def mlp(b):
        m = b["mlp"]
        if cfg.glu:
            return {
                "wi_0.weight": _f32(m["gate_proj"]["kernel"]).T,
                "wi_1.weight": _f32(m["up_proj"]["kernel"]).T,
                "wo.weight": _f32(m["down_proj"]["kernel"]).T,
            }
        return {
            "wi.weight": _f32(m["up_proj"]["kernel"]).T,
            "wo.weight": _f32(m["down_proj"]["kernel"]).T,
        }

    shared = _f32(lm["embed_tokens"]["embedding"])
    sd = {
        "shared.weight": shared,
        "encoder.embed_tokens.weight": shared,
        "decoder.embed_tokens.weight": shared,
        "encoder.final_layer_norm.weight": _f32(lm["enc_ln_f"]["scale"]),
        "decoder.final_layer_norm.weight": _f32(lm["dec_ln_f"]["scale"]),
        "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight":
            _f32(lm["enc_rel_bias"]["embedding"]["embedding"]),
        "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight":
            _f32(lm["dec_rel_bias"]["embedding"]["embedding"]),
    }
    for i in range(cfg.n_encoder_layers):
        b, p = lm[f"enc_block_{i}"], f"encoder.block.{i}."
        for k, v in attn(b, "attn").items():
            sd[p + "layer.0.SelfAttention." + k] = v
        sd[p + "layer.0.layer_norm.weight"] = _f32(b["ln_attn"]["scale"])
        for k, v in mlp(b).items():
            sd[p + "layer.1.DenseReluDense." + k] = v
        sd[p + "layer.1.layer_norm.weight"] = _f32(b["ln_mlp"]["scale"])
    for i in range(cfg.n_decoder_layers):
        b, p = lm[f"dec_block_{i}"], f"decoder.block.{i}."
        for k, v in attn(b, "attn").items():
            sd[p + "layer.0.SelfAttention." + k] = v
        sd[p + "layer.0.layer_norm.weight"] = _f32(b["ln_attn"]["scale"])
        for k, v in attn(b, "cross_attn").items():
            sd[p + "layer.1.EncDecAttention." + k] = v
        sd[p + "layer.1.layer_norm.weight"] = _f32(b["ln_cross"]["scale"])
        for k, v in mlp(b).items():
            sd[p + "layer.2.DenseReluDense." + k] = v
        sd[p + "layer.2.layer_norm.weight"] = _f32(b["ln_mlp"]["scale"])
    sd["lm_head.weight"] = (
        shared if cfg.tie_embeddings else _f32(lm["lm_head"]["kernel"]).T
    )
    return sd


_EXPORTERS: Dict[str, Callable] = {
    "t5": _export_t5,
    "gpt2": _export_gpt2,
    "llama": _export_llama,
    "gpt_neox": _export_gpt_neox,
    "gptj": _export_gptj,
    "opt": _export_opt,
    "bloom": _export_bloom,
    "gpt_bigcode": _export_gpt_bigcode,
    "lfm2_moe": _export_lfm2_moe,
    "pangu_ultra_moe": _export_pangu_ultra_moe,
    "ouro": _export_ouro,
    "dots3_note": _export_dots3_note,
    "falcon_h1": _export_falcon_h1,
    "smallthinker": _export_smallthinker,
}


def infer_family(cfg) -> str:
    """Best-effort family inference from a model config's structure
    (used when exporting a model that wasn't loaded from an HF dir)."""
    if getattr(cfg, "is_seq2seq", False):
        return "t5"
    if getattr(cfg, "loop_steps", 1) > 1:
        return "ouro"
    if getattr(cfg, "has_conv_layers", False):
        return "lfm2_moe"
    if getattr(cfg, "has_ssm_layers", False):
        return "falcon_h1"
    if getattr(cfg, "has_linear_layers", False):
        return "ling_flash" if cfg.has_latent_layers else "solar_open2"
    if getattr(cfg, "latent_kinds", ()):
        return "dots3_note"
    if getattr(cfg, "has_latent_layers", False):
        return "pangu_ultra_moe"
    if getattr(cfg, "attention_kinds", ()):
        return "smallthinker" if cfg.moe_route_on == "block_input" else "laguna"
    if cfg.alibi:
        return "bloom"
    if cfg.pos_offset:
        return "opt"
    if cfg.parallel_residual:
        return "gptj" if cfg.shared_ln else "gpt_neox"
    if cfg.pos_embed == "rope":
        return "llama"
    if (cfg.n_kv_heads or cfg.n_heads) != cfg.n_heads:
        return "gpt_bigcode"
    return "gpt2"


def params_to_hf_state_dict(params: Dict, cfg: TransformerConfig, family: str = None) -> Dict:
    """Export our LM params back to an HF-layout state dict for
    `save_pretrained` interop."""
    family = family or cfg.hf_family or infer_family(cfg)
    if family not in _EXPORTERS:
        raise NotImplementedError(
            f"no tensor-name mapping for HF family {family!r}: its config keys convert "
            "(config_to_hf), its checkpoint's tensor names are not public")
    return _EXPORTERS[family](params["lm"], cfg)


def config_to_hf(cfg: TransformerConfig, family: str = None) -> Dict:
    """Inverse of config_from_hf: a loadable HF config dict (model_type +
    architectures included), so `save_pretrained` exports are
    self-contained — including models born from `random:` presets with no
    source config.json to copy."""
    family = family or cfg.hf_family or infer_family(cfg)
    if family == "t5":
        # inverse of _seq2seq_config_from_hf's activation mapping: HF runs
        # ACT2FN[dense_act_fn], where 'gelu' is exact-erf and 'gelu_new'
        # is the tanh approx; 'gated-gelu' forces gelu_new on import so it
        # round-trips to our "gelu"
        if cfg.glu:
            if cfg.activation == "gelu_exact":
                raise ValueError(
                    "T5 cannot express a gated exact-erf GELU "
                    "(gated-gelu always runs gelu_new)"
                )
            ffp = {"gelu": "gated-gelu", "silu": "gated-silu",
                   "relu": "gated-relu"}[cfg.activation]
        else:
            ffp = {"relu": "relu", "gelu_exact": "gelu", "silu": "silu",
                   "gelu": "gelu_new"}[cfg.activation]
        return dict(
            model_type="t5", architectures=["T5ForConditionalGeneration"],
            is_encoder_decoder=True,
            vocab_size=cfg.vocab_size, d_model=cfg.d_model, d_kv=cfg.head_dim,
            d_ff=cfg.d_ff, num_layers=cfg.n_encoder_layers,
            num_decoder_layers=cfg.n_decoder_layers, num_heads=cfg.n_heads,
            relative_attention_num_buckets=cfg.relative_attention_num_buckets,
            relative_attention_max_distance=cfg.relative_attention_max_distance,
            feed_forward_proj=ffp,
            tie_word_embeddings=cfg.tie_embeddings,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
            decoder_start_token_id=cfg.decoder_start_token_id,
            # preserve the SOURCE tokenizer's ids (recorded at import);
            # models born from presets fall back to T5 conventions
            pad_token_id=(cfg.pad_token_id if cfg.pad_token_id is not None
                          else cfg.decoder_start_token_id),
            eos_token_id=cfg.eos_token_id if cfg.eos_token_id is not None else 1,
        )
    if family == "smallthinker":
        banded = [int(kind == "sliding_attention") for kind in cfg.layer_types]
        return dict(
            model_type="smallthinker", architectures=["SmallThinkerForCausalLM"], vocab_size=cfg.vocab_size,
            hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim, max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.layer_norm_epsilon, moe_ffn_hidden_size=cfg.expert_d_ff,
            moe_num_primary_experts=cfg.moe_experts, moe_num_active_primary_experts=cfg.moe_top_k,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True, rope_layout=banded,
            sliding_window_layout=banded, sliding_window_size=cfg.sliding_window, rope_scaling=None,
            rope_theta=cfg.rope_of("sliding_attention").theta, tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "laguna":
        def rope(spec):
            entry = dict(rope_type="default", rope_theta=spec.theta, partial_rotary_factor=spec.pct)
            if spec.yarn_factor > 0:
                entry.update(rope_type="yarn", factor=spec.yarn_factor,
                             original_max_position_embeddings=spec.yarn_original_max,
                             beta_fast=spec.yarn_beta_fast, beta_slow=spec.yarn_beta_slow,
                             attention_factor=spec.attention_factor)
            return entry

        return dict(
            model_type="laguna", vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            max_position_embeddings=cfg.max_seq_len, attention_bias=False,
            rms_norm_eps=cfg.layer_norm_epsilon, num_experts=cfg.moe_experts,
            num_experts_per_tok=cfg.moe_top_k, moe_intermediate_size=cfg.expert_d_ff,
            shared_expert_intermediate_size=cfg.moe_shared_d_ff, tie_word_embeddings=cfg.tie_embeddings,
            gating=cfg.attn_gate == "per_head", sliding_window=cfg.sliding_window,
            rope_parameters={kind: rope(spec) for kind, spec in cfg.rope_kinds},
            layer_types=list(cfg.layer_types), moe_apply_router_weight_on_input=False,
            mlp_layer_types=["dense" if i < cfg.moe_dense_layers else "sparse" for i in range(cfg.n_layers)],
            moe_routed_scaling_factor=cfg.moe_routed_scale,
            num_attention_heads_per_layer=list(cfg.layer_heads),
        )
    if family == "ling_flash":
        period = next((i + 1 for i, kind in enumerate(cfg.layer_types) if kind == "latent_attention"), cfg.n_layers + 1)
        return dict(
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
            num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_heads,
            head_dim=cfg.head_dim, max_position_embeddings=cfg.max_seq_len, rms_norm_eps=cfg.layer_norm_epsilon,
            rope_theta=cfg.rope_theta, q_lora_rank=cfg.q_lora_rank or None, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, rotary_dim=cfg.qk_rope_head_dim, use_qk_norm=cfg.qk_norm,
            layer_group_size=period, short_conv_kernel_size=cfg.conv_kernel, linear_silu=True,
            kda_safe_gate=True, kda_lower_bound=cfg.kda_lower_bound,
            no_kda_lora=True, use_kda_lora=False, group_norm_size=1, num_kv_heads_for_linear_attn=0,
            gated_attention_proj_granularity_type="head_wise",
            first_k_dense_replace=cfg.moe_dense_layers, num_experts=cfg.moe_experts,
            num_experts_per_tok=cfg.moe_top_k, moe_intermediate_size=cfg.expert_d_ff,
            moe_shared_expert_intermediate_size=cfg.moe_shared_d_ff, n_group=cfg.moe_n_group,
            topk_group=cfg.moe_topk_group, score_function="sigmoid", moe_router_enable_expert_bias=True,
            norm_topk_prob=True, routed_scaling_factor=cfg.moe_routed_scale,
            expert_swiglu_limit_list=[0] * cfg.n_layers, share_expert_swiglu_limit_list=[0] * cfg.n_layers,
            tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "solar_open2":
        return dict(
            model_type="solar_open2", vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim, max_position_embeddings=cfg.max_seq_len,
            rms_norm_eps=cfg.layer_norm_epsilon, use_rope=False, use_gqa_gate=True,
            gqa_layers=[i for i, kind in enumerate(cfg.layer_types) if kind == "attention"],
            linear_attn_config=dict(short_conv_kernel_size=cfg.conv_kernel, head_dim=cfg.head_dim,
                                    num_heads=cfg.n_heads, num_kv_heads=None),
            kda_use_full_proj=False, kda_allow_neg_eigval=cfg.kda_beta_max > 1.0,
            first_k_dense_replace=0, n_routed_experts=cfg.moe_experts, n_shared_experts=1,
            num_experts_per_tok=cfg.moe_top_k, moe_intermediate_size=cfg.expert_d_ff, norm_topk_prob=True,
            routed_scaling_factor=cfg.moe_routed_scale, tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "falcon_h1":
        m = cfg.multipliers
        return dict(
            model_type="falcon_h1", architectures=["FalconH1ForCausalLM"], vocab_size=cfg.vocab_size,
            hidden_size=cfg.d_model, intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers,
            num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            max_position_embeddings=cfg.max_seq_len, rms_norm_eps=cfg.layer_norm_epsilon,
            rope_theta=cfg.rope_theta, rope_scaling=None, hidden_act="silu", attention_bias=False,
            mlp_bias=False, projectors_bias=False, attn_layer_indices=None,
            tie_word_embeddings=cfg.tie_embeddings,
            mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim, mamba_d_ssm=cfg.ssm_heads * cfg.ssm_head_dim,
            mamba_d_state=cfg.ssm_state, mamba_n_groups=cfg.ssm_groups, mamba_d_conv=cfg.ssm_conv_kernel,
            mamba_chunk_size=cfg.ssm_chunk, mamba_conv_bias=True, mamba_proj_bias=False, mamba_rms_norm=True,
            mamba_norm_before_gate=False, mamba_use_mlp=True,
            embedding_multiplier=m.embedding, lm_head_multiplier=m.lm_head,
            attention_in_multiplier=m.attention_in, attention_out_multiplier=m.attention_out,
            key_multiplier=m.key, ssm_in_multiplier=m.ssm_in, ssm_out_multiplier=m.ssm_out,
            ssm_multipliers=list(m.ssm), mlp_multipliers=list(m.mlp),
        )
    if family == "dots3_note":
        kinds = {ours: theirs for theirs, ours in _DOTS3_KINDS.items()}
        full, swa = cfg.latent_of("sparse_latent_attention"), cfg.latent_of("sliding_latent_attention")
        shape = lambda pre, spec: {
            pre + "num_attention_heads": spec.n_heads, pre + "num_key_value_heads": spec.n_heads,
            pre + "q_lora_rank": spec.q_lora_rank, pre + "kv_lora_rank": spec.kv_lora_rank,
            pre + "qk_nope_head_dim": spec.qk_nope_head_dim, pre + "qk_rope_head_dim": spec.qk_rope_head_dim,
            pre + "v_head_dim": spec.v_head_dim}
        return dict(
            model_type="dots3_note", vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers, max_position_embeddings=cfg.max_seq_len,
            attention_bias=False, hidden_act="silu", rms_norm_eps=cfg.layer_norm_epsilon, rope_scaling=None,
            layer_types=[kinds[kind] for kind in cfg.layer_types], **shape("", full), **shape("swa_", swa),
            rope_theta=cfg.rope_of("sparse_latent_attention").theta,
            swa_rope_theta=cfg.rope_of("sliding_latent_attention").theta, sliding_window_size=swa.window,
            index_n_heads=full.index_heads, index_head_dim=full.index_head_dim, index_topk=full.index_topk,
            apply_mla_qkv_lora_rescale=full.rescale, attention_gate_type="headwise",
            swa_attention_gate_type="headwise", first_k_dense_replace=cfg.moe_dense_layers, moe_layer_freq=1,
            n_routed_experts=cfg.moe_experts, n_shared_experts=1, num_experts_per_tok=cfg.moe_top_k,
            moe_intermediate_size=cfg.expert_d_ff, norm_topk_prob=True, scoring_func="sigmoid",
            topk_method="noaux_tc", routed_scaling_factor=cfg.moe_routed_scale,
            tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "ouro":
        return dict(
            model_type="ouro", architectures=["OuroForCausalLM"], vocab_size=cfg.vocab_size,
            hidden_size=cfg.d_model, intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers,
            num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            hidden_act="silu", max_position_embeddings=cfg.max_seq_len, max_window_layers=cfg.n_layers,
            layer_types=["full_attention"] * cfg.n_layers, rms_norm_eps=cfg.layer_norm_epsilon,
            rope_scaling=None, rope_theta=cfg.rope_theta, sliding_window=None, use_sliding_window=False,
            tie_word_embeddings=cfg.tie_embeddings, total_ut_steps=cfg.loop_steps,
            early_exit_threshold=cfg.loop_exit_threshold,
        )
    if family == "pangu_ultra_moe":
        return dict(
            model_type="pangu_ultra_moe", vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.n_heads, max_position_embeddings=cfg.max_seq_len, attention_bias=False,
            hidden_act="silu", rms_norm_eps=cfg.layer_norm_epsilon, rope_theta=cfg.rope_theta,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, sandwich_norm=cfg.sandwich_norm,
            num_nextn_predict_layers=cfg.mtp_layers, first_k_dense_replace=cfg.moe_dense_layers,
            n_routed_experts=cfg.moe_experts, n_shared_experts=1, num_experts_per_tok=cfg.moe_top_k,
            moe_intermediate_size=cfg.expert_d_ff, norm_topk_prob=True,
            routed_scaling_factor=cfg.moe_routed_scale, tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "lfm2_moe":
        return dict(
            model_type="lfm2_moe", architectures=["Lfm2MoeForCausalLM"],
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
            num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.kv_heads,
            intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.expert_d_ff,
            max_position_embeddings=cfg.max_seq_len, rope_theta=cfg.rope_theta,
            norm_eps=cfg.layer_norm_epsilon, conv_L_cache=cfg.conv_kernel, conv_bias=False,
            layer_types=["conv" if t == "conv" else "full_attention" for t in cfg.layer_types],
            num_dense_layers=cfg.moe_dense_layers, num_experts=cfg.moe_experts,
            num_experts_per_tok=cfg.moe_top_k, norm_topk_prob=True, use_expert_bias=True,
            routed_scaling_factor=1, tie_embedding=cfg.tie_embeddings,
        )
    if family == "gpt2":
        return dict(
            model_type="gpt2", architectures=["GPT2LMHeadModel"],
            vocab_size=cfg.vocab_size, n_embd=cfg.d_model, n_layer=cfg.n_layers,
            n_head=cfg.n_heads, n_inner=cfg.d_ff, n_positions=cfg.max_seq_len,
            n_ctx=cfg.max_seq_len, layer_norm_epsilon=cfg.layer_norm_epsilon,
            activation_function="gelu_new",
            tie_word_embeddings=cfg.tie_embeddings,
        )
    if family == "llama":
        mistral = cfg.sliding_window is not None
        return dict(
            model_type="mistral" if mistral else "llama",
            architectures=["MistralForCausalLM" if mistral else "LlamaForCausalLM"],
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.kv_heads, intermediate_size=cfg.d_ff,
            max_position_embeddings=cfg.max_seq_len, rope_theta=cfg.rope_theta,
            rms_norm_eps=cfg.layer_norm_epsilon,
            tie_word_embeddings=cfg.tie_embeddings, hidden_act="silu",
            **({"sliding_window": cfg.sliding_window} if mistral else {}),
        )
    if family == "gpt_neox":
        return dict(
            model_type="gpt_neox", architectures=["GPTNeoXForCausalLM"],
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            intermediate_size=cfg.d_ff, max_position_embeddings=cfg.max_seq_len,
            rotary_pct=cfg.rotary_pct, rotary_emb_base=cfg.rope_theta,
            use_parallel_residual=cfg.parallel_residual,
            tie_word_embeddings=cfg.tie_embeddings,
            layer_norm_eps=cfg.layer_norm_epsilon,
            # import maps hidden_act=="gelu" -> gelu_exact, else tanh-gelu
            hidden_act="gelu" if cfg.activation == "gelu_exact" else "gelu_new",
        )
    if family == "gptj":
        return dict(
            model_type="gptj", architectures=["GPTJForCausalLM"],
            vocab_size=cfg.vocab_size, n_embd=cfg.d_model, n_layer=cfg.n_layers,
            n_head=cfg.n_heads, n_inner=cfg.d_ff, n_positions=cfg.max_seq_len,
            rotary_dim=cfg.rotary_dim, layer_norm_epsilon=cfg.layer_norm_epsilon,
            activation_function="gelu_new",
        )
    if family == "opt":
        return dict(
            model_type="opt", architectures=["OPTForCausalLM"],
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
            ffn_dim=cfg.d_ff, max_position_embeddings=cfg.max_seq_len,
            do_layer_norm_before=True, word_embed_proj_dim=cfg.d_model,
            activation_function="relu" if cfg.activation == "relu" else "gelu",
        )
    if family == "bloom":
        if cfg.d_ff != 4 * cfg.d_model:
            # the HF bloom config has no d_ff field (import assumes 4x) —
            # raise here instead of crashing on kernel shapes at reload
            raise ValueError(
                f"bloom export requires d_ff == 4*d_model, got {cfg.d_ff}"
            )
        return dict(
            model_type="bloom", architectures=["BloomForCausalLM"],
            vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
            n_layer=cfg.n_layers, n_head=cfg.n_heads,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
        )
    if family == "gpt_bigcode":
        if cfg.kv_heads not in (1, cfg.n_heads):
            raise ValueError(
                "gpt_bigcode export supports multi_query (1 kv head) or "
                f"full MHA only, got n_kv_heads={cfg.kv_heads}"
            )
        return dict(
            model_type="gpt_bigcode", architectures=["GPTBigCodeForCausalLM"],
            vocab_size=cfg.vocab_size, n_embd=cfg.d_model, n_layer=cfg.n_layers,
            n_head=cfg.n_heads, n_inner=cfg.d_ff, n_positions=cfg.max_seq_len,
            multi_query=cfg.kv_heads == 1,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
        )
    raise ValueError(f"No HF config export for family '{family}'")
