"""Jitted autoregressive sampling engine.

This replaces HF `model.generate` (used by the reference at
accelerate_base_trainer.py:256-282) and the reference's two hand-written
token loops (ILQL Q-guided generate, modeling_ilql.py:325-412; NeMo
sampling loop, modeling_nemo_ppo.py:1158-1222) with ONE compiled
`lax.while_loop`: prefill the KV cache with the (left-padded, static-shape)
prompt batch, then decode step-by-step entirely on device. Per-step logit
processing covers temperature / top-k / top-p sampling, a transition
logit-mask (adjacency constraints, e.g. randomwalks), and the ILQL
beta*(Q-V) advantage shift — the reference needs a separate generate loop
per mode; here they are hooks on the same engine.

Early exit: the while_loop condition includes "all sequences finished", so
short generations stop early (like HF's `StoppingCriteria`) without
dynamic shapes — outputs are always [b, max_new_tokens], with a validity
mask. Stop-sequence trimming is string-level host-side post-processing
(trainer.decode, mirroring accelerate_base_trainer.py:203-254).
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.models.transformer import (
    TransformerConfig, init_kv_cache, live_width_index, live_widths, prefill_by_blocks)
from trlx_tpu.ops.ilql import topk_mask
from trlx_tpu.ops.quant import dequantize_tree


@dataclass(frozen=True)
class GenerationConfig:
    """HF-compatible generation knobs (reference default gen_kwargs:
    default_configs.py:52-57)."""

    max_new_tokens: int = 40
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    do_sample: bool = True
    eos_token_id: int = 0
    pad_token_id: int = 0
    min_new_tokens: int = 0
    # HF RepetitionPenaltyLogitsProcessor (the NeMo generate default,
    # modeling_nemo_ppo.py:1169): tokens seen so far (prompt included) get
    # positive logits divided / negative logits multiplied by this
    repetition_penalty: float = 1.0
    # > 1 switches to deterministic beam search (ops/beam_search.py — the
    # reference's HF generate num_beams, e.g. ppo_translation_t5.py:99)
    num_beams: int = 1
    length_penalty: float = 1.0
    # ILQL advantage shift (reference gen_kwargs beta, default_configs.py:92)
    beta: float = 1.0
    # HF SuppressTokensLogitsProcessor (GenerationConfig.suppress_tokens):
    # these ids get -inf at every decode step
    suppress_tokens: tuple = ()

    @classmethod
    def from_gen_kwargs(cls, gen_kwargs: Dict, eos_token_id: int, pad_token_id: int):
        kw = dict(gen_kwargs or {})
        kw.pop("max_length", None)
        return cls(
            max_new_tokens=int(kw.get("max_new_tokens", 40)),
            temperature=float(kw.get("temperature", 1.0)),
            top_k=int(kw.get("top_k", 0) or 0),
            top_p=float(kw.get("top_p", 1.0)),
            do_sample=bool(kw.get("do_sample", True)),
            min_new_tokens=int(kw.get("min_new_tokens", 0) or 0),
            repetition_penalty=float(kw.get("repetition_penalty", 1.0) or 1.0),
            num_beams=int(kw.get("num_beams", 1) or 1),
            length_penalty=float(kw.get("length_penalty", 1.0) or 1.0),
            beta=float(kw.get("beta", 1.0)),
            suppress_tokens=tuple(kw.get("suppress_tokens") or ()),
            eos_token_id=eos_token_id,
            pad_token_id=pad_token_id,
        )


def process_logits(
    logits: jnp.ndarray,  # [b, V] f32
    cfg: GenerationConfig,
    step: jnp.ndarray,
    seen: Optional[jnp.ndarray] = None,  # [b, V] bool: token appeared so far
) -> jnp.ndarray:
    """Repetition-penalty / temperature / top-k / top-p / min-new-tokens
    logit processing, matching HF LogitsProcessor order (repetition ->
    temperature -> top_k -> top_p)."""
    logits = logits.astype(jnp.float32)
    if cfg.repetition_penalty != 1.0 and seen is not None:
        p = cfg.repetition_penalty
        penalized = jnp.where(logits > 0, logits / p, logits * p)
        logits = jnp.where(seen, penalized, logits)
    if cfg.min_new_tokens > 0:
        # forbid EOS before min_new_tokens
        eos_penalty = jnp.where(step < cfg.min_new_tokens, -jnp.inf, 0.0)
        logits = logits.at[:, cfg.eos_token_id].add(eos_penalty)
    if cfg.do_sample and cfg.temperature not in (0.0, 1.0):
        logits = logits / cfg.temperature
    if cfg.top_k and cfg.top_k > 0:
        logits = topk_mask(logits, cfg.top_k)
    if cfg.do_sample and cfg.top_p < 1.0:
        logits = topp_mask(logits, cfg.top_p)
    return logits


def select_token(scores: jnp.ndarray, key, cfg: GenerationConfig) -> jnp.ndarray:
    """Pick next tokens from processed scores [b, V]: categorical sampling
    under do_sample (temperature 0 degrades to greedy, like HF), argmax
    otherwise. The ONE token-selection rule shared by the while-loop
    sampler below and the continuous-batching inference engine
    (trlx_tpu/inference/engine.py) — keeping greedy decode bit-identical
    between them."""
    if cfg.do_sample and cfg.temperature != 0.0:
        return jax.random.categorical(key, scores, axis=-1)
    return jnp.argmax(scores, axis=-1)


def sampled_token_logprob(raw_logits: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """Policy logprob of the chosen token, read off the RAW (pre-shift,
    pre-warper) f32 logits [b, V] — the same quantity
    `logprobs_of_labels` extracts from the batched scoring forward at
    that position. Shared by the rollout fast path
    (method.capture_rollout_stats) and the inference engine's fused
    decode step so both report true policy logprobs regardless of
    temperature/top-k/suppress warping."""
    lp = jax.nn.log_softmax(raw_logits, axis=-1)
    return jnp.take_along_axis(lp, token[:, None].astype(jnp.int32), axis=-1)[:, 0]


def topp_mask(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus mask: keep tokens until cumulative prob exceeds p (always
    keeping the top-1), set the rest to -inf. Shared by the sampling loop
    and beam-sample (ops/beam_search.py)."""
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs >= p
    threshold = jnp.where(cutoff_mask, jnp.inf, sorted_logits).min(axis=-1, keepdims=True)
    return jnp.where(logits < threshold, -jnp.inf, logits)


#: columns of a prefill block (`BlockPlan`), at the widths the cells run
PREFILL_BLOCK = 128


@dataclass(frozen=True)
class BlockPlan:
    """How `generate` follows a chunk's longest prompt inside ONE program of
    static shape `[rows, plen]` + `max_new`: the shapes, which are static,
    and the two things read from the chunk's first live column, which is
    data. The prompt block, left-padded to whole blocks (`pad`), is
    prefilled `block` columns at a time from the block that holds the first
    column any row has a token in; a decode step attends over the narrowest
    of a few suffixes of the cache that still holds that column
    (`transformer.live_widths`, chosen in `decode_step` from the cache's
    `first`). `first` is a host integer (the trainer's counters) or a traced
    scalar (the program), in the caller's columns."""

    block: int
    pad: int
    blocks: int
    columns: int  # the cache's: pad + plen + max_new

    @classmethod
    def of(cls, plen: int, max_new: int, block: int) -> "BlockPlan":
        pad = -plen % block
        return cls(block, pad, (plen + pad) // block, pad + plen + max_new)

    def first_block(self, first):
        return (first + self.pad) // self.block

    def read_columns(self, first: int) -> int:
        """The cache columns a decode step reads, of `columns`."""
        return live_widths(self.columns)[live_width_index(first + self.pad, self.columns)]


def block_plan(model_cfg: TransformerConfig, gen_cfg: GenerationConfig, plen: int,
               block: int = PREFILL_BLOCK, spec_k: int = 0) -> Optional[BlockPlan]:
    """The plan `make_generate_fn`'s program runs a prompt block of `plen`
    columns by, or None where it keeps the one-shot prefill: the sampler is
    the token-at-a-time causal loop (one beam, no speculative rounds), and
    the model's layers and the width allow it (`prefill_by_blocks`)."""
    if (block <= 0 or spec_k > 0 or gen_cfg.num_beams > 1 or getattr(model_cfg, "is_seq2seq", False)
            or not prefill_by_blocks(model_cfg, plen, block)):
        return None
    return BlockPlan.of(plen, gen_cfg.max_new_tokens, block)


def first_live_column(attn_mask):
    """The first column any row has a token in (0 where none has): numpy in,
    a host integer out; a traced mask in, a traced scalar out."""
    xp = np if isinstance(attn_mask, np.ndarray) else jnp
    return xp.argmax(attn_mask.astype(bool).any(axis=0)).astype(xp.int32)


def make_generate_fn(
    model,
    model_cfg: TransformerConfig,
    gen_cfg: GenerationConfig,
    mode: str = "lm",  # "lm" | "ilql"
    logit_mask: Optional[np.ndarray] = None,  # [V, V] True = forbidden transition
    two_qs: bool = True,
    capture: bool = False,
    capture_split: int = 0,
    spec_k: int = 0,  # > 0: self-speculative decode, k drafts per round
    spec_split: int = 0,  # hydra split = draft trunk depth (required when spec_k > 0)
    spec_draft_head: Optional[Tuple] = None,  # (A [d, r], B [r, V]) low-rank readout
    prefill_block: int = PREFILL_BLOCK,  # 0: always the one-shot prefill
) -> Callable:
    """Build a jittable generate(params, input_ids, attn_mask, rng) ->
    dict(samples, response_tokens, response_mask). Shapes are static per
    (batch, prompt_len); jit-cache the returned fn per shape bucket.

    Where `block_plan` gives one (a left-padded prompt of two blocks of
    `prefill_block` columns or more, layers that all keep columns), the
    program's WORK follows the chunk's longest prompt while its shapes stay:
    the blocks in front of the first live column are not prefilled and the
    decode loop does not read them (`BlockPlan`). Tokens, masks and captured
    stats are those of the one-shot program.

    Covers both architectures: causal (prefill the prompt into the KV
    cache, continue) and seq2seq (encode the prompt once, decode from
    `decoder_start_token_id` with cross-attention — reference T5 generate
    path via HF, plus ILQL seq2seq generation modeling_ilql.py:481-667).

    With `capture` on (rollout fast path, method.capture_rollout_stats)
    the output dict additionally carries the stats PPO scoring would
    otherwise recompute with a full batched forward:

    - "logprobs"  [b, max_new] f32 — policy logprob of each sampled token
      (raw-logit log-softmax, i.e. what logprobs_of_labels reads at the
      same positions);
    - "values"    [b, max_new] f32 — value head at each token's INPUT
      position (v(x_{<t}), matching `values[:, :-1]` window semantics of
      the batched scorer);
    - "h_split"   [b, plen + max_new, d] — activation entering block
      `capture_split`, so the frozen-reference branch can resume from the
      hydra split (`forward(start=split)`) without re-running shared layers.

    Single-beam causal LM only."""
    max_new = gen_cfg.max_new_tokens
    forbid = jnp.asarray(logit_mask) if logit_mask is not None else None
    suppress = None
    if gen_cfg.suppress_tokens:
        # [V] additive mask, built once here so the id list (possibly tens
        # of thousands of entries) constant-folds instead of re-tracing
        m = np.zeros((model_cfg.vocab_size,), np.float32)
        m[np.asarray(gen_cfg.suppress_tokens, np.int64)] = -np.inf
        suppress = jnp.asarray(m)
    is_seq2seq = bool(getattr(model_cfg, "is_seq2seq", False))

    if capture and (mode != "lm" or is_seq2seq or gen_cfg.num_beams > 1):
        raise NotImplementedError(
            "rollout stat capture supports single-beam causal LM "
            "generation only (no ILQL, seq2seq, or beam search)"
        )

    if spec_k > 0:
        # Self-speculative decode gates. These mirror the trainer-side
        # `_spec_decode_available` checks but refuse loudly here too, so a
        # direct make_generate_fn caller can't silently get a sampler whose
        # distribution differs from the plain one.
        if mode != "lm" or is_seq2seq or gen_cfg.num_beams > 1:
            raise NotImplementedError(
                "speculative decode supports single-beam causal LM "
                "generation only (no ILQL, seq2seq, or beam search)"
            )
        if gen_cfg.repetition_penalty != 1.0:
            raise NotImplementedError(
                "speculative decode with repetition_penalty != 1 is not "
                "supported (the seen-token mask would need per-draft "
                "rollback)"
            )
        if getattr(model_cfg, "has_slot_state", False):
            from trlx_tpu.models.transformer import slot_state_of

            raise NotImplementedError(
                f"speculative decode over slot state ({slot_state_of(model_cfg)}) is not supported: "
                "rejected drafts roll back by clearing mask bits, which does not undo a state a row"
            )
        if getattr(model_cfg, "moe_experts", 0) > 0:
            raise NotImplementedError(
                "speculative decode with MoE blocks is not supported "
                "(expert routing differs between draft and verify widths)"
            )
        if spec_split <= 0:
            raise ValueError(
                "speculative decode requires a hydra split > 0 (the frozen "
                "trunk IS the draft model)"
            )
        if spec_draft_head is None:
            raise ValueError(
                "speculative decode requires a draft head (A, B) — see "
                "spec_draft_head_from_params"
            )

    if gen_cfg.num_beams > 1:
        if mode != "lm" or logit_mask is not None or gen_cfg.suppress_tokens:
            raise NotImplementedError(
                "num_beams > 1 supports plain LM generation only (no ILQL "
                "advantage shift, transition logit masks, or suppress_tokens)"
            )
        if gen_cfg.repetition_penalty != 1.0:
            raise NotImplementedError(
                "repetition_penalty under num_beams > 1 is not supported"
            )
        if not gen_cfg.do_sample and (
            gen_cfg.temperature not in (0.0, 1.0)
            or gen_cfg.top_k
            or gen_cfg.top_p < 1.0
        ):
            # refuse rather than silently ignoring warpers: HF's
            # deterministic beam search likewise takes no warpers —
            # set do_sample=True for beam-SAMPLE (ops/beam_search.py)
            raise NotImplementedError(
                "temperature/top_k/top_p with num_beams > 1 require "
                "do_sample=True (beam sample); deterministic beam search "
                "takes no sampling knobs"
            )
        from trlx_tpu.ops.beam_search import make_beam_generate_fn

        return make_beam_generate_fn(model, model_cfg, gen_cfg)

    def step_model(params, tokens, cache, token_mask, is_prefill):
        """One model step -> (last_logits f32 [b, V], ilql adv | None,
        value | None [b] f32, h_split | None [b, t, d], cache)."""
        if mode == "ilql":
            logits, qs, target_qs, vs, cache = model.apply(
                {"params": params}, tokens, cache, token_mask, is_prefill,
                method=type(model).decode_step,
            )
            if two_qs:
                q = jnp.minimum(target_qs[0][:, -1, :], target_qs[1][:, -1, :])
            else:
                q = target_qs[0][:, -1, :]
            adv = q - vs[:, -1, :]  # [b, V]
            return logits[:, -1].astype(jnp.float32), adv, None, None, cache
        if capture:
            logits, values, cache, h_split = model.apply(
                {"params": params}, tokens, cache, token_mask, is_prefill,
                with_value=True, capture_split=capture_split,
                method=type(model).decode_step,
            )
            return (
                logits[:, -1].astype(jnp.float32),
                None,
                values[:, -1].astype(jnp.float32),
                h_split,
                cache,
            )
        logits, _, cache = model.apply(
            {"params": params}, tokens, cache, token_mask, is_prefill,
            method=type(model).decode_step,
        )
        return logits[:, -1].astype(jnp.float32), None, None, None, cache

    def shift_logits(logits, adv, prev_token):
        """Mode-specific logit rewrite before sampling."""
        if suppress is not None:
            logits = logits + suppress
        if forbid is not None:
            # forbid transitions from the previous token (reference
            # modeling_ilql.py:378-380)
            logits = jnp.where(forbid[prev_token], -jnp.inf, logits)
        if mode == "ilql":
            logits = jax.nn.log_softmax(logits, axis=-1) + gen_cfg.beta * adv
        return logits

    def decode_loop(rng, cache, last_logits, last_adv, last_value, prev_token0, params, b,
                    token_dtype, seen0=None, hs0=None):
        """Fused sampling loop. Token 0 is drawn here from the prefill
        logits, OUTSIDE the while_loop, so the carry holds the previous
        TOKEN (int32 [b]) instead of a [b, V] f32 logits bank, and each
        body iteration runs model-step -> shift/warp -> draw as one fused
        block — no per-token [b, vocab] round-trip through the carry, and
        no trailing model call whose logits are thrown away when the
        budget runs out. RNG split order and per-step logit math are
        unchanged, so sampled tokens are bit-identical to the previous
        structure.

        Under `capture` the carry additionally accumulates each sampled
        token's raw-logit policy logprob, the value head at its input
        position, and the split-point activations (`hs0` arrives with the
        prefill's prompt rows already written)."""
        if last_adv is None:
            last_adv = jnp.zeros((b, 1), dtype=jnp.float32)
        track_seen = gen_cfg.repetition_penalty != 1.0
        if track_seen and seen0 is None:
            raise ValueError(
                "repetition_penalty != 1 requires an initial seen-token mask"
            )
        if not track_seen:
            # dummy 1-wide when unused so the while_loop carry stays tiny
            seen0 = jnp.zeros((b, 1), dtype=bool)

        def sample(rng, logits, adv, prev_token, finished, seen, i):
            rng, key = jax.random.split(rng)
            scores = shift_logits(logits, adv, prev_token)
            scores = process_logits(scores, gen_cfg, i, seen if track_seen else None)
            token = select_token(scores, key, gen_cfg).astype(token_dtype)
            token = jnp.where(finished, gen_cfg.pad_token_id, token)
            valid = (~finished).astype(jnp.int32)
            finished = finished | (token == gen_cfg.eos_token_id)
            if track_seen:
                seen = seen.at[jnp.arange(b), token].set(True)
            return rng, token, valid, finished, seen

        finished0 = jnp.zeros((b,), dtype=bool)
        rng, token0, valid0, finished0, seen0 = sample(
            rng, last_logits, last_adv, prev_token0, finished0, seen0, 0
        )
        out_tokens0 = jnp.full((b, max_new), gen_cfg.pad_token_id, dtype=token_dtype)
        out_tokens0 = out_tokens0.at[:, 0].set(token0)
        out_mask0 = jnp.zeros((b, max_new), dtype=jnp.int32).at[:, 0].set(valid0)
        if capture:
            lp0 = jnp.zeros((b, max_new), jnp.float32).at[:, 0].set(
                sampled_token_logprob(last_logits, token0)
            )
            v0 = jnp.zeros((b, max_new), jnp.float32).at[:, 0].set(last_value)
            cap0 = (lp0, v0, hs0)
        else:
            cap0 = ()
        state = (1, rng, cache, token0, valid0, finished0, out_tokens0, out_mask0,
                 seen0, cap0)

        def cond(state):
            return (state[0] < max_new) & ~jnp.all(state[5])

        def body(state):
            i, rng, cache, prev_token, prev_valid, finished, out_tokens, out_mask, seen, cap = state
            logits, adv, value, h_cap, cache = step_model(
                params, prev_token[:, None], cache, prev_valid[:, None], False
            )
            rng, token, valid, finished, seen = sample(rng, logits, adv, prev_token, finished,
                                                       seen, i)
            out_tokens = jax.lax.dynamic_update_slice(out_tokens, token[:, None], (0, i))
            out_mask = jax.lax.dynamic_update_slice(out_mask, valid[:, None], (0, i))
            if capture:
                lp_buf, v_buf, hs_buf = cap
                lp_buf = jax.lax.dynamic_update_slice(
                    lp_buf, sampled_token_logprob(logits, token)[:, None], (0, i)
                )
                v_buf = jax.lax.dynamic_update_slice(v_buf, value[:, None], (0, i))
                # h_cap is the split activation at prev_token's position
                # q + i - 1 (q = prompt width baked into hs_buf)
                hs_off = hs_buf.shape[1] - max_new
                hs_buf = jax.lax.dynamic_update_slice(hs_buf, h_cap, (0, hs_off + i - 1, 0))
                cap = (lp_buf, v_buf, hs_buf)
            return (i + 1, rng, cache, token, valid, finished, out_tokens, out_mask, seen, cap)

        final = jax.lax.while_loop(cond, body, state)
        return final[6], final[7], final[9]

    def prefill_blocks(params, input_ids, attn_mask, plan):
        """The prompt block into a fresh cache, `plan.block` columns a step
        from the block that holds the chunk's first live column: one traced
        body, a trip count that is data. -> (what `step_model` gives for the
        last block, the captured rows over all the cache's columns (zeros
        where no block ran: only padding queries ever read them)). The cache
        carries the first live column (`first`), from which every decode step
        chooses the suffix it reads."""
        b, block = input_ids.shape[0], plan.block
        first = first_live_column(attn_mask)
        start = plan.first_block(first)
        if plan.pad:
            input_ids = jnp.pad(input_ids, ((0, 0), (plan.pad, 0)), constant_values=gen_cfg.pad_token_id)
            attn_mask = jnp.pad(attn_mask, ((0, 0), (plan.pad, 0)))
        cache = {**init_kv_cache(model_cfg, b, plan.columns), "index": start * block,
                 "first": first + plan.pad}
        vocab = jnp.zeros((b, model_cfg.vocab_size), jnp.float32)
        carry = (
            cache, vocab, vocab if mode == "ilql" else None,
            jnp.zeros((b,), jnp.float32) if capture else None,
            jnp.zeros((b, plan.columns, model_cfg.d_model), model_cfg.dtype) if capture else None,
        )

        def body(j, carry):
            cache, hs = carry[0], carry[4]
            tokens = jax.lax.dynamic_slice_in_dim(input_ids, j * block, block, axis=1)
            token_mask = jax.lax.dynamic_slice_in_dim(attn_mask, j * block, block, axis=1)
            logits, adv, value, h_cap, cache = step_model(params, tokens, cache, token_mask, True)
            if capture:
                hs = jax.lax.dynamic_update_slice(hs, h_cap.astype(hs.dtype), (0, j * block, 0))
            return cache, logits, adv, value, hs

        cache, logits, adv, value, hs = jax.lax.fori_loop(start, plan.blocks, body, carry)
        return (logits, adv, value, cache), hs

    def generate(params, input_ids, attn_mask, rng):
        # no-op for dense trees; reconstructs any int8 {q, scale} leaves of
        # the frozen-trunk decode view (method.quantize_frozen_trunk)
        # inside the jitted graph
        params = dequantize_tree(params)
        b, plen = input_ids.shape
        total = plen + max_new
        plan = block_plan(model_cfg, gen_cfg, plen, prefill_block)
        hs0 = None
        if plan is not None:
            (last_logits, last_adv, last_value, cache), hs0 = prefill_blocks(
                params, input_ids, attn_mask, plan)
        else:
            cache = init_kv_cache(model_cfg, b, total)
            last_logits, last_adv, last_value, h_cap, cache = step_model(
                params, input_ids, cache, attn_mask, True
            )
        seen0 = None
        if gen_cfg.repetition_penalty != 1.0:
            # HF semantics: the penalty covers prompt tokens too
            counts = jnp.zeros((b, model_cfg.vocab_size), jnp.int32)
            counts = counts.at[jnp.arange(b)[:, None], input_ids].add(
                attn_mask.astype(jnp.int32)
            )
            seen0 = counts > 0
        if capture and plan is None:
            # split activations over the full [prompt + response] width:
            # prefill fills the prompt rows, the loop writes one row per
            # model step (the final sampled token's row is never written
            # — it is only ever a masked key / padding query downstream)
            hs0 = jnp.zeros((b, total, h_cap.shape[-1]), h_cap.dtype)
            hs0 = jax.lax.dynamic_update_slice(hs0, h_cap, (0, 0, 0))

        out_tokens, out_mask, cap = decode_loop(
            rng, cache, last_logits, last_adv, last_value, input_ids[:, -1], params, b,
            input_ids.dtype, seen0, hs0,
        )
        samples = jnp.concatenate([input_ids, out_tokens], axis=1)
        samples_mask = jnp.concatenate([attn_mask.astype(jnp.int32), out_mask], axis=1)
        out = {
            "samples": samples,
            "samples_mask": samples_mask,
            "response_tokens": out_tokens,
            "response_mask": out_mask,
        }
        if capture:
            out["logprobs"], out["values"], hs = cap
            out["h_split"] = hs[:, plan.pad:] if plan is not None and plan.pad else hs
        return out

    def generate_seq2seq(params, input_ids, attn_mask, rng):
        """Encoder runs once; the decoder starts from decoder_start_token
        and decodes under the same loop. Samples are decoder-side only
        (start token included), matching HF seq2seq generate output that
        the reference stores as response tensors."""
        b, _ = input_ids.shape
        start_id = int(getattr(model_cfg, "decoder_start_token_id", gen_cfg.pad_token_id))
        enc_h = model.apply(
            {"params": params}, input_ids, attn_mask, method=type(model).encode
        )
        cache = model.apply(
            {"params": params}, enc_h, attn_mask, 1 + max_new,
            method=type(model).prepare_cache,
        )
        start = jnp.full((b, 1), start_id, dtype=input_ids.dtype)
        ones = jnp.ones((b, 1), dtype=jnp.int32)
        last_logits, last_adv, _, _, cache = step_model(params, start, cache, ones, True)
        seen0 = None
        if gen_cfg.repetition_penalty != 1.0:
            # decoder-side tokens only (HF penalizes decoder input_ids)
            seen0 = jnp.zeros((b, model_cfg.vocab_size), bool).at[
                jnp.arange(b), start_id
            ].set(True)
        out_tokens, out_mask, _ = decode_loop(
            rng, cache, last_logits, last_adv, None, start[:, 0], params, b, input_ids.dtype,
            seen0,
        )
        samples = jnp.concatenate([start, out_tokens], axis=1)
        samples_mask = jnp.concatenate([ones, out_mask], axis=1)
        return {
            "samples": samples,
            "samples_mask": samples_mask,
            "response_tokens": samples,
            "response_mask": samples_mask,
        }

    if spec_k > 0:
        k = spec_k
        a_fac = jnp.asarray(spec_draft_head[0], model_cfg.dtype)
        b_fac = jnp.asarray(spec_draft_head[1], model_cfg.dtype)
        greedy = (not gen_cfg.do_sample) or (gen_cfg.temperature == 0.0)
        if capture and capture_split != spec_split:
            raise ValueError(
                "capture_split must equal spec_split under speculative "
                "decode (both are the hydra split)"
            )

        def spec_draft(params, tokens, cache, token_mask):
            """One trunk-only step, blocks [0, split): (h_split, its `ln_f`
            reading for the draft head, cache). No head runs."""
            _, h_norm, cache, h_split = model.apply(
                {"params": params}, tokens, cache, token_mask,
                stop=spec_split, capture_split=spec_split,
                method=type(model).decode_step,
            )
            return h_split, h_norm, cache

        def spec_verify(params, h, cache, row_start, positions):
            """The suffix blocks over all k+1 drafted positions at once."""
            out = model.apply(
                {"params": params}, h, cache, None,
                start=spec_split, block_start=row_start, positions=positions,
                method=type(model).decode_step,
                **({"with_value": True} if capture else {}),
            )
            # slot 1 is the policy wrapper's values (None without capture)
            # or a bare TransformerLM's h_final: unused without capture
            return out[0], out[1] if capture else None, out[2]

        def warp(raw_logits, prev_token, step):
            return process_logits(
                shift_logits(raw_logits, None, prev_token), gen_cfg, step, None
            )

        def generate_spec(params, input_ids, attn_mask, rng):
            """Draft/verify round schedule. Each round: feed the pending
            token plus k sampled drafts through the frozen TRUNK only (k+1
            per-row t=1 cached steps, low-rank early-exit readout between
            them), then ONE batched suffix pass over all k+1 positions
            resuming from the trunk's own h_split (verify pays suffix
            blocks only), accept the longest matching draft prefix with
            exact rejection-sampling correction, and roll rejected KV back
            by clearing mask bits. Greedy output is bitwise the plain
            sampler's (argmax prefix match); sampled output follows the
            identical warped distribution (standard speculative-sampling
            correctness)."""
            params = dequantize_tree(params)
            b, plen = input_ids.shape
            total = plen + max_new
            token_dtype = input_ids.dtype
            # k spare cache slots: a round may write k positions past the
            # budget before the rollback clears them
            cache = init_kv_cache(model_cfg, b, total + k)
            last_logits, _, last_value, h_cap, cache = step_model(
                params, input_ids, cache, attn_mask, True
            )
            # token 0: bitwise the plain sampler's preamble (same prefill,
            # same RNG split, same warp chain)
            rng, key = jax.random.split(rng)
            scores0 = warp(last_logits, input_ids[:, -1], 0)
            token0 = select_token(scores0, key, gen_cfg).astype(token_dtype)
            finished0 = (token0 == gen_cfg.eos_token_id) | (max_new <= 1)
            out_tokens0 = jnp.full((b, max_new), gen_cfg.pad_token_id, dtype=token_dtype)
            out_tokens0 = out_tokens0.at[:, 0].set(token0)
            out_mask0 = jnp.zeros((b, max_new), jnp.int32).at[:, 0].set(1)
            if capture:
                lp0 = jnp.zeros((b, max_new), jnp.float32).at[:, 0].set(
                    sampled_token_logprob(last_logits, token0)
                )
                v0 = jnp.zeros((b, max_new), jnp.float32).at[:, 0].set(last_value)
                hs0 = jnp.zeros((b, total, h_cap.shape[-1]), h_cap.dtype)
                hs0 = jax.lax.dynamic_update_slice(hs0, h_cap, (0, 0, 0))
                cap0 = (lp0, v0, hs0)
            else:
                cap0 = ()
            # scalar-index prefill cache -> per-row offsets (rows diverge
            # once they accept different draft counts)
            row_cache = {
                "row_index": jnp.full((b,), cache["index"], jnp.int32),
                "mask": cache["mask"],
                "pos": cache["pos"],
                "layers": cache["layers"],
            }
            state = (
                jnp.asarray(0, jnp.int32), rng, row_cache, token0, finished0,
                jnp.ones((b,), jnp.int32),  # out_i: token 0 already written
                out_tokens0, out_mask0,
                jnp.zeros((b,), jnp.int32),  # rounds (per active row)
                jnp.zeros((b,), jnp.int32),  # accepted drafts
                cap0,
            )
            jidx = jnp.arange(k + 1)[None, :]

            def cond(state):
                return (state[0] <= max_new) & jnp.any(~state[4])

            def body(state):
                (i, rng, cache, pending, finished, out_i, out_tokens,
                 out_mask, rounds, acc_tot, cap) = state
                active = ~finished
                act_i = active.astype(jnp.int32)
                row_start = cache["row_index"]
                pos_start = cache["pos"]
                f = pending
                h_rows, q_scores, draft_toks, toks_fed = [], [], [], [pending]
                for j in range(k + 1):
                    h_j, hn_j, cache = spec_draft(
                        params, f[:, None], cache, act_i[:, None]
                    )
                    h_rows.append(h_j)
                    if j < k:
                        rng, key = jax.random.split(rng)
                        dl = ((hn_j[:, 0] @ a_fac) @ b_fac).astype(jnp.float32)
                        sq = warp(dl, f, out_i + j)
                        f = select_token(sq, key, gen_cfg).astype(token_dtype)
                        q_scores.append(sq)
                        draft_toks.append(f)
                        toks_fed.append(f)
                h_block = jnp.concatenate(h_rows, axis=1)  # [b, k+1, d]
                positions = pos_start[:, None] + jnp.arange(k + 1)[None, :]
                logits_v, values_v, cache = spec_verify(
                    params, h_block, cache, row_start, positions
                )
                logits_v = logits_v.astype(jnp.float32)
                p_scores = [
                    warp(logits_v[:, j], toks_fed[j], out_i + j)
                    for j in range(k + 1)
                ]
                # longest accepted draft prefix
                if greedy:
                    acc = [
                        jnp.argmax(p_scores[j], -1).astype(token_dtype)
                        == draft_toks[j]
                        for j in range(k)
                    ]
                else:
                    acc = []
                    for j in range(k):
                        rng, key = jax.random.split(rng)
                        u = jax.random.uniform(key, (b,))
                        tok = draft_toks[j].astype(jnp.int32)[:, None]
                        lr = (
                            jnp.take_along_axis(
                                jax.nn.log_softmax(p_scores[j], -1), tok, 1
                            )
                            - jnp.take_along_axis(
                                jax.nn.log_softmax(q_scores[j], -1), tok, 1
                            )
                        )[:, 0]
                        acc.append(u < jnp.exp(jnp.minimum(lr, 0.0)))
                run = jnp.ones((b,), bool)
                m = jnp.zeros((b,), jnp.int32)
                for j in range(k):
                    run = run & acc[j]
                    m = m + run.astype(jnp.int32)
                # correction candidates per possible acceptance count:
                # greedy -> the full-model argmax; sampled -> residual
                # normalize(clip(p - q, 0)) for a rejection at j, the plain
                # warped draw for the all-accepted bonus position
                corr = []
                for j in range(k + 1):
                    if greedy:
                        corr.append(jnp.argmax(p_scores[j], -1).astype(token_dtype))
                    elif j < k:
                        rng, key = jax.random.split(rng)
                        p_w = jax.nn.softmax(p_scores[j], -1)
                        q_w = jax.nn.softmax(q_scores[j], -1)
                        res = jnp.clip(p_w - q_w, 0.0, None)
                        tot = res.sum(-1, keepdims=True)
                        res = jnp.where(tot > 0, res / tot, p_w)
                        corr.append(
                            jax.random.categorical(
                                key,
                                jnp.where(res > 0, jnp.log(res), -jnp.inf),
                                axis=-1,
                            ).astype(token_dtype)
                        )
                    else:
                        rng, key = jax.random.split(rng)
                        corr.append(
                            select_token(p_scores[j], key, gen_cfg).astype(token_dtype)
                        )
                corr = jnp.stack(corr, axis=1)  # [b, k+1]
                corr_at_m = jnp.take_along_axis(corr, m[:, None], axis=1)[:, 0]
                draft_mat = jnp.stack(draft_toks + [corr[:, k]], axis=1)
                emit_toks = jnp.where(
                    jidx < m[:, None],
                    draft_mat,
                    jnp.where(
                        jidx == m[:, None], corr_at_m[:, None], gen_cfg.pad_token_id
                    ),
                ).astype(token_dtype)
                # eos / budget truncation of this round's emissions
                alive = active
                valids = []
                for j in range(k + 1):
                    v_j = alive & (j <= m) & (out_i + j < max_new)
                    valids.append(v_j)
                    alive = v_j & (emit_toks[:, j] != gen_cfg.eos_token_id)
                valid_mat = jnp.stack(valids, axis=1)
                emit_toks = jnp.where(
                    valid_mat, emit_toks, gen_cfg.pad_token_id
                ).astype(token_dtype)
                e = valid_mat.astype(jnp.int32).sum(1)
                hit_eos = jnp.any(
                    valid_mat & (emit_toks == gen_cfg.eos_token_id), axis=1
                )
                new_out_i = out_i + e
                new_finished = finished | (
                    active & (hit_eos | (new_out_i >= max_new))
                )
                new_pending = jnp.where(active & ~new_finished, corr_at_m, pending)
                # roll back rejected KV: keep mask bits for the e fed-and-
                # kept tokens f_0..f_{e-1}, clear the rest — next round's
                # writes land exactly on the first cleared offset
                rows_b = jnp.arange(b)[:, None]
                offs = row_start[:, None] + jidx
                new_mask_c = cache["mask"].at[rows_b, offs].set(
                    (jidx < e[:, None]).astype(cache["mask"].dtype)
                )
                cache = dict(
                    cache, mask=new_mask_c,
                    row_index=row_start + e, pos=pos_start + e,
                )
                out_idx = jnp.where(valid_mat, out_i[:, None] + jidx, max_new)
                out_tokens = out_tokens.at[rows_b, out_idx].set(emit_toks)
                out_mask = out_mask.at[rows_b, out_idx].set(
                    valid_mat.astype(jnp.int32)
                )
                if capture:
                    lp_buf, v_buf, hs_buf = cap
                    lsm = jax.nn.log_softmax(logits_v, axis=-1)
                    lp_emit = jnp.take_along_axis(
                        lsm, emit_toks.astype(jnp.int32)[..., None], axis=-1
                    )[..., 0]
                    lp_buf = lp_buf.at[rows_b, out_idx].set(lp_emit)
                    v_buf = v_buf.at[rows_b, out_idx].set(
                        values_v.astype(jnp.float32)
                    )
                    # h rows for the fed tokens f_0..f_{e-1} land at their
                    # sequence positions plen + out_i - 1 + j; the final
                    # emitted token's row is never written (same invariant
                    # as the plain capture loop)
                    hs_off = hs_buf.shape[1] - max_new
                    h_idx = jnp.where(
                        jidx < e[:, None],
                        hs_off + out_i[:, None] - 1 + jidx,
                        hs_buf.shape[1],
                    )
                    hs_buf = hs_buf.at[rows_b, h_idx].set(
                        h_block.astype(hs_buf.dtype)
                    )
                    cap = (lp_buf, v_buf, hs_buf)
                return (i + 1, rng, cache, new_pending, new_finished, new_out_i,
                        out_tokens, out_mask, rounds + act_i,
                        acc_tot + m * act_i, cap)

            final = jax.lax.while_loop(cond, body, state)
            out_tokens, out_mask = final[6], final[7]
            samples = jnp.concatenate([input_ids, out_tokens], axis=1)
            samples_mask = jnp.concatenate(
                [attn_mask.astype(jnp.int32), out_mask], axis=1
            )
            out = {
                "samples": samples,
                "samples_mask": samples_mask,
                "response_tokens": out_tokens,
                "response_mask": out_mask,
                "spec_rounds": final[8],
                "spec_accepted": final[9],
            }
            if capture:
                out["logprobs"], out["values"], out["h_split"] = final[10]
            return out

        return generate_spec

    return generate_seq2seq if is_seq2seq else generate


def spec_draft_head_from_params(params, model_cfg: TransformerConfig, rank: int):
    """Low-rank draft readout (A [d, r], B [r, V]) from the unembedding:
    truncated SVD W_U ≈ A @ B, computed host-side ONCE. Under a hydra
    split with tied embeddings the unembedding never trains, so the
    factors never go stale; with an untied (trainable) lm_head they decay
    in quality as training moves the head — a PERF effect only, since the
    rejection-sampling correction keeps the sampled distribution exact
    regardless of draft quality. Draft logits = ln_f(h_split) @ A @ B,
    an early-exit readout that streams r*(d+V) draft-head bytes per step
    instead of the full d*V unembedding."""
    def dense(leaf):
        # tolerate the int8 decode view (ops/quant.py node layout)
        if isinstance(leaf, dict) and set(leaf.keys()) == {"q", "scale"}:
            return np.asarray(leaf["q"], np.float32) * np.asarray(leaf["scale"], np.float32)
        return np.asarray(leaf, np.float32)

    lm = params["lm"] if "lm" in params else params
    if model_cfg.tie_embeddings:
        w = dense(lm["embed_tokens"]["embedding"]).T  # [d, V]
    else:
        w = dense(lm["lm_head"]["kernel"])  # [d, V]
    r = int(min(rank, min(w.shape)))
    u, s, vt = np.linalg.svd(w, full_matrices=False)
    return (u[:, :r] * s[:r][None, :]).astype(np.float32), vt[:r].astype(np.float32)


def generate(
    model,
    model_cfg: TransformerConfig,
    params,
    input_ids,
    attn_mask,
    rng,
    gen_cfg: GenerationConfig,
    mode: str = "lm",
    logit_mask=None,
    two_qs: bool = True,
    capture: bool = False,
    capture_split: int = 0,
    spec_k: int = 0,
    spec_split: int = 0,
    spec_draft_head: Optional[Tuple] = None,
    prefill_block: int = PREFILL_BLOCK,
):
    """One-shot convenience wrapper (not cached across shapes)."""
    fn = make_generate_fn(model, model_cfg, gen_cfg, mode, logit_mask, two_qs,
                          capture=capture, capture_split=capture_split,
                          spec_k=spec_k, spec_split=spec_split,
                          spec_draft_head=spec_draft_head, prefill_block=prefill_block)
    return fn(params, jnp.asarray(input_ids), jnp.asarray(attn_mask), rng)
