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
from typing import Any, Callable, Dict, Optional

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
               block: int = PREFILL_BLOCK) -> Optional[BlockPlan]:
    """The plan `make_generate_fn`'s program runs a prompt block of `plen`
    columns by, or None where it keeps the one-shot prefill: the sampler is
    the token-at-a-time causal loop (one beam), and
    the model's layers and the width allow it (`prefill_by_blocks`)."""
    if (block <= 0 or gen_cfg.num_beams > 1 or getattr(model_cfg, "is_seq2seq", False)
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

    return generate_seq2seq if is_seq2seq else generate


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
    prefill_block: int = PREFILL_BLOCK,
):
    """One-shot convenience wrapper (not cached across shapes)."""
    fn = make_generate_fn(model, model_cfg, gen_cfg, mode, logit_mask, two_qs,
                          capture=capture, capture_split=capture_split, prefill_block=prefill_block)
    return fn(params, jnp.asarray(input_ids), jnp.asarray(attn_mask), rng)
