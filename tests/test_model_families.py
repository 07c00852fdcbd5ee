"""Model-family parity: our single parameterized TransformerLM vs the HF
torch implementations the reference wraps per-architecture
(trlx/models/modeling_ppo.py:502-1222, hf_get_branch_class :1598-1637).

For each family a tiny randomly-initialized HF model is saved to disk,
converted through trlx_tpu.models.hf_interop, and checked for exact logits
parity (f32) — this covers both the converter layouts (fused qkv, rotary
conventions, ALiBi, position offsets) and the architecture flags
(parallel residual, partial rotary, shared LN, MQA).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

torch = pytest.importorskip("torch")

from trlx_tpu.models import CausalLMWithValueHead  # noqa: E402
from trlx_tpu.models import hf_interop  # noqa: E402

VOCAB, SEQ = 128, 16


def _tiny_hf_model(family):
    import transformers as tf

    common = dict(vocab_size=VOCAB)
    if family == "gpt2":
        cfg = tf.GPT2Config(n_positions=64, n_embd=32, n_layer=2, n_head=4, **common)
        cls = tf.GPT2LMHeadModel
    elif family == "llama":
        cfg = tf.LlamaConfig(
            hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, **common,
        )
        cls = tf.LlamaForCausalLM
    elif family == "gpt_neox":
        cfg = tf.GPTNeoXConfig(
            hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, rotary_pct=0.25, max_position_embeddings=64,
            use_parallel_residual=True, **common,
        )
        cls = tf.GPTNeoXForCausalLM
    elif family == "gptj":
        cfg = tf.GPTJConfig(
            n_positions=64, n_embd=32, n_layer=2, n_head=4, rotary_dim=4, **common
        )
        cls = tf.GPTJForCausalLM
    elif family == "opt":
        cfg = tf.OPTConfig(
            hidden_size=32, ffn_dim=64, num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, do_layer_norm_before=True,
            word_embed_proj_dim=32, **common,
        )
        cls = tf.OPTForCausalLM
    elif family == "bloom":
        cfg = tf.BloomConfig(hidden_size=32, n_layer=2, n_head=4, **common)
        cls = tf.BloomForCausalLM
    elif family == "gpt_bigcode":
        cfg = tf.GPTBigCodeConfig(
            n_positions=64, n_embd=32, n_layer=2, n_head=4, multi_query=True, **common
        )
        cls = tf.GPTBigCodeForCausalLM
    else:
        raise ValueError(family)
    torch.manual_seed(0)
    model = cls(cfg)
    model.eval()
    return model


FAMILIES = ["gpt2", "llama", "gpt_neox", "gptj", "opt", "bloom", "gpt_bigcode"]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def _convert(tmp_path, family):
    hf_model = _tiny_hf_model(family)
    path = str(tmp_path / family)
    hf_model.save_pretrained(path, safe_serialization=True)
    cfg = hf_interop.config_from_hf(path, dtype=jnp.float32)
    model = CausalLMWithValueHead(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    template = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    params = hf_interop.load_params_from_hf(path, cfg, template)
    return hf_model, cfg, model, params, path


@pytest.mark.parametrize("family", FAMILIES)
def test_logits_parity(tmp_path, family, rng):
    hf_model, cfg, model, params, _ = _convert(tmp_path, family)

    tokens = rng.integers(0, VOCAB, size=(2, SEQ))
    # row 0: full; row 1: left-padded by 5
    mask = np.ones((2, SEQ), dtype=np.int64)
    mask[1, :5] = 0

    kwargs = {}
    if family in ("gpt2", "gpt_bigcode"):
        # HF's plain forward uses arange positions regardless of padding;
        # the reference trainer passes mask-aware position_ids explicitly
        # (accelerate_ppo_trainer.py:176-180), which is what our model
        # computes internally — supply the same to the oracle.
        pos = np.clip(np.cumsum(mask, axis=-1) - 1, 0, None)
        kwargs["position_ids"] = torch.tensor(pos)
    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.tensor(tokens), attention_mask=torch.tensor(mask), **kwargs
        ).logits.numpy()

    logits, _, _ = jax.jit(model.apply)(
        {"params": params}, jnp.asarray(tokens, jnp.int32), jnp.asarray(mask, jnp.int32)
    )
    ours = np.asarray(logits, np.float32)
    valid = mask.astype(bool)
    np.testing.assert_allclose(ours[valid], ref[valid], atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("family", FAMILIES)
def test_export_round_trip(tmp_path, family, rng):
    """params -> HF state dict -> params is the identity (and the exported
    dict matches the original HF checkpoint key set)."""
    hf_model, cfg, model, params, path = _convert(tmp_path, family)
    sd = hf_interop.params_to_hf_state_dict(params, cfg)

    orig = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    # HF save drops tied/duplicate leaves; every original key we exported
    # must match numerically.
    checked = 0
    for k, v in orig.items():
        if k in sd:
            np.testing.assert_allclose(sd[k], v, atol=1e-6, err_msg=k)
            checked += 1
    assert checked >= len(sd) * 0.9  # near-total coverage of exported keys

    assert cfg.hf_family == family
    assert hf_interop.infer_family(cfg) == family


def test_mistral_sliding_window_parity(tmp_path, rng):
    """Mistral maps to the llama family plus a sliding window; with
    window < seq the band must match HF's banded attention exactly."""
    import transformers as tf

    torch.manual_seed(0)
    hf_model = tf.MistralForCausalLM(tf.MistralConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=6,
        attn_implementation="eager",
    ))
    hf_model.eval()
    path = str(tmp_path / "mistral")
    hf_model.save_pretrained(path, safe_serialization=True)

    cfg = hf_interop.config_from_hf(path, dtype=jnp.float32)
    assert cfg.sliding_window == 6
    model = CausalLMWithValueHead(cfg)
    tokens8 = jnp.zeros((1, 8), jnp.int32)
    template = jax.jit(model.init)(jax.random.PRNGKey(0), tokens8, jnp.ones_like(tokens8))["params"]
    params = hf_interop.load_params_from_hf(path, cfg, template)

    tokens = rng.integers(0, VOCAB, size=(2, SEQ))  # SEQ=16 > window=6
    mask = np.ones((2, SEQ), dtype=np.int64)
    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.tensor(tokens), attention_mask=torch.tensor(mask)
        ).logits.numpy()
    ours, _, _ = jax.jit(model.apply)(
        {"params": params}, jnp.asarray(tokens, jnp.int32), jnp.asarray(mask, jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-3, rtol=2e-3)

    # windowed != unwindowed beyond the band (the test actually bites)
    cfg_nw = hf_interop.config_from_hf(path, dtype=jnp.float32, sliding_window=None)
    logits_nw, _, _ = jax.jit(CausalLMWithValueHead(cfg_nw).apply)(
        {"params": params}, jnp.asarray(tokens, jnp.int32), jnp.asarray(mask, jnp.int32)
    )
    assert not np.allclose(np.asarray(ours)[:, -1], np.asarray(logits_nw)[:, -1], atol=1e-4)


def test_sliding_window_decode_matches_forward():
    """Cached decode applies the same band as the training forward."""
    from trlx_tpu.models import config_from_preset, init_kv_cache
    from trlx_tpu.models.transformer import TransformerLM

    cfg = config_from_preset("llama-tiny", vocab_size=64, dtype=jnp.float32,
                             sliding_window=4)
    model = TransformerLM(cfg)
    rng_np = np.random.default_rng(0)
    tokens = jnp.asarray(rng_np.integers(0, 64, (2, 12)), jnp.int32)
    mask = jnp.ones_like(tokens)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, mask)["params"]
    full_logits, _, _ = jax.jit(model.apply)({"params": params}, tokens, mask)

    step = jax.jit(lambda tok, cache, m, prefill: model.apply(
        {"params": params}, tok, cache, m, prefill, method=TransformerLM.decode_step),
        static_argnums=3)
    cache = init_kv_cache(cfg, 2, 12, dtype=jnp.float32)
    logits, _, cache = step(tokens[:, :6], cache, mask[:, :6], True)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, :6]), atol=1e-4
    )
    for i in range(6, 12):
        logits, _, cache = step(tokens[:, i:i + 1], cache, mask[:, i:i + 1], False)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full_logits[:, i]), atol=1e-4,
            err_msg=f"step {i}",
        )


# ---------------------------------------------------------------------------
# T5 / seq2seq interop (reference loads t5 via PreTrainedModelWrapper.
# from_pretrained, modeling_base.py:123-326, and wraps it with the branch
# classes in modeling_ppo.py:1242-1592)
# ---------------------------------------------------------------------------

T5_VARIANTS = {
    # t5 v1.0: relu MLP, tied embeddings, logits scaled by d_model**-0.5
    "t5_v10": dict(feed_forward_proj="relu", tie_word_embeddings=True,
                   num_decoder_layers=2),
    # v1.1/flan-t5: gated-gelu, untied lm_head, no logit scaling, and an
    # encoder/decoder depth mismatch + d_kv != d_model/n_heads
    "flan_t5": dict(feed_forward_proj="gated-gelu", tie_word_embeddings=False,
                    num_decoder_layers=3),
    # plain (non-gated) gelu runs HF's exact-erf GELU, not gelu_new —
    # pins the activation mapping divergence
    "t5_gelu": dict(feed_forward_proj="gelu", tie_word_embeddings=True,
                    num_decoder_layers=2),
}


def _tiny_t5(variant):
    import transformers as tf

    cfg = tf.T5Config(
        vocab_size=VOCAB, d_model=32, d_kv=16, d_ff=64, num_layers=2,
        num_heads=4, decoder_start_token_id=0, **T5_VARIANTS[variant],
    )
    torch.manual_seed(0)
    model = tf.T5ForConditionalGeneration(cfg)
    model.eval()
    return model


def _convert_t5(tmp_path, variant):
    from trlx_tpu.models import Seq2SeqLMWithValueHead

    hf_model = _tiny_t5(variant)
    path = str(tmp_path / variant)
    hf_model.save_pretrained(path, safe_serialization=True)
    cfg = hf_interop.config_from_hf(path, dtype=jnp.float32)
    assert cfg.is_seq2seq and cfg.hf_family == "t5"
    model = Seq2SeqLMWithValueHead(cfg)
    tok = jnp.zeros((1, 8), jnp.int32)
    template = jax.jit(model.init)(
        jax.random.PRNGKey(0), tok, jnp.ones_like(tok), tok, jnp.ones_like(tok)
    )["params"]
    params = hf_interop.load_params_from_hf(path, cfg, template)
    return hf_model, cfg, model, params, path


def _t5_logits(model, params, enc, enc_mask, dec, dec_mask):
    logits, _, _, _ = jax.jit(model.apply, static_argnums=5)(
        {"params": params},
        jnp.asarray(enc, jnp.int32), jnp.asarray(enc_mask, jnp.int32),
        jnp.asarray(dec, jnp.int32), jnp.asarray(dec_mask, jnp.int32), 0,
    )
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("variant", sorted(T5_VARIANTS))
def test_t5_logits_parity(tmp_path, variant, rng):
    """Encoder+decoder logits parity vs the torch oracle, with encoder
    right-padding (T5 tokenizers pad right) exercising the padding bias."""
    hf_model, cfg, model, params, _ = _convert_t5(tmp_path, variant)

    enc = rng.integers(3, VOCAB, size=(2, 12))
    enc_mask = np.ones((2, 12), dtype=np.int64)
    enc_mask[1, 9:] = 0
    dec = rng.integers(3, VOCAB, size=(2, 7))
    dec[:, 0] = cfg.decoder_start_token_id
    dec_mask = np.ones((2, 7), dtype=np.int64)

    with torch.no_grad():
        ref = hf_model(
            input_ids=torch.tensor(enc), attention_mask=torch.tensor(enc_mask),
            decoder_input_ids=torch.tensor(dec),
            decoder_attention_mask=torch.tensor(dec_mask),
        ).logits.numpy()
    ours = _t5_logits(model, params, enc, enc_mask, dec, dec_mask)
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("variant", sorted(T5_VARIANTS))
def test_t5_export_round_trip(tmp_path, variant, rng):
    """params -> HF state dict matches the original checkpoint tensors, and
    the exported dir (config_to_hf + torch.save) loads back through plain
    transformers AutoModelForSeq2SeqLM with identical logits — the
    save_pretrained contract (reference modeling_base.py:327-374)."""
    import json as _json

    hf_model, cfg, model, params, _ = _convert_t5(tmp_path, variant)
    sd = hf_interop.params_to_hf_state_dict(params, cfg)

    orig = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    checked = 0
    for k, v in orig.items():
        if k in sd:
            np.testing.assert_allclose(sd[k], v, atol=1e-6, err_msg=k)
            checked += 1
    assert checked >= len(orig)  # every original tensor is covered

    out = tmp_path / f"{variant}_export"
    out.mkdir()
    torch.save(
        {k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in sd.items()},
        str(out / "pytorch_model.bin"),
    )
    with open(out / "config.json", "w") as f:
        _json.dump(hf_interop.config_to_hf(cfg), f)

    from transformers import AutoModelForSeq2SeqLM

    reloaded = AutoModelForSeq2SeqLM.from_pretrained(str(out))
    reloaded.eval()
    enc = rng.integers(3, VOCAB, size=(1, 10))
    dec = rng.integers(3, VOCAB, size=(1, 5))
    dec[:, 0] = cfg.decoder_start_token_id
    ones_e, ones_d = np.ones_like(enc), np.ones_like(dec)
    with torch.no_grad():
        a = hf_model(
            input_ids=torch.tensor(enc), attention_mask=torch.tensor(ones_e),
            decoder_input_ids=torch.tensor(dec),
            decoder_attention_mask=torch.tensor(ones_d),
        ).logits.numpy()
        b = reloaded(
            input_ids=torch.tensor(enc), attention_mask=torch.tensor(ones_e),
            decoder_input_ids=torch.tensor(dec),
            decoder_attention_mask=torch.tensor(ones_d),
        ).logits.numpy()
    np.testing.assert_allclose(b, a, atol=1e-5, rtol=1e-5)


def test_t5_hydra_split_parity(tmp_path, rng):
    """forward_seq2seq_policy_and_ref with split>0 (frozen top decoder
    branch resumed from the trunk's hidden state) must equal the full
    frozen forward on real converted weights — the T5Branch contract
    (reference modeling_ppo.py:1353-1592)."""
    from trlx_tpu.models import (
        forward_seq2seq_policy_and_ref,
        seq2seq_ref_param_subtree,
    )

    hf_model, cfg, model, params, _ = _convert_t5(tmp_path, "flan_t5")
    split = cfg.n_decoder_layers - 1
    ref_sub = seq2seq_ref_param_subtree(params, cfg, split)
    ref_full = seq2seq_ref_param_subtree(params, cfg, 0)

    enc = rng.integers(3, VOCAB, size=(2, 10))
    dec = rng.integers(3, VOCAB, size=(2, 6))
    dec[:, 0] = cfg.decoder_start_token_id
    enc_mask, dec_mask = np.ones_like(enc), np.ones_like(dec)
    args = (jnp.asarray(enc, jnp.int32), jnp.asarray(enc_mask, jnp.int32),
            jnp.asarray(dec, jnp.int32), jnp.asarray(dec_mask, jnp.int32))

    _, _, ref_logits_split = forward_seq2seq_policy_and_ref(
        model, params, ref_sub, *args, split
    )
    _, _, ref_logits_full = forward_seq2seq_policy_and_ref(
        model, params, ref_full, *args, 0
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits_split), np.asarray(ref_logits_full), atol=1e-4
    )
    # and the trunk logits match the torch oracle
    with torch.no_grad():
        oracle = hf_model(
            input_ids=torch.tensor(enc), attention_mask=torch.tensor(enc_mask),
            decoder_input_ids=torch.tensor(dec),
            decoder_attention_mask=torch.tensor(dec_mask),
        ).logits.numpy()
    np.testing.assert_allclose(
        np.asarray(ref_logits_full, np.float32), oracle, atol=2e-4, rtol=2e-4
    )


def test_preset_coverage():
    """Every family has at least one preset and they build."""
    from trlx_tpu.models.transformer import PRESETS, config_from_preset

    for name in ("neox-tiny", "gptj-tiny", "opt-tiny", "bloom-tiny", "bigcode-tiny"):
        assert name in PRESETS
        cfg = config_from_preset(name, vocab_size=64, dtype=jnp.float32)
        model = CausalLMWithValueHead(cfg)
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
        logits, values, _ = jax.jit(model.apply)({"params": params}, tokens, jnp.ones_like(tokens))
        assert logits.shape == (1, 8, 64)
        assert np.all(np.isfinite(np.asarray(logits)))


def test_fused_attention_eligibility():
    from trlx_tpu.models.transformer import TransformerConfig, fused_attention_ok

    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64)
    assert not fused_attention_ok(TransformerConfig(**base, attn_impl="xla"), 128)
    assert fused_attention_ok(TransformerConfig(**base, attn_impl="flash"), 128)
    # window inactive when seq fits inside it -> fused stays on
    cfg = TransformerConfig(**base, attn_impl="flash", sliding_window=4096)
    assert fused_attention_ok(cfg, 2048)
    assert not fused_attention_ok(cfg, 8192)
    assert not fused_attention_ok(cfg, None)
    # ring + window can never be proven inactive locally -> loud error
    with pytest.raises(NotImplementedError):
        fused_attention_ok(
            TransformerConfig(**base, attn_impl="ring", sliding_window=4096), 128
        )
    assert not fused_attention_ok(TransformerConfig(**base, attn_impl="flash", alibi=True), 128)
