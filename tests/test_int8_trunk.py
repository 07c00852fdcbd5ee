"""The int8 view of the frozen trunk that generation may decode from
(`method.quantize_frozen_trunk`): round trip, closeness to dense decode,
which leaves it quantizes, and the one-time gate-off warnings of the
pipelined / sequence-parallel trainers."""

import logging
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.models import build_model
from trlx_tpu.ops.quant import (
    dequantize_tree,
    quantize_array,
    quantize_decode_params,
    quantize_frozen_flat,
)
from trlx_tpu.ops.sampling import GenerationConfig, make_generate_fn


EOS, PAD = 63, 62


def make_lm(**kw):
    mc = ModelConfig(model_path="random:gpt2-tiny", model_extra_configs={"dtype": "float32"})
    return build_model(mc, vocab_size=64, **kw)


def gen_cfg(**kw):
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("eos_token_id", EOS)
    kw.setdefault("pad_token_id", PAD)
    return GenerationConfig(**kw)


def prompts():
    ids = jnp.asarray([[PAD, PAD, 5, 6, 7], [PAD, 1, 2, 3, 4]], dtype=jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1, 1], [0, 1, 1, 1, 1]], dtype=jnp.int32)
    return ids, mask


# ----------------------------------------------------------------------
# Int8 frozen-trunk decode
# ----------------------------------------------------------------------


def test_int8_roundtrip_tolerance():
    x = np.random.default_rng(0).normal(size=(16, 32)).astype(np.float32)
    q = quantize_array(jnp.asarray(x))
    back = np.asarray(dequantize_tree(q))
    # per-output-channel symmetric int8 (scale over all axes but the
    # last): error bounded by half a quantization step
    step = np.abs(x).max(axis=0, keepdims=True) / 127.0
    assert np.all(np.abs(back - x) <= step * 0.5 + 1e-7)


def test_int8_close_to_dense_greedy():
    """Int8 weight-only decode stays token-level close to dense decode on
    the tiny model (the quantization error is far below the typical logit
    margin)."""
    model, cfg, params = make_lm()
    ids, mask = prompts()
    qparams = quantize_decode_params(params, split=1)
    plain = jax.jit(make_generate_fn(model, cfg, gen_cfg(do_sample=False)))
    od = plain(params, ids, mask, jax.random.PRNGKey(0))
    oq = plain(qparams, ids, mask, jax.random.PRNGKey(0))
    agree = (np.asarray(od["response_tokens"]) == np.asarray(oq["response_tokens"])).mean()
    assert agree >= 0.75


def test_quantize_frozen_flat_targets_trunk_only():
    """The flat-dict variant quantizes only frozen-trunk matrices: block
    indices < split plus embeddings; biases / norms / scalars stay dense."""
    _, _, params = make_lm()
    from flax.traverse_util import flatten_dict
    flat = flatten_dict(params)
    frozen = {k: v for k, v in flat.items()
              if any(str(p) == "block_0" or str(p) in ("embed_tokens", "embed_pos")
                     for p in k)}
    q = quantize_frozen_flat(frozen, split=1)
    n_quant = sum(1 for v in q.values() if isinstance(v, dict) and "q" in v)
    assert n_quant > 0
    for k, v in q.items():
        if isinstance(v, dict) and "q" in v:
            assert v["q"].dtype == jnp.int8
        else:
            # anything left dense must be < 2-D or a norm/bias leaf
            assert v.ndim < 2 or not jnp.issubdtype(v.dtype, jnp.floating) or (
                any(str(p) in ("ln_1", "ln_2", "ln_f", "bias", "b") for p in k))


@pytest.mark.parametrize("cls_name", ["pipelined", "sequence_parallel"])
def test_parallel_trainers_warn_once(cls_name):
    """Pipelined / sequence-parallel trainers gate the new flags off with
    exactly one warning each, not one per rollout."""
    if cls_name == "pipelined":
        from trlx_tpu.trainer.pipelined_ppo_trainer import PipelinedPPOTrainer as C
    else:
        from trlx_tpu.trainer.sequence_parallel_ppo_trainer import (
            SequenceParallelPPOTrainer as C,
        )
    # `params` is a merging property on the real trainer; stub it out so
    # the dummy instance needs no partitioned state
    class Dummy(C):
        params = property(lambda self: self._test_params)

    t = object.__new__(Dummy)
    t.config = SimpleNamespace(
        method=SimpleNamespace(capture_rollout_stats=True, quantize_frozen_trunk=True))
    t._test_params = {"lm": {}}
    # the library root logger doesn't propagate to the pytest root handler,
    # so capture with a handler on the library logger itself
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    lib = logging.getLogger("trlx_tpu")
    lib.addHandler(handler)
    try:
        assert t._fast_rollout_available() is False
        assert t._fast_rollout_available() is False
        assert t._decode_params() is t._test_params
        assert t._decode_params() is t._test_params
    finally:
        lib.removeHandler(handler)
    capture_warns = [r for r in records if "capture_rollout_stats" in r.getMessage()]
    quant_warns = [r for r in records if "quantize_frozen_trunk" in r.getMessage()]
    assert len(capture_warns) == 1
    assert len(quant_warns) == 1
