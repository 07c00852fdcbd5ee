"""The plain references against the program's `TransformerLM` at the tiny
presets, on the CPU, on weights from the seed; and the lower-precision
control at a size a test run can hold: the same comparison has to come out
over the limit when the reference computes in bfloat16's next step down.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from benchlib import weights  # noqa: E402
from benchlib.files import load_module  # noqa: E402


def _program_logprobs(config_name, seed, tokens, mask, dtype="float32"):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as f:
        bench = json.load(f)["bench"]
    extra = dict(bench["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(bench["rehearse"]["model_path"].split(":")[1],
                             extra.pop("vocab_size"), **extra, dtype=jnp.dtype(dtype))
    model = CausalLMPolicy(cfg)
    t = jnp.zeros((1, 8), jnp.int32)
    params = weights.make_params(weights.param_shapes(model, t, jnp.ones_like(t)), seed,
                                 jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))[0]
    ref = load_module(f"reference/{bench['reference']}.py")
    lp = load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens))
    want = ref.logprobs(params["lm"], tokens, mask, bench["rehearse_sizes"])
    return np.asarray(lp), np.asarray(want), ref


def _inputs(seed, b=3, t=48, vocab=512):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, size=(b, t)).astype(np.int32)
    mask = np.ones((b, t), np.int32)
    mask[1, :7] = 0  # left padding, as the prompt pipeline pads
    mask[2, :20] = 0
    return tokens, mask


@pytest.mark.parametrize("config_name", ["pythia-1.4b", "gpt2-xl"])
@pytest.mark.parametrize("seed", [0, 3_000_000_019])
def test_reference_matches_program_in_float32(config_name, seed):
    tokens, mask = _inputs(seed)
    got, want, _ = _program_logprobs(config_name, seed, tokens, mask)
    valid = mask[:, :-1].astype(bool)
    # float32 against float32: only the order of sums differs
    assert np.abs(got - want)[valid].max() < 2e-4


@pytest.mark.parametrize("config_name", ["pythia-1.4b", "gpt2-xl"])
def test_lower_precision_is_told_apart(config_name):
    """float32 program against the reference: tiny error; bfloat16 program:
    an error far above it. The same ordering, one step down (bf16 against
    int8), is what the chip's control shows at the cells' own sizes."""
    tokens, mask = _inputs(5)
    valid = mask[:, :-1].astype(bool)
    got32, want, _ = _program_logprobs(config_name, 5, tokens, mask)
    got16, _, _ = _program_logprobs(config_name, 5, tokens, mask, dtype="bfloat16")
    rms32 = float(np.sqrt(np.mean((got32 - want)[valid] ** 2)))
    rms16 = float(np.sqrt(np.mean((got16 - want)[valid] ** 2)))
    assert rms16 > 10 * rms32
