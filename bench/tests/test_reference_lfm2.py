"""`bench/reference/lfm2_moe.py` against a naive loop, one token at a time,
written from the layer equations alone (numpy, float64, no batching, no
masks: a row is its real tokens); against the program's `TransformerLM` at
the rehearsal size; and the lower-precision control, which the limits of
`correct` have to refuse.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_reference_lfm2.py -q
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

with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
    CONFIG = json.load(f)["bench"]
SIZES = CONFIG["rehearse_sizes"]
ref = load_module("reference/lfm2_moe.py")


def _model(dtype="float32"):
    import jax.numpy as jnp

    from trlx_tpu.models import CausalLMPolicy, config_from_preset

    extra = dict(CONFIG["rehearse"]["model_extra_configs"], attn_impl="xla")
    cfg = config_from_preset(CONFIG["rehearse"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, dtype=jnp.dtype(dtype))
    return CausalLMPolicy(cfg)


def _params(seed):
    import jax.numpy as jnp

    t = jnp.zeros((1, 8), jnp.int32)
    return weights.make_params(weights.param_shapes(_model(), t, jnp.ones_like(t)), seed, jnp.float32)


def _inputs(seed, lens=(40, 33, 12), width=40):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, SIZES["vocab_size"], size=(len(lens), width)).astype(np.int32)
    mask = np.asarray([[0] * (width - n) + [1] * n for n in lens], np.int32)
    return tokens * mask, mask


# --- the naive loop ---------------------------------------------------------


def _rms(x, scale, eps):
    return x / np.sqrt((x * x).mean() + eps) * scale


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rope(x, pos, base):
    d = x.shape[-1]
    out = x.copy()
    for i in range(d // 2):
        angle = pos / base ** (2 * i / d)
        a, b = x[..., i], x[..., i + d // 2]
        out[..., i] = a * np.cos(angle) - b * np.sin(angle)
        out[..., i + d // 2] = b * np.cos(angle) + a * np.sin(angle)
    return out


def naive_row_logprobs(lm, row_tokens, sizes):
    """log p(token[i + 1] | token[:i + 1]) for ONE row of real tokens."""
    p64 = lambda tree: {k: (p64(v) if isinstance(v, dict) else np.asarray(v, np.float64)) for k, v in tree.items()}
    lm = p64(lm)
    eps, heads, kv_heads = sizes["norm_eps"], sizes["num_attention_heads"], sizes["num_key_value_heads"]
    emb = lm["embed_tokens"]["embedding"]
    hs = [emb[t] for t in row_tokens]  # the residual stream, a vector a position
    for layer in range(sizes["num_hidden_layers"]):
        p = lm[f"block_{layer}"]
        xs = [_rms(h, p["ln_attn"]["scale"], eps) for h in hs]
        if sizes["layer_types"][layer] == "conv":
            w, taps = p["conv"]["kernel"], p["conv"]["kernel"].shape[0]
            zs, gates = [], []
            for x in xs:
                gate_b, gate_c, u = np.split(x @ p["conv"]["in_proj"]["kernel"], 3)
                zs.append(gate_b * u)
                gates.append(gate_c)
            ops = []
            for t in range(len(xs)):
                c = sum(w[j] * zs[t - (taps - 1) + j] for j in range(taps) if t - (taps - 1) + j >= 0)
                ops.append((gates[t] * c) @ p["conv"]["out_proj"]["kernel"])
        else:
            a, hd = p["attn"], len(hs[0]) // heads
            qs, ks, vs = [], [], []
            for pos, x in enumerate(xs):
                q = (x @ a["q_proj"]["kernel"]).reshape(heads, hd)
                k = (x @ a["k_proj"]["kernel"]).reshape(kv_heads, hd)
                q = np.stack([_rms(r, a["q_norm"]["scale"], eps) for r in q])
                k = np.stack([_rms(r, a["k_norm"]["scale"], eps) for r in k])
                qs.append(_rope(q, pos, sizes["rope_theta"]))
                ks.append(_rope(k, pos, sizes["rope_theta"]))
                vs.append((x @ a["v_proj"]["kernel"]).reshape(kv_heads, hd))
            ops = []
            for t in range(len(xs)):
                out = np.zeros((heads, hd))
                for h in range(heads):
                    g = h // (heads // kv_heads)
                    scores = np.asarray([qs[t][h] @ ks[s][g] for s in range(t + 1)]) / np.sqrt(hd)
                    probs = np.exp(scores - scores.max())
                    probs /= probs.sum()
                    out[h] = sum(pr * vs[s][g] for s, pr in enumerate(probs))
                ops.append(out.reshape(-1) @ a["o_proj"]["kernel"])
        hs = [h + o for h, o in zip(hs, ops)]
        m = p["mlp"]
        for t, h in enumerate(hs):
            x = _rms(h, p["ln_mlp"]["scale"], eps)
            if layer < sizes["num_dense_layers"]:
                y = (_silu(x @ m["gate_proj"]["kernel"]) * (x @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]
            else:
                s = 1.0 / (1.0 + np.exp(-(x @ m["router"]["kernel"])))
                chosen = np.argsort(-(s + m["expert_bias"]["bias"]), kind="stable")[: sizes["num_experts_per_tok"]]
                total = s[chosen].sum() + 1e-6
                held = m["expert_down"]["kernel"].shape[1] // len(x)
                y = np.zeros_like(x)
                for e in chosen:
                    if e >= held:  # an expert of another chip: its part is absent
                        continue
                    w1, w3, w2 = (np.split(m[n]["kernel"], held, axis=1)[e]
                                  for n in ("expert_gate", "expert_up", "expert_down"))
                    y += s[e] / total * ((_silu(x @ w1) * (x @ w3)) @ w2)
            hs[t] = h + y
    out = []
    for t in range(len(hs) - 1):
        logits = _rms(hs[t], lm["ln_f"]["scale"], eps) @ emb.T
        logits -= logits.max()
        out.append(logits[row_tokens[t + 1]] - np.log(np.exp(logits).sum()))
    return np.asarray(out)


@pytest.mark.parametrize("seed", [0, 3_000_000_019])
def test_reference_matches_the_naive_loop(seed):
    params = _params(seed)
    tokens, mask = _inputs(seed, lens=(14, 9, 3), width=14)
    got = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    for row, n in enumerate(mask.sum(-1)):
        want = naive_row_logprobs(params["lm"], tokens[row, -n:], SIZES)
        np.testing.assert_allclose(got[row, len(tokens[row]) - n:], want, atol=2e-4)


@pytest.mark.parametrize("seed", [0, 3_000_000_019])
def test_reference_matches_the_program_in_float32(seed):
    import jax
    import jax.numpy as jnp

    params = _params(seed)
    tokens, mask = _inputs(seed)
    with jax.default_matmul_precision("highest"):
        logits = _model().apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))[0]
    got = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    assert np.abs(got - want)[valid].max() < 2e-4


def _readings(seed):
    import jax.numpy as jnp

    params = _params(seed)
    tokens, mask = _inputs(seed, lens=(96, 70, 31, 96), width=96)
    valid = (mask[:, :-1] * mask[:, 1:]).astype(bool)
    want = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES))
    control = np.asarray(ref.logprobs(params["lm"], tokens, mask, SIZES, int8=True))
    logits = _model("bfloat16").apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))[0]
    program = np.asarray(load_module("reference/plain_ops.py").logprobs_of_next(logits, jnp.asarray(tokens)))
    rms = lambda a: float(np.sqrt(np.mean((a - want)[valid] ** 2)))
    return rms(program), rms(control)


@pytest.mark.parametrize("seed", [5, 2_147_483_747, 3_000_000_203])
def test_the_int8_control_is_told_apart(seed):
    """The reference computed in int8 in the program's place (every dense
    and expert product, both operands) reads well above the bfloat16 program
    on every seed, and the program under each limit of `correct`. The limits
    themselves were set at the cell's own size, where both readings are six
    times larger (ten layers at width 2048, and experts that flip on
    near-ties): this size's control stays under them, so the refusal itself
    is the chip's to show (`control_onchip.py`; readings in the reference's
    comment)."""
    program, control = _readings(seed)
    assert control > 1.3 * program, (program, control)
    for name, limit in ref.LIMITS["ppo"].items():
        assert program < limit, (name, program, limit)
