"""Fused/ring attention vs the naive softmax reference.

The reference repo has no attention kernels of its own (flash attention is
delegated to TransformerEngine, SURVEY.md §2.6), so the oracle here is the
mathematical definition, computed densely in f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops.attention import (
    _flash_fwd_pallas,
    blockwise_attention,
    flash_attention,
)


def naive_attention(q, k, v, mask=None, causal=True):
    b, tq, nh, hd = q.shape
    tk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / np.sqrt(hd)
    allowed = jnp.ones((tq, tk), bool)
    if causal:
        allowed = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
    bias = jnp.where(allowed, 0.0, -1e30)[None, None]
    if mask is not None:
        bias = bias + jnp.where(mask[:, None, None, :].astype(bool), 0.0, -1e30)
    p = jax.nn.softmax(s + bias, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def random_qkv(key, b=2, t=64, nh=4, hd=32):
    kq, kk, kv, km = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, t, nh, hd), jnp.float32)
    k = jax.random.normal(kk, (b, t, nh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, t, nh, hd), jnp.float32)
    # left-padded-style mask with some zeros
    lengths = jax.random.randint(km, (b,), t // 2, t + 1)
    mask = (jnp.arange(t)[None, :] < lengths[:, None]).astype(jnp.int32)
    return q, k, v, mask


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_k", [16, 64, 128])
def test_blockwise_matches_naive(causal, block_k):
    q, k, v, mask = random_qkv(jax.random.PRNGKey(0))
    out = blockwise_attention(q, k, v, mask, causal=causal, block_k=block_k)
    ref = naive_attention(q, k, v, mask, causal=causal)
    # padded key rows are excluded either way; padded query rows may differ
    # (both paths produce garbage there) — compare valid query rows only
    valid = mask[:, :, None, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, out, 0), np.where(valid, ref, 0), atol=1e-5, rtol=1e-5
    )


def test_blockwise_no_mask():
    q, k, v, _ = random_qkv(jax.random.PRNGKey(1), t=32)
    out = blockwise_attention(q, k, v, None, causal=True)
    ref = naive_attention(q, k, v, None, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_gradients_match_naive():
    q, k, v, mask = random_qkv(jax.random.PRNGKey(2), t=32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, mask, causal=True)
        return jnp.sum(jnp.where(mask[:, :, None, None] > 0, out, 0.0) ** 2)

    def loss_naive(q, k, v):
        out = naive_attention(q, k, v, mask, causal=True)
        return jnp.sum(jnp.where(mask[:, :, None, None] > 0, out, 0.0) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(gf, gn, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("t", [64, 96])
def test_pallas_kernel_interpret_matches_naive(t):
    """Validate the Pallas kernel logic itself via the interpreter (the real
    TPU path compiles the same kernel)."""
    q, k, v, mask = random_qkv(jax.random.PRNGKey(3), t=t, hd=64)
    out = _flash_fwd_pallas(q, k, v, mask, True, 32, 32, interpret=True)
    ref = naive_attention(q, k, v, mask, causal=True)
    valid = mask[:, :, None, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, out, 0), np.where(valid, ref, 0), atol=1e-5, rtol=1e-5
    )


def test_ring_attention_matches_naive():
    from trlx_tpu.parallel import MeshRuntime
    from trlx_tpu.parallel.context import context_parallel_attention

    runtime = MeshRuntime.from_config(
        type("P", (), {"data": 2, "fsdp": 1, "tensor": 1, "sequence": 4})()
    )
    q, k, v, mask = random_qkv(jax.random.PRNGKey(4), b=2, t=64)
    out = jax.jit(
        lambda q, k, v, m: context_parallel_attention(runtime.mesh, q, k, v, m)
    )(q, k, v, mask)
    ref = naive_attention(q, k, v, mask, causal=True)
    valid = mask[:, :, None, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, np.asarray(out), 0), np.where(valid, ref, 0),
        atol=1e-5, rtol=1e-5,
    )


def test_ring_attention_gradable():
    from trlx_tpu.parallel import MeshRuntime
    from trlx_tpu.parallel.context import context_parallel_attention

    runtime = MeshRuntime.from_config(
        type("P", (), {"data": 1, "fsdp": 1, "tensor": 1, "sequence": 8})()
    )
    q, k, v, _ = random_qkv(jax.random.PRNGKey(5), b=1, t=64)

    def loss(q, k, v):
        return jnp.sum(context_parallel_attention(runtime.mesh, q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_blockwise_gqa_matches_naive():
    """GQA: q has 8 heads, kv stay at 2 — fused paths map q→kv heads per
    block instead of materializing repeated KV."""
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    b, t, nh, nkv, hd = 2, 32, 8, 2, 16
    q = jax.random.normal(kq, (b, t, nh, hd), jnp.float32)
    k = jax.random.normal(kk, (b, t, nkv, hd), jnp.float32)
    v = jax.random.normal(kv, (b, t, nkv, hd), jnp.float32)
    k_rep = jnp.repeat(k, nh // nkv, axis=2)
    v_rep = jnp.repeat(v, nh // nkv, axis=2)
    out = blockwise_attention(q, k, v, None, causal=True, block_k=16)
    ref = naive_attention(q, k_rep, v_rep, None, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    out_pl = _flash_fwd_pallas(q, k, v, None, True, 16, 16, interpret=True)
    np.testing.assert_allclose(out_pl, ref, atol=1e-5, rtol=1e-5)


def test_model_ring_matches_xla():
    """Full TransformerLM under shard_map with ring attention == the plain
    xla-attention forward (rope positions must be globally correct)."""
    from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
    from trlx_tpu.parallel import MeshRuntime

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    base = dict(
        vocab_size=67, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32, pos_embed="rope",
        norm="rmsnorm", activation="silu", glu=True, tie_embeddings=False,
        use_bias=False,
    )
    runtime = MeshRuntime.from_config(
        type("P", (), {"data": 2, "fsdp": 1, "tensor": 1, "sequence": 4})()
    )
    tokens = np.tile(np.arange(32)[None, :] % 67, (2, 1)).astype(np.int32)
    mask = np.ones((2, 32), np.int32)
    mask[1, -8:] = 0  # right padding on one row

    cfg_x = TransformerConfig(**base, attn_impl="xla")
    cfg_r = TransformerConfig(**base, attn_impl="ring")
    model_x, model_r = TransformerLM(cfg_x), TransformerLM(cfg_r)
    params = model_x.init(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = model_x.apply(params, jnp.asarray(tokens), jnp.asarray(mask))

    ring_fwd = shard_map(
        lambda p, tok, m: model_r.apply(p, tok, m)[0],
        mesh=runtime.mesh,
        in_specs=(P(), P(None, "sequence"), P(None, "sequence")),
        out_specs=P(None, "sequence"),
    )
    lr = jax.jit(ring_fwd)(params, jnp.asarray(tokens), jnp.asarray(mask))
    valid = mask[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, np.asarray(lr), 0), np.where(valid, lx, 0),
        atol=2e-4, rtol=2e-4,
    )


def test_model_flash_matches_xla():
    """TransformerLM forward with attn_impl='flash' equals the einsum path."""
    from trlx_tpu.models.transformer import TransformerConfig, TransformerLM

    base = dict(
        vocab_size=101, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    tokens = np.array([[5, 6, 7, 8, 9, 10, 11, 12]] * 2)
    mask = np.array([[1] * 8, [0, 0, 1, 1, 1, 1, 1, 1]])

    cfg_x = TransformerConfig(**base, attn_impl="xla")
    cfg_f = TransformerConfig(**base, attn_impl="flash")
    model_x, model_f = TransformerLM(cfg_x), TransformerLM(cfg_f)
    params = model_x.init(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = model_x.apply(params, jnp.asarray(tokens), jnp.asarray(mask))
    lf, _, _ = model_f.apply(params, jnp.asarray(tokens), jnp.asarray(mask))
    valid = mask[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, lx, 0), np.where(valid, lf, 0), atol=2e-4, rtol=2e-4
    )


def test_model_blockwise_matches_xla():
    """attn_impl='blockwise' (the cold-cache long-context path: pure-XLA
    lax.scan flash equivalent, r5) equals the einsum path, including GQA
    kv-head repetition and the gradient."""
    from trlx_tpu.models.transformer import TransformerConfig, TransformerLM

    base = dict(
        vocab_size=101, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
    )
    tokens = np.array([[5, 6, 7, 8, 9, 10, 11, 12]] * 2)
    mask = np.array([[1] * 8, [0, 0, 1, 1, 1, 1, 1, 1]])

    cfg_x = TransformerConfig(**base, attn_impl="xla")
    cfg_b = TransformerConfig(**base, attn_impl="blockwise")
    model_x, model_b = TransformerLM(cfg_x), TransformerLM(cfg_b)
    params = model_x.init(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = model_x.apply(params, jnp.asarray(tokens), jnp.asarray(mask))
    lb, _, _ = model_b.apply(params, jnp.asarray(tokens), jnp.asarray(mask))
    valid = mask[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, lx, 0), np.where(valid, lb, 0), atol=2e-4, rtol=2e-4
    )

    def loss(m):
        def f(p):
            lg, _, _ = m.apply(p, jnp.asarray(tokens), jnp.asarray(mask))
            return (lg * mask[:, :, None]).sum()
        return f

    gx = jax.grad(loss(model_x))(params)
    gb = jax.grad(loss(model_b))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
        ),
        gx, gb,
    )


def test_fully_masked_query_rows_have_finite_grads():
    """Left-padded batches give fully-masked query rows; the blockwise/ring
    backward must not blow up (regression: the finalize division clamp
    multiplied upstream grads by 1e30 on the masked branch)."""
    from trlx_tpu.parallel import MeshRuntime
    from trlx_tpu.parallel.context import context_parallel_attention
    from trlx_tpu.ops.attention import blockwise_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 16, 2, 8)).astype(np.float32))
    mask = np.ones((2, 16), np.int32)
    mask[0, :4] = 0  # left padding
    mask = jnp.asarray(mask)

    # deliberately do NOT mask the output: pad-row upstream grads flow
    g = jax.grad(lambda q: jnp.sum(blockwise_attention(q, q, q, mask, True, 8) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()

    runtime = MeshRuntime.from_config(
        type("P", (), {"data": 2, "fsdp": 1, "tensor": 1, "sequence": 4})()
    )
    g2 = jax.grad(
        lambda q: jnp.sum(context_parallel_attention(runtime.mesh, q, q, q, mask) ** 2)
    )(q)
    assert np.isfinite(np.asarray(g2)).all()


# ---------------------------------------------------------------------------
# Flash backward (FlashAttention-2 from (out, lse) residuals)
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v, mask, causal=True):
    b, t, nh, hd = q.shape
    nkv = k.shape[2]
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / np.sqrt(hd)
    allowed = mask[:, None, None, :] > 0
    if causal:
        tri = np.tril(np.ones((t, t), bool))
        allowed = allowed & tri[None, None]
    s = jnp.where(allowed, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(allowed, -1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def _grad_case(nkv=None):
    rng = np.random.default_rng(0)
    b, t, nh, hd = 2, 64, 4, 16
    nkv = nkv or nh
    q = jnp.asarray(rng.normal(size=(b, t, nh, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, t, nkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, t, nkv, hd)).astype(np.float32))
    mask = np.ones((b, t), np.int32)
    mask[0, -9:] = 0   # right padding
    mask[1, :5] = 0    # left padding
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("nkv", [4, 2])
def test_flash_backward_xla_matches_dense_autodiff(nkv):
    """The custom blockwise backward (used whenever flash_attention is
    differentiated off-TPU) == autodiff through dense masked attention,
    padding and GQA included."""
    from trlx_tpu.ops.attention import flash_attention

    q, k, v, mask = _grad_case(nkv)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, mask, causal=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (_dense_attention(q, k, v, mask, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("nkv", [4, 2])
def test_flash_backward_pallas_interpret_matches_xla(nkv):
    """The Pallas dq / dkv kernels (interpreter mode) == the XLA blockwise
    backward on identical residuals."""
    from trlx_tpu.ops.attention import (
        _flash_bwd_pallas,
        _flash_bwd_xla,
        blockwise_attention_lse,
    )

    q, k, v, mask = _grad_case(nkv)
    out, lse = blockwise_attention_lse(q, k, v, mask, causal=True, block_k=32)
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    dq_p, dk_p, dv_p = _flash_bwd_pallas(q, k, v, mask, out, lse, g,
                                         True, 32, 32, interpret=True)
    dq_x, dk_x, dv_x = _flash_bwd_xla(q, k, v, mask, out, lse, g, True, 32)
    np.testing.assert_allclose(np.asarray(dq_p), np.asarray(dq_x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk_p), np.asarray(dk_x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv_p), np.asarray(dv_x), atol=1e-4)


def test_flash_fwd_lse_kernel_interpret():
    """The LSE-emitting forward kernel == blockwise forward + its LSE,
    dead (fully-masked) rows included."""
    from trlx_tpu.ops.attention import (
        _flash_fwd_pallas_lse,
        blockwise_attention_lse,
    )

    q, k, v, mask = _grad_case()
    mask = mask.at[1, :].set(0)  # a fully-masked row
    out_p, lse_p = _flash_fwd_pallas_lse(q, k, v, mask, True, 32, 32,
                                         interpret=True)
    out_b, lse_b = blockwise_attention_lse(q, k, v, mask, causal=True, block_k=32)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_b), atol=1e-4)


def test_flash_backward_memory_is_not_quadratic():
    """Compile-time memory analysis: the backward of a long-sequence flash
    forward must not bank O(t^2) residuals (the old recompute-by-vjp did,
    and OOMed real training at seq 8192)."""
    from trlx_tpu.ops.attention import flash_attention

    b, t, nh, hd = 1, 4096, 2, 16
    q = jnp.zeros((b, t, nh, hd), jnp.float32)
    mask = jnp.ones((b, t), jnp.int32)

    def loss(q):
        return (flash_attention(q, q, q, mask, causal=True) ** 2).sum()

    compiled = jax.jit(jax.grad(loss)).lower(q).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:
        pytest.skip("backend exposes no memory analysis")
    total = analysis.temp_size_in_bytes
    # O(t^2) in f32 would be >= t*t*4 = 64MB per head; linear-in-t buffers
    # at these shapes stay far below
    assert total < t * t * 4, f"backward temps look quadratic: {total}"
