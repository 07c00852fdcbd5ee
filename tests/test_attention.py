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

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_naive = jax.jit(jax.grad(loss_naive, argnums=(0, 1, 2)))(q, k, v)
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
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
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
    params = jax.jit(model_x.init)(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = jax.jit(model_x.apply)(params, jnp.asarray(tokens), jnp.asarray(mask))

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
    params = jax.jit(model_x.init)(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = jax.jit(model_x.apply)(params, jnp.asarray(tokens), jnp.asarray(mask))
    lf, _, _ = jax.jit(model_f.apply)(params, jnp.asarray(tokens), jnp.asarray(mask))
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
    params = jax.jit(model_x.init)(jax.random.PRNGKey(0), jnp.asarray(tokens), jnp.asarray(mask))

    lx, _, _ = jax.jit(model_x.apply)(params, jnp.asarray(tokens), jnp.asarray(mask))
    lb, _, _ = jax.jit(model_b.apply)(params, jnp.asarray(tokens), jnp.asarray(mask))
    valid = mask[:, :, None].astype(bool)
    np.testing.assert_allclose(
        np.where(valid, lx, 0), np.where(valid, lb, 0), atol=2e-4, rtol=2e-4
    )

    def loss(m):
        def f(p):
            lg, _, _ = m.apply(p, jnp.asarray(tokens), jnp.asarray(mask))
            return (lg * mask[:, :, None]).sum()
        return f

    gx = jax.jit(jax.grad(loss(model_x)))(params)
    gb = jax.jit(jax.grad(loss(model_b)))(params)
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
    g = jax.jit(jax.grad(lambda q: jnp.sum(blockwise_attention(q, q, q, mask, True, 8) ** 2)))(q)
    assert np.isfinite(np.asarray(g)).all()

    runtime = MeshRuntime.from_config(
        type("P", (), {"data": 2, "fsdp": 1, "tensor": 1, "sequence": 4})()
    )
    g2 = jax.jit(jax.grad(
        lambda q: jnp.sum(context_parallel_attention(runtime.mesh, q, q, q, mask) ** 2)
    ))(q)
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

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
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


# -- the dense path under grouped heads --------------------------------------
#
# `Attention`'s dense contraction (every cached or unfused call) keeps K and V
# at n_kv_heads and folds the group into the query's axes. The reference is
# what it did before: K and V repeated to the query heads, `jnp.repeat(k, g,
# axis=2)`, in front of the multi-head products. Here that is the same module
# at n_kv_heads == n_heads whose K/V projections, prefix slots and cache are
# the grouped ones repeated head by head, so the reference runs through the
# multi-head branch, which holds no grouping at all.

HD = 8


def _attn_cfg(nh, nkv, **extra):
    from trlx_tpu.models.transformer import TransformerConfig

    return TransformerConfig(**{**dict(
        vocab_size=32, d_model=nh * HD, n_layers=1, n_heads=nh, n_kv_heads=nkv, d_ff=16,
        pos_embed="rope", attn_impl="xla", dtype=jnp.float32, param_dtype=jnp.float32), **extra})


def _repeat_kv_heads(tree, nkv, g):
    """The grouped module's parameters (or cache) with every kv head
    repeated g times: what `jnp.repeat(k, g, axis=2)` made of K and V."""
    def leaf(path, a):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith(("k_proj/kernel", "v_proj/kernel", "k_proj/bias", "v_proj/bias")):
            heads = a.reshape(a.shape[:-1] + (nkv, HD))
            return jnp.repeat(heads, g, axis=-2).reshape(a.shape[:-1] + (nkv * g * HD,))
        if name.endswith(("prefix_k", "prefix_v")):  # [P, nkv, hd]
            return jnp.repeat(a, g, axis=1)
        if name in ("k", "v"):  # a layer's cache [b, S, nkv, hd]
            return jnp.repeat(a, g, axis=2)
        return a

    return jax.tree_util.tree_map_with_path(leaf, tree)


GROUPS = [(4, 2), (8, 2), (4, 1)]  # groups of 2 and 4, and MQA
VARIANTS = {
    "plain": {},
    "alibi": dict(alibi=True, pos_embed="none"),
    "window": dict(sliding_window=5),
    "prefix": dict(prefix_tokens=3),
}
# every group plain; ALiBi, the window and the prefix on one group each
GROUPED_CASES = [(nh, nkv, "plain") for nh, nkv in GROUPS] + [
    (4, 2, "alibi"), (8, 2, "window"), (4, 1, "prefix")]


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar_index", "row_index"])
@pytest.mark.parametrize("t", [1, 3], ids=["one_position", "block_of_3"])
@pytest.mark.parametrize("nh,nkv,variant", GROUPED_CASES,
                         ids=[f"{nh}q{nkv}kv-{v}" for nh, nkv, v in GROUPED_CASES])
def test_dense_cached_attention_keeps_kv_heads_and_matches_repeated_kv(nh, nkv, variant, t, per_row):
    from trlx_tpu.models.transformer import Attention, cached_bias

    g = nh // nkv
    cfg, ref_cfg = _attn_cfg(nh, nkv, **VARIANTS[variant]), _attn_cfg(nh, nh, **VARIANTS[variant])
    b, S = 3, 12
    keys = jax.random.split(jax.random.PRNGKey(nh * 10 + nkv + t), 4)
    h = jax.random.normal(keys[0], (b, t, cfg.d_model), jnp.float32)
    # rows at their own depths (the slot pool) or all at one (the sampler)
    depth = jnp.asarray([4, 7, 2] if per_row else [5, 5, 5], jnp.int32)
    cache_index = depth if per_row else depth[0]
    cols = jnp.arange(S)[None, :]
    new_mask = (cols < depth[:, None] + t).astype(jnp.int32)
    if not per_row:
        new_mask = new_mask.at[1, :2].set(0)  # a left-padded row
    positions = depth[:, None] + jnp.arange(t)[None, :] - (1 - new_mask[:, :1]) * 2
    bias = cached_bias(cfg, new_mask, positions, block_start=cache_index if t > 1 else None)
    filled = (cols < depth[:, None])[:, :, None, None]
    cache = {name: jnp.where(filled, jax.random.normal(key, (b, S, nkv, HD), jnp.float32), 0.0)
             for name, key in zip("kv", keys[1:3])}
    args = (h, bias, positions)
    params = Attention(cfg).init(keys[3], *args, cache, cache_index)["params"]
    if variant == "prefix":  # the slots start near 0: make them count
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: a * 50.0 if "prefix" in str(p[-1]) else a, params)

    out, new_cache = Attention(cfg).apply({"params": params}, *args, cache, cache_index)
    ref, ref_cache = Attention(ref_cfg).apply(
        {"params": _repeat_kv_heads(params, nkv, g)}, *args, _repeat_kv_heads(cache, nkv, g), cache_index)

    assert out.shape == (b, t, cfg.d_model)
    assert new_cache["k"].shape == (b, S, nkv, HD)  # the cache stays at n_kv_heads
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    for name in "kv":
        np.testing.assert_allclose(jnp.repeat(new_cache[name], g, axis=2), ref_cache[name], atol=1e-6)


@pytest.mark.parametrize("nh,nkv,variant", [(4, 2, "plain"), (4, 1, "alibi")],
                         ids=["4q2kv-plain", "4q1kv-alibi"])
def test_dense_forward_gradients_match_repeated_kv(nh, nkv, variant):
    """A whole `attn_impl="xla"` forward of a grouped model: its logits and
    every gradient against the multi-head model with the K/V projections
    repeated. A repeated head's gradient is spread over its g copies, so the
    reference's are summed back over them."""
    from trlx_tpu.models.transformer import TransformerLM

    g = nh // nkv
    extra = dict(VARIANTS[variant], n_layers=2)
    cfg, ref_cfg = _attn_cfg(nh, nkv, **extra), _attn_cfg(nh, nh, **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 9), 0, cfg.vocab_size)
    mask = jnp.ones_like(tokens).at[0, :3].set(0)
    params = jax.jit(TransformerLM(cfg).init)(jax.random.PRNGKey(1), tokens, mask)["params"]
    weight = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.vocab_size), jnp.float32)

    def loss(model_cfg):
        return lambda p: (TransformerLM(model_cfg).apply({"params": p}, tokens, mask)[0] * weight).sum()

    value, grads = jax.jit(jax.value_and_grad(loss(cfg)))(params)
    ref_value, ref_grads = jax.jit(jax.value_and_grad(loss(ref_cfg)))(_repeat_kv_heads(params, nkv, g))
    np.testing.assert_allclose(value, ref_value, rtol=1e-5)

    def fold(a, like):
        if a.shape == like.shape:
            return a
        heads = a.reshape(a.shape[:-1] + (nkv, g, HD))  # a k_proj / v_proj leaf
        return heads.sum(axis=-2).reshape(like.shape)

    folded = jax.tree_util.tree_map(fold, ref_grads, params)
    moved = 0
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(folded)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4, err_msg=str(path))
        moved += bool(np.abs(got).max() > 0)
    assert moved == len(jax.tree_util.tree_leaves(grads))
