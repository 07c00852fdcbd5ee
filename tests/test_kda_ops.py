"""Kimi delta attention four ways (`trlx_tpu/ops/linear_attention.py`): the
scan over tokens that defines it, the chunked form a forward runs, the span
kernel a cached prefill runs and the decode kernel (both through the Pallas
interpreter), the last stepped over the same tokens, on seeded inputs in
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops import linear_attention as la

B, T, H, DK, DV = 2, 83, 4, 16, 8  # 83: a whole chunk of 64, a sub-chunk of 16 and 3 more


def inputs(seed, t=T, b=B, slow=False, floor=-5.0, g_step=None, beta_max=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, H, DK)))
    v = jax.random.normal(ks[2], (b, t, H, DV))
    # `slow`: decays near -0.09 a step, so a state forty tokens back still counts
    g = floor * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, H, DK)) - (4.0 if slow else 0.0))
    if g_step is not None:  # an unbounded gate's range: about `g_step` a step on every channel
        g = g_step * (0.5 + jax.random.uniform(ks[3], (b, t, H, DK)))
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, H)))
    return q, k, v, g, beta


# the bounded gate's two ranges (beta in (0, 1)), and an unbounded gate's ends with beta in (0, 2):
# a channel that forgets everything in a step (e^-30; a product about a reference in the middle
# of a sub-chunk would need e^+240) and one that forgets nothing in a chunk
DECAYS = {"published_decay": {}, "slow_decay": {"slow": True},
          "forgets_in_a_step_beta_to_2": {"g_step": -30.0, "beta_max": 2.0},
          "forgets_nothing_beta_to_2": {"g_step": -1e-4, "beta_max": 2.0}}
by_decay = pytest.mark.parametrize("decay", list(DECAYS.values()), ids=list(DECAYS))


def by_kernel(*x, state=None, **kw):
    """What a cached prefill runs where kernels run: `kda_chunk_fwd` a span, interpreted."""
    return la._chunked_by_kernel(*x, state, kw.get("span", la.SPAN), "interpret")


# the chunked form in XLA and as the span kernel: the same cases hold both to the scan
FORMS = {"xla": la.kda_chunked, "span_kernel": by_kernel}
by_form = pytest.mark.parametrize("form", list(FORMS.values()), ids=list(FORMS))


def step_through_the_kernel(x, live=None, state=None):
    b, t = x[0].shape[:2]
    state = jnp.zeros((b, H, DK, DV), jnp.float32) if state is None else state
    live = jnp.ones((b, t), jnp.int32) if live is None else live
    outs = []
    step = jax.jit(lambda state, *now: la.kda_decode(state, *now, interpret=True))  # one trace for every token
    for i in range(t):
        o, state = step(state, *(a[:, i] for a in x), live[:, i])
        outs.append(o)
    return jnp.stack(outs, 1), state


@by_decay
def test_scan_chunks_and_kernel_agree(decay):
    x = inputs(0, **decay)
    slow = decay.get("slow", False)
    o_scan, s_scan = la.kda_recurrent(*x)
    o_chunk, s_chunk = jax.jit(la.kda_chunked)(*x)
    o_step, s_step = step_through_the_kernel(tuple(a[:, :40] for a in x))
    assert float(jnp.abs(o_scan).max()) > 0.1
    assert bool(jnp.isfinite(o_chunk).all()) and bool(jnp.isfinite(s_chunk).all())
    assert np.abs(o_scan - o_chunk).max() < 1e-5 and np.abs(s_scan - s_chunk).max() < 1e-5
    # spans of two chunks, the state carried from one to the next: the same numbers
    o_span, s_span = jax.jit(lambda *a: la.kda_chunked(*a, chunk=16, span=32))(*x)
    assert np.abs(o_scan - o_span).max() < 1e-5 and np.abs(s_scan - s_span).max() < 1e-5
    # the span kernel, a chunk a span (a span edge, the state carried; two chunks of one span are the next test's)
    o_fwd, s_fwd = jax.jit(lambda *a: by_kernel(*a, span=la.CHUNK))(*x)
    assert bool(jnp.isfinite(o_fwd).all()) and bool(jnp.isfinite(s_fwd).all())
    assert np.abs(o_scan - o_fwd).max() < 1e-5 and np.abs(s_scan - s_fwd).max() < 1e-5
    assert np.abs(o_chunk - o_fwd).max() < 1e-5 and np.abs(s_chunk - s_fwd).max() < 1e-5
    o_40, s_40 = la.kda_recurrent(*(a[:, :40] for a in x))
    assert np.abs(o_40 - o_step).max() < 1e-5 and np.abs(s_40 - s_step).max() < 1e-5
    if slow:  # the first tokens are still in the state forty tokens on
        _, without = la.kda_recurrent(*(a[:, 4:40] for a in x))
        assert np.abs(s_40 - without).max() > 1e-2


@by_form
def test_a_chunk_edge_and_a_carried_state(form):
    """Two calls of the chunked form, the second from the first's state, cut
    inside a sub-chunk: the whole sequence's numbers."""
    x = inputs(1, slow=True)
    o_whole, s_whole = jax.jit(la.kda_recurrent)(*x)
    o_a, s_a = jax.jit(form)(*(a[:, :37] for a in x))
    o_b, s_b = jax.jit(form)(*(a[:, 37:] for a in x), state=s_a)
    assert np.abs(jnp.concatenate([o_a, o_b], 1) - o_whole).max() < 1e-5
    assert np.abs(s_b - s_whole).max() < 1e-5
    if form is by_kernel:  # the kernel's chunk is `CHUNK`, whatever the caller's
        return
    for chunk in (16, 32):  # other chunk sizes, the same numbers
        assert np.abs(jax.jit(lambda *a: la.kda_chunked(*a, chunk=chunk))(*x)[0] - o_whole).max() < 1e-5
    with pytest.raises(ValueError, match="multiple of 16"):
        la.kda_chunked(*x, chunk=24)


@pytest.mark.parametrize("side", ["left", "right"])
def test_a_padded_position_is_the_identity(side):
    """beta = 0 and g = 0 at the padding, on either side of the real tokens:
    the real tokens' outputs and the final state are the unpadded sequence's."""
    x = inputs(2, t=50, slow=True)
    pad = lambda a: jnp.pad(a, ((0, 0), (13, 0) if side == "left" else (0, 13)) + ((0, 0),) * (a.ndim - 2))
    q, k, v, g, beta = (pad(a) for a in x)
    real = slice(13, None) if side == "left" else slice(0, 50)
    # garbage where the padding is, but for what makes it the identity
    noise = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    q, k = (jnp.where(jnp.zeros_like(a).at[:, real].set(1) > 0, a, noise) for a in (q, k))
    o_want, s_want = jax.jit(la.kda_recurrent)(*x)
    for form in (la.kda_recurrent, la.kda_chunked, by_kernel):
        o, s = jax.jit(form)(q, k, v, g, beta)
        assert np.abs(o[:, real] - o_want).max() < 1e-5 and np.abs(s - s_want).max() < 1e-5


@pytest.mark.parametrize("floor", [-5.0, -12.0])
@by_form
def test_the_published_lower_bound_on_every_channel_does_not_overflow(form, floor):
    """g = -5 (and -12, the other published bound) on every channel for 64
    positions: a `k / cumprod` over the chunk would need e^320 (e^768); no
    factor the chunked form or the span kernel takes exceeds 1."""
    q, k, v, g, beta = inputs(3, t=64, b=1)
    g = jnp.full_like(g, floor)
    o_scan, s_scan = jax.jit(la.kda_recurrent)(q, k, v, g, beta)
    o, s = jax.jit(form)(q, k, v, g, beta)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert np.abs(o - o_scan).max() < 1e-5 and np.abs(s - s_scan).max() < 1e-5
    if form is by_kernel:  # forward only
        return
    grads = jax.jit(jax.grad(lambda g: la.kda_chunked(q, k, v, g, beta)[0].sum()))(g)
    assert bool(jnp.isfinite(grads).all())


@pytest.mark.parametrize("decay", [d for name, d in DECAYS.items() if name != "published_decay"],
                         ids=[name for name in DECAYS if name != "published_decay"])
def test_the_chunked_form_differentiates_like_the_scan(decay):
    """One span, and (at the slow decay, where a state crosses spans) spans of
    one chunk each: a gradient through the scan over spans recomputes a span
    from the state it kept."""
    x = inputs(4, t=40, **decay)
    loss = lambda form: lambda *a: (form(*a)[0] ** 2).sum() + form(*a)[1].sum()
    want = jax.jit(jax.grad(loss(la.kda_recurrent), argnums=(0, 1, 2, 3, 4)))(*x)
    by_spans = lambda *a: la.kda_chunked(*a, chunk=16, span=16)
    for form in (la.kda_chunked, by_spans) if decay.get("slow") else (la.kda_chunked,):
        got = jax.jit(jax.grad(loss(form), argnums=(0, 1, 2, 3, 4)))(*x)
        for a, b, name in zip(want, got, "q k v g beta".split()):
            if not (name == "g" and decay.get("g_step") == -30.0):  # nothing survives a step: no gradient to the decay
                assert float(jnp.abs(a).max()) > 1e-3, name
            assert bool(jnp.isfinite(b).all()), name
            # where nothing decays a gradient sums 40 tokens' terms and reaches 3: float32's 1e-5 is of that
            scale = max(1.0, float(jnp.abs(a).max())) if "g_step" in decay else 1.0
            assert np.abs(a - b).max() < 1e-5 * scale, name


def test_the_kernel_leaves_a_masked_row_s_state_to_the_bit_and_the_plain_step_agrees():
    x = inputs(5, t=6, b=3, slow=True)
    state = jax.random.normal(jax.random.PRNGKey(7), (3, H, DK, DV))
    live = jnp.asarray([1, 0, 1], jnp.int32)
    now = tuple(a[:, 0] for a in x)
    o, new = la.kda_decode(state, *now, live, interpret=True)
    assert np.array_equal(new[1], state[1]) and not np.array_equal(new[0], state[0])
    assert float(jnp.abs(o[1]).max()) == 0.0
    o_plain, new_plain = la.kda_decode_step(state, *now, live, None)
    assert np.abs(o - o_plain).max() < 1e-6 and np.abs(new - new_plain).max() < 1e-6
    assert np.array_equal(new_plain[1], state[1])
    # the compiled kernel's tiling: whole groups of 32 heads of 128-multiples; a caller that
    # asks for it elsewhere is told (the engine asks `decode_kernel_takes` first and counts)
    assert la.decode_kernel_takes(32, 128, 128) and not la.decode_kernel_takes(4, 16, 16)
    with pytest.raises(ValueError, match="groups of 32 heads"):
        la.kda_decode_step(state, *now, live, "pallas")


def test_who_takes_the_span_kernel_is_the_mode_and_the_tiling(monkeypatch):
    """`forward_only` (a cached prefill) hands the spans to `kda_chunk_fwd`
    where `ops.attention.kernel_mode()` says "pallas" and the tiling fits, or
    "interpret"; off the TPU, with kernels off, across a mesh, or without
    `forward_only` (a forward that may be differentiated) the XLA form runs."""
    from trlx_tpu.ops import attention

    x, calls, kernel = inputs(6, t=20, b=1), [], la._chunked_by_kernel
    monkeypatch.setattr(la, "_chunked_by_kernel", lambda *a: calls.append(a[-1]) or la.kda_chunked(*a[:6]))
    for mode, takes in (("off", None), ("sharded", None), ("interpret", "interpret"), ("pallas", None)):
        monkeypatch.setattr(attention, "kernel_mode", lambda mode=mode: mode)
        assert la.chunk_kernel_mode(H, DK, DV) == takes  # 4 heads of 16: not the compiled kernel's tiling
        assert la.chunk_kernel_mode(64, 128, 128) == (mode if mode in ("interpret", "pallas") else None)
        jax.eval_shape(lambda: (la.kda_chunked(*x, forward_only=True), la.kda_chunked(*x)))
    assert calls == ["interpret"]
    with pytest.raises(ValueError, match="groups of 32 heads"):
        kernel(*x, None, la.SPAN, "pallas")
