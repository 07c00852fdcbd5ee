"""The Mamba-2 recurrence three ways (`trlx_tpu/ops/ssd.py`): the scan over
tokens that defines it, the chunked form a forward and a prefill run, and the
decode kernel (through the Pallas interpreter) stepped over the same tokens,
on seeded inputs in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops import ssd

B, T, H, G, N, P = 2, 83, 4, 2, 16, 8  # 83: two chunks of 32 and 19 more


def inputs(seed, t=T, b=B, dt_range=(1e-3, 1e-1), rate=(1.0, 16.0)):
    """The family's published initialisation: dt log-uniform in `dt_range`,
    A = -U`rate`. (0.7, 1) x (1, 16) is what a seeded bias of 0.02 n gives: a
    state that forgets in two positions."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, H, P))
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, H), minval=lo, maxval=hi))
    A = -jax.random.uniform(ks[2], (H,), minval=rate[0], maxval=rate[1])
    Bm = jax.random.normal(ks[3], (b, t, G, N)) * N ** -0.5
    C = jax.random.normal(ks[4], (b, t, G, N))
    return x, dt, A, Bm, C


# the published range (a state hundreds of positions old still counts), a head that forgets
# everything in a step (e^-80; a `B / cumprod` over a chunk would need e^+2500) and one that
# forgets nothing in a chunk
DECAYS = {"published_decay": {}, "forgets_in_a_step": {"dt_range": (5.0, 10.0)},
          "forgets_nothing": {"dt_range": (1e-6, 1e-5)}}
by_decay = pytest.mark.parametrize("decay", list(DECAYS.values()), ids=list(DECAYS))


def off(got, want):
    """The largest difference over the largest entry wanted: dt in the published
    range makes states and outputs of a few hundredths."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def at(a, sl):
    """Positions `sl` of the inputs (A has none)."""
    x, dt, A, Bm, C = a
    return x[:, sl], dt[:, sl], A, Bm[:, sl], C[:, sl]


def step_through_the_kernel(a, live=None, state=None):
    x, dt, A, Bm, C = a
    b, t = x.shape[:2]
    state = jnp.zeros((b, H, N, P), jnp.float32) if state is None else state
    live = jnp.ones((b, t), jnp.int32) if live is None else live
    outs = []
    step = jax.jit(lambda state, x, dt, Bm, C, live: ssd.ssd_decode(state, x, dt, A, Bm, C, live, interpret=True))
    for i in range(t):
        y, state = step(state, x[:, i], dt[:, i], Bm[:, i], C[:, i], live[:, i])
        outs.append(y)
    return jnp.stack(outs, 1), state


@by_decay
def test_scan_chunks_and_kernel_agree(decay):
    a = inputs(0, **decay)
    y_scan, s_scan = jax.jit(ssd.ssd_recurrent)(*a)
    y_chunk, s_chunk = jax.jit(lambda *a: ssd.ssd_chunked(*a, chunk=32))(*a)
    y_step, s_step = step_through_the_kernel(at(a, slice(0, 40)))
    assert bool(jnp.isfinite(y_chunk).all()) and bool(jnp.isfinite(s_chunk).all())
    assert off(y_chunk, y_scan) < 1e-5 and off(s_chunk, s_scan) < 1e-5
    y_40, s_40 = jax.jit(ssd.ssd_recurrent)(*at(a, slice(0, 40)))
    assert off(y_step, y_40) < 1e-5 and off(s_step, s_40) < 1e-5
    if not decay:  # the first tokens are still in the state forty tokens on
        _, without = ssd.ssd_recurrent(*at(a, slice(4, 40)))
        assert off(without, s_40) > 0.005  # a thousand times what the forms differ by


def test_a_chunk_edge_and_a_carried_state():
    """Two calls of the chunked form, the second from the first's state, cut
    inside a chunk: the whole sequence's numbers, at any chunk size; then the
    kernel from the carried state."""
    a = inputs(1)
    y_whole, s_whole = jax.jit(ssd.ssd_recurrent)(*a)
    y_a, s_a = jax.jit(lambda *a: ssd.ssd_chunked(*a, chunk=32))(*at(a, slice(0, 37)))
    y_b, s_b = jax.jit(lambda *a, state: ssd.ssd_chunked(*a, state=state, chunk=32))(
        *at(a, slice(37, None)), state=s_a)
    assert off(jnp.concatenate([y_a, y_b], 1), y_whole) < 1e-5 and off(s_b, s_whole) < 1e-5
    for chunk in (16, 24, 128):  # other chunk sizes (the last is one chunk of 83), the same numbers
        assert off(jax.jit(lambda *a: ssd.ssd_chunked(*a, chunk=chunk))(*a)[0], y_whole) < 1e-5
    y_c, s_c = step_through_the_kernel(at(a, slice(37, 60)), state=s_a)
    assert off(y_c, y_whole[:, 37:60]) < 1e-5


@pytest.mark.parametrize("side", ["left", "right"])
def test_a_padded_position_is_the_identity(side):
    """dt = 0 at the padding, on either side of the real tokens: the real
    tokens' outputs and the final state are the unpadded sequence's."""
    a = inputs(2, t=50)
    pad = lambda v: jnp.pad(v, ((0, 0), (13, 0) if side == "left" else (0, 13)) + ((0, 0),) * (v.ndim - 2))
    x, dt, A, Bm, C = a
    x, dt, Bm, C = (pad(v) for v in (x, dt, Bm, C))
    real = slice(13, None) if side == "left" else slice(0, 50)
    # garbage where the padding is, but for what makes it the identity
    noise = jax.random.normal(jax.random.PRNGKey(9), Bm.shape)
    Bm, C = (jnp.where(jnp.zeros_like(v).at[:, real].set(1) > 0, v, noise) for v in (Bm, C))
    y_want, s_want = jax.jit(ssd.ssd_recurrent)(*a)
    for form in (ssd.ssd_recurrent, lambda *a: ssd.ssd_chunked(*a, chunk=32)):
        y, s = jax.jit(form)(x, dt, A, Bm, C)
        assert off(y[:, real], y_want) < 1e-5 and off(s, s_want) < 1e-5


def test_a_row_with_no_token_keeps_its_state_to_the_bit():
    """`live` 0: the kernel and the plain step copy the row's state through
    and give 0, whatever the row's inputs; a live row beside it moves."""
    a = at(inputs(3, t=1), 0)
    x, dt, A, Bm, C = a
    state = jax.random.normal(jax.random.PRNGKey(4), (B, H, N, P))
    live = jnp.asarray([0, 1], jnp.int32)
    for mode in ("interpret", None):
        y, new = jax.jit(lambda s: ssd.ssd_decode_step(s, x, dt, A, Bm, C, live, mode))(state)
        assert np.array_equal(new[0], state[0]) and not np.asarray(y[0]).any()
        y_want, s_want = ssd.ssd_step(state[1], x[1], dt[1], A, Bm[1], C[1])
        assert off(new[1], s_want) < 1e-6 and off(y[1], y_want) < 1e-5


def test_the_chunked_form_differentiates_like_the_scan():
    """Gradients to every input through three chunks (the scan over chunks
    recomputes a chunk from its carried state) against the definition's."""
    a = inputs(5, t=70)
    weight = jax.random.normal(jax.random.PRNGKey(6), (B, 70, H, P))

    def loss(form):
        def f(*a):
            y, s = form(*a)
            return (y * weight).sum() + (s * s).sum()
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))

    want = loss(ssd.ssd_recurrent)(*a)
    got = loss(lambda *a: ssd.ssd_chunked(*a, chunk=32))(*a)
    for name, w, g in zip(("x", "dt", "A", "B", "C"), want, got):
        assert bool(jnp.isfinite(g).all()), name
        assert off(g, w) < 1e-4, name


def test_the_kernel_refuses_what_its_tiling_does_not_take():
    assert ssd.decode_kernel_takes(32, 256, 128) and ssd.decode_kernel_takes(64, 128, 128)
    assert not ssd.decode_kernel_takes(24, 256, 128) and not ssd.decode_kernel_takes(32, 64, 128)
    a = at(inputs(7, t=1), 0)
    with pytest.raises(ValueError, match="groups of 32 heads"):
        ssd.ssd_decode(jnp.zeros((B, H, N, P)), *a, jnp.ones((B,), jnp.int32))
