"""1F1B schedule (parallel/onef1b.py): grad parity vs the GPipe-autodiff
engine, and the activation-memory bound that motivates it.

The reference's Apex engine interleaves each microbatch's forward and
backward so at most O(S) microbatches are in flight and logits only ever
exist per-microbatch (modeling_nemo_ppo.py:713-731); the GPipe path here
banks the full batch's final activations AND hands [B, t, V] logits to an
outside-the-pipe loss. These tests pin that the hand-scheduled 1F1B
engine (in-pipe per-microbatch loss, ring stash of stage inputs) computes
THE SAME loss/grads while its backward temp memory stays independent of
the microbatch count and strictly below the GPipe program's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.parallel.onef1b import make_1f1b_grad_fn
from trlx_tpu.parallel.pipeline import (
    make_gpipe_forward_stacked,
    make_pipe_mesh,
    stack_block_params,
)
from trlx_tpu.trainer.pipelined_mixin import causal_ce_1f1b_parts
from trlx_tpu.trainer.sft_trainer import causal_lm_ce_loss


def _setup(n_layers=4, n_stages=2, B=16, t=32, freeze_split=0, vocab=97):
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=32, n_layers=n_layers, n_heads=4, d_ff=64,
        max_seq_len=t, dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, vocab, size=(B, t)), jnp.int32)
    # left-ish padding pattern with some fully-real rows
    mask = np.ones((B, t), np.int32)
    mask[::3, : t // 4] = 0
    mask = jnp.asarray(mask)
    params = model.init(jax.random.PRNGKey(0), tokens[:1], mask[:1])
    mesh = make_pipe_mesh(n_stages)
    stacked, rest = stack_block_params(params["params"], n_layers, n_stages)
    return cfg, model, mesh, stacked, rest, tokens, mask


def _gpipe_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask,
                          n_mb, freeze_split=0):
    fwd = make_gpipe_forward_stacked(
        model, cfg, mesh, n_microbatches=n_mb, freeze_split=freeze_split
    )

    def loss_fn(stacked, rest):
        logits = fwd(stacked, rest, tokens, mask)
        return causal_lm_ce_loss(logits, tokens, mask)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
        stacked, rest
    )
    return loss, grads


def _onef1b_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask,
                           n_mb, freeze_split=0):
    parts = causal_ce_1f1b_parts(model)
    engine = make_1f1b_grad_fn(
        model, cfg, mesh, n_mb, parts["loss_mb"], ctx_fn=parts["ctx_fn"],
        freeze_split=freeze_split,
    )

    def run(stacked, rest):
        batch = {"input_ids": tokens, "attention_mask": mask}
        toks, m, loss_batch = parts["prepare"](batch)
        loss, stats, (d_stacked, d_rest, d_heads) = engine(
            stacked, rest, {}, toks, m, loss_batch
        )
        return loss, (d_stacked, d_rest)

    return jax.jit(run)(stacked, rest)


def _assert_tree_close(a, b, rtol=2e-5, atol=1e-6):
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(flat_a) == len(flat_b)
    for path, la in flat_a:
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(flat_b[path]), rtol=rtol, atol=atol,
            err_msg=str(path),
        )


@pytest.mark.parametrize("n_mb", [2, 4])
def test_sft_grad_parity(n_mb):
    cfg, model, mesh, stacked, rest, tokens, mask = _setup()
    l0, g0 = _gpipe_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask, n_mb)
    l1, (ds, dr) = _onef1b_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask, n_mb)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


def test_grad_parity_with_freeze_split():
    """Bottom-2-layers frozen (num_layers_unfrozen semantics): the in-tick
    stop_gradient must cut the same gradients in both schedules."""
    cfg, model, mesh, stacked, rest, tokens, mask = _setup()
    l0, g0 = _gpipe_loss_and_grads(
        cfg, model, mesh, stacked, rest, tokens, mask, 4, freeze_split=2
    )
    l1, (ds, dr) = _onef1b_loss_and_grads(
        cfg, model, mesh, stacked, rest, tokens, mask, 4, freeze_split=2
    )
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])
    # and the split actually froze something: stage-0 block grads all zero
    frozen_leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x[:1, :1], ds)
    )
    assert all(float(jnp.abs(l).max()) == 0.0 for l in frozen_leaves)


@pytest.mark.parametrize("axes", [dict(tensor=2), dict(fsdp=2)])
def test_grad_parity_with_tensor_axis(axes):
    """1F1B with a GSPMD-auto tensor/fsdp axis inside the manual program
    (TP x PP / ZeRO x PP composition): the hand vjps must transpose
    correctly through the auto-sharded stage matmuls. f32 (XLA:CPU bf16
    partial-manual limitation, parallel/context.py)."""
    cfg, model, mesh, stacked, rest, tokens, mask = _setup()
    mesh_tp = make_pipe_mesh(2, **axes)
    l0, g0 = _gpipe_loss_and_grads(cfg, model, mesh_tp, stacked, rest, tokens, mask, 2)
    l1, (ds, dr) = _onef1b_loss_and_grads(cfg, model, mesh_tp, stacked, rest, tokens, mask, 2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


def test_grad_parity_with_sequence_axis():
    """1F1B x SP: ring attention inside every stage over a manual
    sequence axis, CE targets preshifted globally so no shard reads its
    neighbor's labels. Right padding (the SP CE convention)."""
    from dataclasses import replace

    cfg, model, mesh, stacked, rest, tokens, mask = _setup()
    rcfg = replace(cfg, attn_impl="ring")
    rmodel = TransformerLM(rcfg)
    # right-padded mask (SP CE requires it; _setup's default is left-ish)
    m = np.ones(mask.shape, np.int32)
    m[::3, -mask.shape[1] // 4:] = 0
    m = jnp.asarray(m)
    mesh_sp = make_pipe_mesh(2, sequence=2)
    l0, g0 = _gpipe_loss_and_grads(rcfg, rmodel, mesh_sp, stacked, rest, tokens, m, 2)
    l1, (ds, dr) = _onef1b_loss_and_grads(rcfg, rmodel, mesh_sp, stacked, rest, tokens, m, 2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


def test_m_smaller_than_stages():
    """M < S exercises the short-pipeline edge of the ring stash."""
    cfg, model, mesh, stacked, rest, tokens, mask = _setup(B=16)
    l0, g0 = _gpipe_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask, 1)
    l1, (ds, dr) = _onef1b_loss_and_grads(cfg, model, mesh, stacked, rest, tokens, mask, 1)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


def _temp_bytes(kind, n_mb):
    cfg, model, mesh, stacked, rest, tokens, mask = _setup(B=64, t=64, vocab=251)
    if kind == "gpipe":
        fwd = make_gpipe_forward_stacked(model, cfg, mesh, n_microbatches=n_mb)

        def loss_fn(stacked, rest):
            logits = fwd(stacked, rest, tokens, mask)
            return causal_lm_ce_loss(logits, tokens, mask)[0]

        fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    else:
        parts = causal_ce_1f1b_parts(model)
        engine = make_1f1b_grad_fn(
            model, cfg, mesh, n_mb, parts["loss_mb"], ctx_fn=parts["ctx_fn"]
        )

        def run(stacked, rest):
            toks, m, loss_batch = parts["prepare"](
                {"input_ids": tokens, "attention_mask": mask}
            )
            return engine(stacked, rest, {}, toks, m, loss_batch)

        fn = jax.jit(run)
    compiled = fn.lower(stacked, rest).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:
        pytest.skip("backend exposes no memory analysis")
    return analysis.temp_size_in_bytes


def test_memory_independent_of_microbatches():
    small = _temp_bytes("1f1b", 2)
    large = _temp_bytes("1f1b", 8)
    assert large < small * 1.5, (small, large)


def _interleaved_setup(n_layers, S, v, B=16, t=32, vocab=97):
    from trlx_tpu.parallel.pipeline import stack_block_params_interleaved

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=32, n_layers=n_layers, n_heads=4, d_ff=64,
        max_seq_len=t, dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, vocab, size=(B, t)), jnp.int32)
    mask = np.ones((B, t), np.int32)
    mask[::3, : t // 4] = 0
    mask = jnp.asarray(mask)
    params = model.init(jax.random.PRNGKey(0), tokens[:1], mask[:1])
    mesh = make_pipe_mesh(S)
    stacked, rest = stack_block_params_interleaved(params["params"], n_layers, S, v)
    return cfg, model, mesh, stacked, rest, tokens, mask


def _interleaved_1f1b_parity(n_layers, S, v, n_mb, B=16, freeze_split=0):
    cfg, model, mesh, stacked, rest, tokens, mask = _interleaved_setup(
        n_layers, S, v, B=B
    )
    fwd = make_gpipe_forward_stacked(
        model, cfg, mesh, n_microbatches=n_mb, n_virtual=v,
        freeze_split=freeze_split,
    )

    def loss_fn(stacked, rest):
        return causal_lm_ce_loss(fwd(stacked, rest, tokens, mask), tokens, mask)[0]

    l0, g0 = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(stacked, rest)

    parts = causal_ce_1f1b_parts(model)
    engine = make_1f1b_grad_fn(
        model, cfg, mesh, n_mb, parts["loss_mb"], ctx_fn=parts["ctx_fn"],
        n_virtual=v, freeze_split=freeze_split,
    )

    def run(stacked, rest):
        batch = {"input_ids": tokens, "attention_mask": mask}
        toks, m, loss_batch = parts["prepare"](batch)
        loss, stats, (ds, dr, dh) = engine(stacked, rest, {}, toks, m, loss_batch)
        return loss, (ds, dr)

    l1, (ds, dr) = jax.jit(run)(stacked, rest)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


@pytest.mark.parametrize("n_layers,S,v,n_mb,B", [
    (4, 2, 2, 2, 16),    # M == S
    (4, 2, 2, 8, 32),    # deep steady state
    (6, 2, 3, 4, 16),    # three chunks per device
    (8, 4, 2, 4, 32),    # four stages
    (8, 4, 2, 2, 16),    # M < ramp
])
def test_interleaved_1f1b_grad_parity(n_layers, S, v, n_mb, B):
    """r4: the 1F1B engine generalizes to interleaved virtual stages
    (chunk-stage schedule t_F = E(m)+k / t_B = E(m)+2Sv-2-k, ring-wrap
    fwd/bwd chains, per-chunk stash + grad accumulation): loss and full
    grad parity vs the interleaved-GPipe autodiff reference across chunk
    counts, microbatch counts, and the M < ramp edge."""
    _interleaved_1f1b_parity(n_layers, S, v, n_mb, B=B)


def test_interleaved_1f1b_grad_parity_freeze():
    """Layer freezing cuts at GLOBAL layer indices, which interleaving
    scatters round-robin across devices — the chunk layer_offset must map
    each chunk slot to its global layer for the stop_gradient cut."""
    _interleaved_1f1b_parity(4, 2, 2, 4, freeze_split=2)


def test_interleaved_1f1b_grad_parity_sequence_axis():
    """Interleave x SP x 1F1B: ring attention runs inside every chunk over
    the manual sequence axis, which forces the predicated always-compute
    slots (slot_conds off — collectives may not sit under the
    pipe-varying cond), exercising the v > 1 non-cond branches."""
    from trlx_tpu.parallel.pipeline import stack_block_params_interleaved

    n_layers, S, vv, n_mb, B, t = 4, 2, 2, 4, 16, 32
    cfg = TransformerConfig(
        vocab_size=97, d_model=32, n_layers=n_layers, n_heads=4, d_ff=64,
        max_seq_len=t, dtype=jnp.float32, attn_impl="ring",
    )
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, 97, size=(B, t)), jnp.int32)
    m = np.ones((B, t), np.int32)
    m[::3, -t // 4:] = 0  # right padding (SP CE requirement)
    m = jnp.asarray(m)
    params = model.init(jax.random.PRNGKey(0), tokens[:1], m[:1])
    mesh = make_pipe_mesh(S, sequence=2)
    stacked, rest = stack_block_params_interleaved(params["params"], n_layers, S, vv)
    fwd = make_gpipe_forward_stacked(model, cfg, mesh, n_microbatches=n_mb,
                                     n_virtual=vv)

    def loss_fn(stacked, rest):
        return causal_lm_ce_loss(fwd(stacked, rest, tokens, m), tokens, m)[0]

    l0, g0 = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(stacked, rest)

    parts = causal_ce_1f1b_parts(model)
    engine = make_1f1b_grad_fn(model, cfg, mesh, n_mb, parts["loss_mb"],
                               ctx_fn=parts["ctx_fn"], n_virtual=vv)

    def run(stacked, rest):
        batch = {"input_ids": tokens, "attention_mask": m}
        toks, mm, loss_batch = parts["prepare"](batch)
        loss, stats, (ds, dr, dh) = engine(stacked, rest, {}, toks, mm, loss_batch)
        return loss, (ds, dr)

    l1, (ds, dr) = jax.jit(run)(stacked, rest)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    _assert_tree_close(ds, g0[0])
    _assert_tree_close(dr, g0[1])


def test_memory_below_gpipe():
    """At the same workload the 1F1B program must need LESS temp memory
    than GPipe-autodiff: no [B, t, V] logits bank, no full-batch
    activation bank."""
    gpipe = _temp_bytes("gpipe", 8)
    onef1b = _temp_bytes("1f1b", 8)
    assert onef1b < gpipe, (onef1b, gpipe)
