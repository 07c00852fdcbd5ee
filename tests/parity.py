"""What the parity tests share: a model's init, forward or loss as ONE compiled
program, and one trainer's loss against the plain trainer's on the same
parameters and batch. Called op by op, a model compiles a program an
operation: hundreds a call, most of a test's seconds (ROADMAP D10)."""

import functools

import numpy as np

import jax
from flax.traverse_util import flatten_dict

from trlx_tpu.models.transformer import TransformerLM


@functools.lru_cache(maxsize=None)
def jitted_init(model):
    """`model.init` under `jax.jit`, one program a module and shape: a test's
    seeds and parametrized cases that build the same module share it."""
    return jax.jit(model.init)


@functools.lru_cache(maxsize=None)
def jitted_forward(cfg):
    """(params, tokens, mask) -> logits of `TransformerLM(cfg)`, one compiled
    program a configuration and shape."""
    return jax.jit(lambda params, tokens, mask: TransformerLM(cfg).apply({"params": params}, tokens, mask)[0])


def assert_loss_parity(loss_fn, args, plain_loss_fn, plain_args, rtol=1e-4):
    """`loss_fn(*args)` and `plain_loss_fn(*plain_args)` give the same loss.

    Both run under `jax.jit`, as the trainers run them. The plain side's
    arguments come to the host first, so its program is placed on the plain
    trainer's one device whatever mesh the arrays were made on."""
    loss, _ = jax.jit(loss_fn)(*args)
    plain_loss, _ = jax.jit(plain_loss_fn)(*jax.device_get(plain_args))
    np.testing.assert_allclose(
        float(jax.device_get(loss)), float(jax.device_get(plain_loss)), rtol=rtol
    )


def assert_pipelined_loss_parity(trainer, plain, batch, rtol=1e-4):
    """A pipelined trainer's loss over its stacked parameters against the
    plain trainer's over the same parameters in the standard layout, every
    leaf trainable on both sides."""
    assert_loss_parity(
        trainer.make_loss_fn(),
        (flatten_dict(dict(trainer.params)), {}, trainer.batch_to_device(batch)),
        plain.make_loss_fn(), (flatten_dict(trainer.standard_params()), {}, batch), rtol,
    )
