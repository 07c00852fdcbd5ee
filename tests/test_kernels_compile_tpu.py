"""Every Pallas entry point AOT-compiles for the TPU v5e, with no chip.

The installed libtpu compiles for a described topology
(`jax.experimental.topologies`), so Mosaic's verdict on a kernel costs
seconds of CPU here instead of minutes of chip: a kernel that only runs
under `interpret=True` cannot land again. Shapes are GPT-2 small's (12
heads x 64, vocab 50,257) at the benchmark's and the PPO cycle's sizes.
Compiling says the kernel is accepted, not that it is right: parity on the
chip is `chip_smoke.py`'s job.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from trlx_tpu.ops import attention, fused_ce  # noqa: E402
from trlx_tpu.ops.paged_attention import paged_attention_decode  # noqa: E402

S = jax.ShapeDtypeStruct
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices


def compile_for(fn, args, shardings, out_shardings=None):
    """Lower `fn` for the TPU and compile it for the shardings' devices."""
    jitted = jax.jit(fn, in_shardings=shardings, out_shardings=out_shardings)
    return jitted.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def kernel_names(compiled) -> list:
    """The names of the Mosaic custom calls of a compiled program: the
    `name=` of each `pl.pallas_call`, which is what the profiler's trace
    calls the kernel's events (`%flash_fwd.1 = ... custom-call(...)`)."""
    return [m.group(1) for m in re.finditer(
        r'%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())]


def _flash_args(shape):
    b, t, nh, hd = shape
    qkv = S(shape, BF16)
    return qkv, S((b, t), I32), S((b, nh, t), F32)


def _named_kernels():
    qkv, mask, lse = _flash_args((2, 256, 4, 64))
    nkv, blk, hd = 2, 16, 64
    arena = S((9, nkv, blk, hd), BF16)
    return {
        "flash_fwd": (lambda q, k, v, m: attention._flash_fwd_pallas(q, k, v, m, True, None, None),
                      (qkv, qkv, qkv, mask), ["flash_fwd"]),
        "flash_fwd_lse": (
            lambda q, k, v, m: attention._flash_fwd_pallas_lse(q, k, v, m, True, None, None),
            (qkv, qkv, qkv, mask), ["flash_fwd_lse"]),
        "flash_bwd": (lambda q, k, v, m, o, l, g: attention._flash_bwd_pallas(
            q, k, v, m, o, l, g, True, None, None),
            (qkv, qkv, qkv, mask, qkv, lse, qkv), ["flash_bwd_dq", "flash_bwd_dkv"]),
        "fused_ce_fwd": (fused_ce._logprobs_pallas, (S((256, 50257), BF16), S((256,), I32)),
                         ["fused_ce_fwd"]),
        "paged_decode": (paged_attention_decode,
                         (S((4, 4, hd), BF16), arena, arena, S((4, 3), I32), S((4, 3 * blk), I32)),
                         ["paged_decode"]),
    }


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_fwd_lse", "flash_bwd", "fused_ce_fwd",
                                    "paged_decode"])
def test_every_kernel_carries_its_name(v5e, kernel):
    """A trace tells the kernels apart by these names (bench/metrics, PERF.md
    section 3): the flash forward from the fused CE, dq from dk/dv."""
    fn, args, names = _named_kernels()[kernel]
    compiled = compile_for(fn, args, SingleDeviceSharding(v5e[0]))
    assert sorted(kernel_names(compiled)) == sorted(names)


# bench parity shape, and one PPO minibatch (32 rows of 64 + 40 tokens)
FLASH_SHAPES = [(4, 1024, 12, 64), (32, 104, 12, 64)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_forward_lse_and_backward_compile(v5e, shape):
    b, t, nh, hd = shape
    one = SingleDeviceSharding(v5e[0])
    qkv = S(shape, BF16)
    mask = S((b, t), I32)
    fwd = compile_for(
        lambda q, k, v, m: attention._flash_fwd_pallas(q, k, v, m, True, None, None),
        (qkv, qkv, qkv, mask), one)
    assert mosaic_calls(fwd) == 1
    lse = compile_for(
        lambda q, k, v, m: attention._flash_fwd_pallas_lse(q, k, v, m, True, None, None),
        (qkv, qkv, qkv, mask), one)
    assert mosaic_calls(lse) == 1
    bwd = compile_for(
        lambda q, k, v, m, o, l, g: attention._flash_bwd_pallas(
            q, k, v, m, o, l, g, True, None, None),
        (qkv, qkv, qkv, mask, qkv, S((b, nh, t), F32), qkv), one)
    assert mosaic_calls(bwd) == 2  # dq; dk/dv


@pytest.mark.parametrize("rows", [2048, 1280])
def test_fused_ce_compiles(v5e, rows):
    one = SingleDeviceSharding(v5e[0])
    compiled = compile_for(
        fused_ce._logprobs_pallas, (S((rows, 50257), BF16), S((rows,), I32)), one)
    assert mosaic_calls(compiled) == 1


# (n_heads, n_kv_heads, head_dim): GPT-2 small (group 1), a 7B-class GQA
# shape (group 4), and llama-tiny, the repo's own group-2 preset
PAGED_SHAPES = [(12, 12, 64), (32, 8, 128), (4, 2, 16)]


@pytest.mark.parametrize("dtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("blk", [16, 32])
@pytest.mark.parametrize("heads", PAGED_SHAPES, ids=lambda h: "x".join(map(str, h)))
def test_paged_decode_compiles_without_copying_the_arena(v5e, heads, blk, dtype):
    nh, nkv, hd = heads
    b, n_tbl, n_blocks = 8, 6, 49
    one = SingleDeviceSharding(v5e[0])
    arena = S((n_blocks, nkv, blk, hd), dtype)
    args = [S((b, nh, hd), BF16), arena, arena, S((b, n_tbl), I32),
            S((b, n_tbl * blk), I32)]
    fn = paged_attention_decode
    if dtype == I8:
        plane = S((n_blocks, 1, nkv * blk), F32)
        args += [plane, plane]
        fn = lambda q, k, v, t, m, ks, vs: paged_attention_decode(  # noqa: E731
            q, k, v, t, m, k_scale=ks, v_scale=vs)
    compiled = compile_for(fn, args, one)
    assert mosaic_calls(compiled) == 1
    if hd >= 64:
        # The kernel reads the arena and its scale planes where they lie: a
        # layout the TPU does not keep row-major would show up here as a
        # whole-operand copy in front of the custom call, every step.
        text = compiled.as_text()
        for operand in (arena, *args[5:]):
            dims = ",".join(map(str, operand.shape))
            assert not re.search(rf"= \w+\[{dims}\]\S* copy\(", text), (
                f"per-call copy of a [{dims}] arena operand")


LAYOUTS = [(4, 1, 1), (2, 1, 2), (1, 2, 2)]  # (data, fsdp, tensor)


def _mesh(devices, layout):
    data, fsdp, tensor = layout
    return Mesh(np.asarray(devices).reshape(data, fsdp, tensor, 1),
                ("data", "fsdp", "tensor", "sequence"))


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: "x".join(map(str, l)))
def test_sharded_flash_wrapper_compiles(v5e, layout):
    mesh = _mesh(v5e, layout)
    b, t, nh, hd = 32, 104, 12, 64
    qkv = S((b, t, nh, hd), BF16)
    assert attention._sharded_flash_ok(mesh, qkv, qkv)
    spec = NamedSharding(mesh, P(("data", "fsdp"), None, "tensor", None))
    rows = NamedSharding(mesh, P(("data", "fsdp"), None))
    compiled = compile_for(
        functools.partial(attention.flash_attention_sharded, mesh),
        (qkv, qkv, qkv, S((b, t), I32)), (spec, spec, spec, rows), spec)
    assert mosaic_calls(compiled) == 1


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: "x".join(map(str, l)))
def test_sharded_fused_ce_wrapper_compiles(v5e, layout):
    mesh = _mesh(v5e, layout)
    # GPT-2's 50,257 is odd: under tensor=2 the vocab must be padded to
    # shard at all (_sharded_ce_ok), so the tensor layouts use 50,304
    n, vocab = 1280, 50257 if layout[2] == 1 else 50304
    assert fused_ce._sharded_ce_ok(mesh, n, vocab)
    logits = NamedSharding(mesh, P(("data", "fsdp"), "tensor"))
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    compiled = compile_for(
        functools.partial(fused_ce.fused_logprobs_sharded, mesh),
        (S((n, vocab), BF16), S((n,), I32)), (logits, rows), (rows, rows))
    assert mosaic_calls(compiled) == 1
