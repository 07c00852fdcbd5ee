"""Context-parallel plumbing: shard_map wrappers for ring attention.

The reference has no context parallelism at all (SURVEY.md §2.7: CP/ring
attention row is "none"); this module is the scale-out path the TPU build
adds. `ring_attention` itself (trlx_tpu/ops/ring_attention.py) is written
against a named axis; this wrapper binds it to a concrete mesh so callers
holding global (or GSPMD-sharded) arrays can use it directly.
"""

import functools
from typing import Optional

import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.ops.ring_attention import ring_attention


def partial_shard_map(fn, mesh: Mesh, in_specs, out_specs, manual,
                      compute_dtype=None):
    """shard_map manual over `manual` axes only; every other mesh axis
    stays under GSPMD (auto) control, so rule-table param shardings
    (fsdp=ZeRO, tensor=TP) keep working INSIDE the manual program — XLA
    inserts the gather/all-reduce collectives. This is how sequence
    parallelism composes with TP/FSDP (reference: Megatron SP lives inside
    a TP group, modeling_nemo_ppo.py:160-164) and how the GPipe program
    composes with TP/FSDP (trlx_tpu/parallel/pipeline.py).

    When every non-manual axis has size 1 there is nothing to
    auto-partition and the plain full-manual shard_map is used — which
    also sidesteps an XLA:CPU crash compiling bf16 collectives under
    partially-manual meshes (observed on jax 0.9 / 8-device host
    platform; f32 and full-manual bf16 both compile). Consequence:
    TP/FSDP-composed programs on the CPU test mesh pin dtype=float32 —
    ENFORCED below: a bf16 call on a partially-manual CPU mesh raises a
    clear error instead of dying in a silent compiler SIGABRT. Real TPU
    is unaffected; bf16 compile-only coverage of the composed programs
    lives in tests/test_bf16_composed.py (jit(...).lower() exercises the
    full trace/lowering in bf16 without invoking the crashing backend
    compile)."""
    manual = set(manual) & set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if all(sizes[a] == 1 for a in mesh.axis_names if a not in manual):
        return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    smapped = shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=manual,
    )

    def guarded(*args):
        import os

        import jax

        # trace/lowering alone is safe (only the backend COMPILE aborts) —
        # bf16 lowering tests set this to exercise the composed programs
        if os.environ.get("TRLX_ALLOW_CPU_BF16_PARTIAL"):
            return smapped(*args)
        # the crash needs only bf16 VALUES crossing the partial-manual
        # collectives — params are often f32 (param_dtype) while the
        # computation runs bf16, so the caller passes its activation
        # dtype via `compute_dtype`
        if jax.default_backend() == "cpu" and (
            compute_dtype == jnp.bfloat16
            or any(
                getattr(x, "dtype", None) == jnp.bfloat16
                for x in jax.tree_util.tree_leaves(args)
            )
        ):
            raise NotImplementedError(
                "bf16 inputs to a PARTIALLY-manual shard_map on the CPU "
                "backend: XLA:CPU aborts compiling bf16 collectives under "
                "partial-manual meshes (silent SIGABRT). Pin float32 for "
                "CPU tests of TP/FSDP-composed pipeline/sequence programs "
                "(model_extra_configs.dtype='float32'); real TPU runs "
                "bf16 fine. See parallel/context.py partial_shard_map."
            )
        return smapped(*args)

    return guarded


def context_parallel_attention(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = True,
    block_k: int = 128,
) -> jnp.ndarray:
    """Exact attention with the sequence dim sharded over the mesh's
    "sequence" axis and batch over ("data", "fsdp"). Inputs are global
    [b, t, nh, hd] arrays (jit will reshard as needed); output has the
    same global shape/sharding."""
    qkv_spec = P(("data", "fsdp"), "sequence", None, None)
    mask_spec = P(("data", "fsdp"), "sequence")

    fn = shard_map(
        functools.partial(ring_attention, causal=causal, block_k=block_k),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    if mask is None:
        mask = jnp.ones(q.shape[:2], jnp.int32)
    return fn(q, k, v, mask)
