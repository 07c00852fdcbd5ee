"""Pipeline parallelism: GPipe microbatch schedule over a "pipe" mesh axis.

The reference's PP comes from the Apex pipeline engine — Python-driven
send/recv of tensor_shape-tagged activations between PP ranks with a
microbatch calculator and fwd/bwd schedule (SURVEY.md §2.6:
modeling_nemo_ppo.py:713-731, per-stage model construction :497-536, PP
checkpoint resharding :321-352). The TPU-native design needs none of that
machinery: transformer blocks are homogeneous, so per-stage "model
surgery" collapses to *stacking* block params [n_stages, layers_per_stage,
...] and sharding the leading dim over the "pipe" axis. One `shard_map`
program then runs the classic GPipe schedule:

    tick r ∈ [0, M + S - 1):
      stage 0 ingests microbatch r (clamped);
      every stage applies its layer stack to its current activation;
      `ppermute` hands activations (+ their padding masks) one hop down;
      the last stage banks finished microbatches.

Warmup/drain bubbles are predicated out with `where` instead of skipped —
the graph stays static and XLA overlaps the ppermute with the next tick's
compute. The backward pass is pure autodiff: transposing `ppermute`
reverses the ring, so the reverse-pipeline schedule falls out of
`jax.grad` with no hand-written 1F1B engine. Embedding/unembedding are
replicated compute on every stage (negligible next to the block stack;
removes the reference's first/last-stage embedding-sync all-reduce,
modeling_nemo_ppo.py:765-769).
"""

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.models.transformer import (
    Block,
    TransformerConfig,
    position_ids,
    train_bias,
)

PIPE_AXIS = "pipe"


def _varying(x, axis_name: str):
    """Mark a replicated value as device-varying over `axis_name` so it can
    seed a shard_map scan carry whose outputs vary (VMA types)."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def make_pipe_mesh(
    n_stages: int, devices=None, tensor: int = 1, fsdp: int = 1, sequence: int = 1
) -> Mesh:
    """("data", "pipe", "fsdp", "tensor", "sequence") mesh for pipelined
    trainers.

    "data", "pipe" and "sequence" are the MANUAL axes of the GPipe
    shard_map program; "fsdp"/"tensor" stay under GSPMD (auto) control so
    tensor parallelism and ZeRO param sharding compose with the pipeline
    without hand-written collectives — XLA inserts the Megatron-style
    all-reduces from the stacked params' PartitionSpecs (the reference
    instead nests Apex Column/RowParallelLinear modules inside its
    pipeline engine, modeling_nemo_ppo.py:93-121, 713-731). With
    sequence > 1 the pipeline stages run ring attention over the
    "sequence" axis — the PP x SP composition of the reference's 65B
    layout (megatron_65b.yaml:49-50 + sequence_parallel: True), except
    context length scales with chips instead of being capped by one TP
    group. "sequence" is innermost so the per-block K/V ring ppermutes
    ride the fastest ICI links."""
    devices = devices if devices is not None else jax.devices()
    if len(devices) % (n_stages * tensor * fsdp * sequence) != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_stages} stages x "
            f"fsdp={fsdp} x tensor={tensor} x sequence={sequence}"
        )
    # Any extra devices form a leading data axis for DP x PP hybrids. Use
    # mesh_utils placement so consecutive pipe stages land on neighboring
    # ICI links (the per-tick ppermute hop), mirroring make_mesh.
    sizes = (
        len(devices) // (n_stages * tensor * fsdp * sequence),
        n_stages, fsdp, tensor, sequence,
    )
    try:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(sizes, devices=devices)
    except Exception:  # CPU/host meshes without topology info
        arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, ("data", PIPE_AXIS, "fsdp", "tensor", "sequence"))


def partial_shard_map(fn, mesh: Mesh, in_specs, out_specs, compute_dtype=None):
    """GPipe's shard_map: manual over ("data", "pipe", "sequence");
    fsdp/tensor stay GSPMD-auto (see trlx_tpu/parallel/context.py
    partial_shard_map for the mechanism and the XLA:CPU bf16 caveat —
    `compute_dtype` feeds its bf16-on-CPU guard). "sequence" is
    intersected with the mesh's axes, so meshes without a sequence axis
    are unaffected."""
    from trlx_tpu.parallel.context import partial_shard_map as _psm

    return _psm(fn, mesh, in_specs, out_specs,
                manual={"data", PIPE_AXIS, "sequence"},
                compute_dtype=compute_dtype)


def stacked_param_shardings(mesh: Mesh, stacked, n_lead: int, rules=None):
    """NamedShardings for a stacked block pytree: dim 0 over "pipe", the
    other leading (virtual-stage / layers-per-stage) dims replicated, and
    the matrix dims per the TP/FSDP rule table — the stacked-layout
    analogue of infer_param_shardings. On a mesh without fsdp/tensor axes
    the trailing spec degrades to replicated."""
    from jax.sharding import NamedSharding

    from trlx_tpu.parallel.sharding import GPT_RULES, param_path

    rules = rules if rules is not None else GPT_RULES

    def _spec(keypath, leaf):
        shape = np.shape(leaf)
        trailing = rules.spec_for(param_path(keypath), shape[n_lead:], mesh)
        trailing = tuple(trailing) + (None,) * (len(shape) - n_lead - len(tuple(trailing)))
        return NamedSharding(mesh, P(PIPE_AXIS, *(None,) * (n_lead - 1), *trailing))

    return jax.tree_util.tree_map_with_path(_spec, stacked)


def unstack_block_params(stacked: Dict, rest: Dict, n_layers: int) -> Dict:
    """Inverse of stack_block_params: rebuild the standard per-block param
    layout (block_0..block_{n-1} + non-block entries)."""
    flat = jax.tree_util.tree_map(lambda x: x.reshape(n_layers, *x.shape[2:]), stacked)
    out = dict(rest)
    for i in range(n_layers):
        out[f"block_{i}"] = jax.tree_util.tree_map(lambda x: x[i], flat)
    return out


def stack_block_params(params: Dict, n_layers: int, n_stages: int) -> Tuple[Dict, Dict]:
    """Split a TransformerLM param tree into (stacked block params with
    leading [n_stages, layers_per_stage], non-block params). The inverse of
    the reference's per-stage model_provider_func — no surgery, just a
    pytree reshape."""
    if n_layers % n_stages != 0:
        raise ValueError(f"n_layers={n_layers} not divisible by n_stages={n_stages}")
    inner = params["params"] if "params" in params else params
    blocks = [inner[f"block_{i}"] for i in range(n_layers)]
    if len({jax.tree_util.tree_structure(b) for b in blocks}) > 1:
        raise NotImplementedError(
            "pipeline stages scan ONE block over stacked layers; a model whose layers differ "
            "(layer_types, leading dense layers before experts) cannot be stacked"
        )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    lps = n_layers // n_stages
    stacked = jax.tree_util.tree_map(
        lambda x: x.reshape(n_stages, lps, *x.shape[1:]), stacked
    )
    rest = {k: v for k, v in inner.items() if not k.startswith("block_")}
    return stacked, rest


def _apply_layer_stack(cfg: TransformerConfig, layer_params, h, bias, positions,
                       attn_mask, layer_offset=0, freeze_split: int = 0,
                       collect_aux: bool = False):
    """Sequentially apply this stage's layers via lax.scan over the stacked
    param dim (static per-layer graph, compiled once).

    `freeze_split` > 0 freezes the bottom `freeze_split` GLOBAL layers
    (reference freeze_bottom_causal_layers under PP,
    modeling_nemo_ppo.py:497-536): each frozen layer's output passes
    through `stop_gradient`, so no cotangent reaches its params or
    anything below it. `layer_offset` (static or traced — the stage/chunk
    index is an axis_index) maps the scan slot to the global layer.

    `collect_aux` additionally returns the sum of the layers' MoE
    load-balancing scalars (sown via flax intermediates, which cannot
    cross the enclosing shard_map on their own — the GSPMD trainers'
    mutable=["intermediates"] route stops at the manual-mesh boundary)."""
    block = Block(cfg)
    n_local = jax.tree_util.tree_leaves(layer_params)[0].shape[0]

    if collect_aux:
        from trlx_tpu.models.transformer import moe_aux_from_intermediates

        def fwd(lp, h):
            (h_out, _), inter = block.apply(
                {"params": lp}, h, bias, positions, attn_mask=attn_mask,
                mutable=["intermediates"],
            )
            return h_out, moe_aux_from_intermediates(inter).astype(jnp.float32)
    else:
        def fwd(lp, h):
            h_out, _ = block.apply({"params": lp}, h, bias, positions, attn_mask=attn_mask)
            return h_out, jnp.float32(0)

    if cfg.remat_blocks:
        # backward recomputes each layer's internals instead of banking
        # them across every pipeline tick — cfg.remat_blocks docstring.
        # prevent_cse=False: inside lax.scan the CSE-prevention barriers
        # are unnecessary (jax.checkpoint docs) and cost on the hot path.
        fwd = jax.checkpoint(fwd, prevent_cse=False)

    def body(carry, xs):
        h, aux = carry
        lp, i = xs
        h_out, layer_aux = fwd(lp, h)
        if freeze_split > 0:
            frozen = (layer_offset + i) < freeze_split
            # value-level select: d/dh is scaled by the 0/1 indicator, so
            # frozen layers contribute no param grads and cut the backward
            # below them; the update mask (pipelined_mixin) additionally
            # shields them from optimizer side effects like weight decay
            h_out = jnp.where(frozen, jax.lax.stop_gradient(h_out), h_out)
        return (h_out, aux + layer_aux), None

    # the aux carry must share h's varying-manual-axes type (VMA): a plain
    # scalar literal is unvarying and the scan carry type check rejects it
    aux0 = jnp.zeros_like(h[(0,) * h.ndim], dtype=jnp.float32)
    (h, aux), _ = jax.lax.scan(
        body, (h, aux0), (layer_params, jnp.arange(n_local))
    )
    return (h, aux) if collect_aux else h


def gpipe_blocks(
    cfg: TransformerConfig,
    stage_params,  # local [1, lps, ...] pytree (sharded over pipe axis)
    h: jnp.ndarray,  # [B, t, d] full batch (replicated across pipe axis)
    attn_mask: jnp.ndarray,  # [B, t]
    n_microbatches: int,
    positions: Optional[jnp.ndarray] = None,  # [B, t] GLOBAL position ids
    axis_name: str = PIPE_AXIS,
    freeze_split: int = 0,
    with_aux: bool = False,
) -> jnp.ndarray:
    """Run the block stack as a GPipe pipeline. Must be called inside
    shard_map with `axis_name` bound. Returns [B, t, d] (valid on every
    stage — the final activations are broadcast from the last stage);
    with `with_aux`, also the MoE load-balancing scalar summed over ALL
    stages' layers and averaged over microbatches (the microbatch mean
    matches the GSPMD trainers' one-forward-over-the-batch semantics up
    to routing statistics granularity).

    `positions` carries GLOBAL position ids computed before the shard_map
    (a local cumsum would restart at 0 on every sequence shard and is not
    left-padding-robust under SP); None falls back to the local cumsum,
    which is only correct when the sequence dim is unsharded."""
    S = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    my_layers = jax.tree_util.tree_map(lambda x: x[0], stage_params)

    B, t, d = h.shape
    M = n_microbatches
    assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
    mb = B // M
    if positions is None:
        positions = position_ids(attn_mask)
    h_mbs = h.reshape(M, mb, t, d)
    mask_mbs = attn_mask.reshape(M, mb, t)
    pos_mbs = positions.reshape(M, mb, t)

    lps = jax.tree_util.tree_leaves(my_layers)[0].shape[0]

    def stage(x, mask, pos):
        # shared bias policy with TransformerLM (None => fused kernel
        # builds causal+padding structure blockwise, no O(t^2) tensor)
        bias = train_bias(cfg, mask)
        return _apply_layer_stack(
            cfg, my_layers, x, bias, pos, mask,
            layer_offset=idx * lps, freeze_split=freeze_split,
            collect_aux=with_aux,
        )

    fwd_perm = [(s, s + 1) for s in range(S - 1)]  # no wraparound

    def tick(carry, r):
        recv_h, recv_mask, recv_pos, aux_acc = carry
        r_in = jnp.clip(r, 0, M - 1)
        mb_h = jax.lax.dynamic_index_in_dim(h_mbs, r_in, 0, keepdims=False)
        mb_mask = jax.lax.dynamic_index_in_dim(mask_mbs, r_in, 0, keepdims=False)
        mb_pos = jax.lax.dynamic_index_in_dim(pos_mbs, r_in, 0, keepdims=False)
        x = jnp.where(idx == 0, mb_h, recv_h)
        mask = jnp.where(idx == 0, mb_mask, recv_mask)
        pos = jnp.where(idx == 0, mb_pos, recv_pos)
        if with_aux:
            y, aux = stage(x, mask, pos)
            # only ticks doing REAL microbatch work contribute (stage idx
            # processes microbatch r - idx; ramp/drain slots run garbage)
            valid = (r >= idx) & (r < idx + M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        else:
            y = stage(x, mask, pos)

        next_h, next_mask, next_pos = jax.lax.ppermute(
            (y, mask, pos), axis_name, fwd_perm
        )
        # y rides the scan OUTPUT (ys), not the carry: a carry-borne bank
        # is saved by the scan's backward at EVERY tick — O(M^2)
        # activation residuals — while ys are written once, keeping the
        # bank O(M) (tests/test_pipeline_memory.py pins the bound)
        return (next_h, next_mask, next_pos, aux_acc), y

    init = jax.tree_util.tree_map(
        lambda x: _varying(x, axis_name),
        (jnp.zeros_like(h_mbs[0]), jnp.zeros_like(mask_mbs[0]),
         jnp.zeros_like(pos_mbs[0]),
         # zeros_like inherits h's varying-axes type; a scalar literal
         # would trip the scan carry VMA check once the stage aux (which
         # varies over data/pipe/sequence) accumulates into it
         jnp.zeros_like(h_mbs[0, 0, 0, 0], dtype=jnp.float32)),
    )
    (_, _, _, aux_acc), ys = jax.lax.scan(tick, init, jnp.arange(M + S - 1))

    # Microbatch m finishes on the LAST stage at tick m + S - 1; broadcast
    # those activations to all stages (mask-and-psum; one collective, lets
    # unembed/loss run replicated).
    out = ys[S - 1:]
    out = jax.lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)), axis_name)
    out = out.reshape(B, t, d)
    if with_aux:
        # total over stages (each stage summed only its own layers), mean
        # over microbatches
        aux_total = jax.lax.psum(aux_acc, axis_name) / M
        return out, aux_total
    return out


def stack_block_params_interleaved(
    params: Dict, n_layers: int, n_stages: int, n_virtual: int
) -> Tuple[Dict, Dict]:
    """Round-robin (virtual-stage) chunk layout: [n_stages, n_virtual, lps,
    ...] where device `idx` holds chunks `l*n_stages + idx` for loop l —
    the interleaved-1F1B placement of Megatron's virtual pipeline
    (reference: virtual-PP bucket config, modeling_nemo_ppo.py:573-585).
    With n_virtual == 1 this is exactly stack_block_params (the GPipe
    layout), so call sites need no dispatch."""
    if n_virtual == 1:
        return stack_block_params(params, n_layers, n_stages)
    if n_layers % (n_stages * n_virtual) != 0:
        raise ValueError(
            f"n_layers={n_layers} not divisible by pipeline={n_stages} x "
            f"pipeline_interleave={n_virtual}"
        )
    stacked, rest = stack_block_params(params, n_layers, n_stages * n_virtual)
    stacked = jax.tree_util.tree_map(
        lambda x: x.reshape(n_virtual, n_stages, *x.shape[1:]).swapaxes(0, 1),
        stacked,
    )
    return stacked, rest


def unstack_block_params_interleaved(
    stacked: Dict, rest: Dict, n_layers: int, n_virtual: int
) -> Dict:
    """Inverse of stack_block_params_interleaved; with n_virtual == 1 this
    is exactly unstack_block_params, so call sites need no dispatch."""
    if n_virtual == 1:
        return unstack_block_params(stacked, rest, n_layers)
    flat = jax.tree_util.tree_map(
        lambda x: x.swapaxes(0, 1).reshape(-1, *x.shape[2:]), stacked
    )
    return unstack_block_params(flat, rest, n_layers)


def interleaved_blocks(
    cfg: TransformerConfig,
    stage_params,  # local [1, v, lps, ...] pytree (sharded over pipe axis)
    h: jnp.ndarray,  # [B, t, d] full batch (replicated across pipe axis)
    attn_mask: jnp.ndarray,  # [B, t]
    n_microbatches: int,
    n_virtual: int,
    positions: Optional[jnp.ndarray] = None,  # [B, t] GLOBAL position ids
    axis_name: str = PIPE_AXIS,
    freeze_split: int = 0,
) -> jnp.ndarray:
    """Interleaved (virtual-stage) pipeline schedule: each device holds
    `n_virtual` layer chunks placed round-robin, and every microbatch loops
    the device ring `n_virtual` times. The pipeline bubble shrinks from
    (S-1)/M of GPipe to ~(S-1)/(M·v): the fill/drain ramp now costs
    thin chunks instead of a device's whole layer stack.

    Microbatch m enters stage 0 at tick `t_m = (m mod S) + (m div S)·S·v` —
    within a wave of S microbatches entries are back-to-back, and waves are
    spaced S·v apart so a device never hosts two microbatches on the same
    tick (m and m' collide iff t_m ≡ t_m' (mod S) with |t_m − t_m'| < S·v;
    the spacing rules both out). At tick r, device `idx` serves microbatch
    `m = base + w·S` on loop `l = q // S`, where `base = (r − idx) mod S`,
    `w = (r − base) div (S·v)`, `q = r − t_m`; chunk l covers global layers
    `(l·S + idx)·lps .. +lps`. The ring ppermute wraps around (S−1 → 0) so
    loop l's output on the last device feeds loop l+1 on the first; like
    the GPipe path, bubbles are predicated out with `where` and backward is
    pure autodiff through the transposed ppermute."""
    S = jax.lax.psum(1, axis_name)  # static: psum of a literal
    idx = jax.lax.axis_index(axis_name)
    v = n_virtual
    my_chunks = jax.tree_util.tree_map(lambda x: x[0], stage_params)  # [v, lps, ...]

    B, t, d = h.shape
    M = n_microbatches
    assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
    mb = B // M
    if positions is None:
        positions = position_ids(attn_mask)
    h_mbs = h.reshape(M, mb, t, d)
    mask_mbs = attn_mask.reshape(M, mb, t)
    pos_mbs = positions.reshape(M, mb, t)

    lps = jax.tree_util.tree_leaves(my_chunks)[0].shape[1]

    def stage(chunk_params, x, mask, pos, loop):
        bias = train_bias(cfg, mask)
        # chunk `loop` on device idx covers global layers starting at
        # (loop*S + idx) * lps (the round-robin placement)
        return _apply_layer_stack(
            cfg, chunk_params, x, bias, pos, mask,
            layer_offset=(loop * S + idx) * lps, freeze_split=freeze_split,
        )

    ring_perm = [(s, (s + 1) % S) for s in range(S)]
    span = S * v
    t_last = ((M - 1) % S) + ((M - 1) // S) * span
    n_ticks = t_last + span

    def tick(carry, r):
        recv_h, recv_mask, recv_pos = carry
        base = (r - idx) % S
        w = (r - base) // span
        q = r - base - w * span  # ticks since this mb entered stage 0
        m = base + w * S
        loop = q // S
        valid = (w >= 0) & (m < M)

        m_in = jnp.clip(m, 0, M - 1)
        mb_h = jax.lax.dynamic_index_in_dim(h_mbs, m_in, 0, keepdims=False)
        mb_mask = jax.lax.dynamic_index_in_dim(mask_mbs, m_in, 0, keepdims=False)
        mb_pos = jax.lax.dynamic_index_in_dim(pos_mbs, m_in, 0, keepdims=False)
        ingest = (idx == 0) & (loop == 0) & valid
        x = jnp.where(ingest, mb_h, recv_h)
        mask = jnp.where(ingest, mb_mask, recv_mask)
        pos = jnp.where(ingest, mb_pos, recv_pos)

        loop_in = jnp.clip(loop, 0, v - 1)
        chunk = jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(p, loop_in, 0, keepdims=False),
            my_chunks,
        )
        y = stage(chunk, x, mask, pos, loop_in)

        next_h, next_mask, next_pos = jax.lax.ppermute(
            (y, mask, pos), axis_name, ring_perm
        )
        # bank via scan OUTPUT, not carry (see gpipe_blocks)
        return (next_h, next_mask, next_pos), y

    init = jax.tree_util.tree_map(
        lambda x: _varying(x, axis_name),
        (jnp.zeros_like(h_mbs[0]), jnp.zeros_like(mask_mbs[0]),
         jnp.zeros_like(pos_mbs[0])),
    )
    _, ys = jax.lax.scan(tick, init, jnp.arange(n_ticks))

    # Microbatch m enters stage 0 at (m mod S) + (m div S)·S·v and the
    # last device finishes its loop v-1 exactly S·v - 1 ticks later.
    finish = np.asarray(
        [(m % S) + (m // S) * span + span - 1 for m in range(M)], np.int32
    )
    out = jnp.take(ys, jnp.asarray(finish), axis=0)
    out = jax.lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)), axis_name)
    return out.reshape(B, t, d)


def make_gpipe_forward_stacked(
    model,  # TransformerLM (or a module exposing embed/unembed + blocks)
    cfg: TransformerConfig,
    mesh: Mesh,
    n_microbatches: int,
    with_hidden: bool = False,
    n_virtual: int = 1,
    freeze_split: int = 0,
    with_aux: bool = False,
) -> Callable:
    """Build fn(stacked, rest, tokens, attn_mask) -> logits (or
    (logits, h_final) with with_hidden) where `stacked` is the
    [n_stages, lps, ...] block pytree living sharded over the "pipe" axis
    — the layout the pipelined trainer keeps params in permanently, so no
    per-call restacking. With n_virtual > 1 `stacked` is the interleaved
    [n_stages, n_virtual, lps, ...] layout and the interleaved schedule
    runs instead of GPipe. `with_aux` (GPipe only) appends the MoE
    load-balancing scalar to the outputs — the in-pipe route for the aux
    loss the GSPMD trainers read from flax intermediates (which cannot
    cross the shard_map)."""
    if with_aux and n_virtual > 1:
        raise NotImplementedError(
            "MoE aux collection is not wired through the interleaved "
            "schedule (chunk ticks would need per-chunk validity gating); "
            "use pipeline_interleave=1 with MoE"
        )

    def embed(rest_params, tokens, positions):
        return model.apply({"params": {**rest_params}}, tokens, positions, method=model.embed)

    def unembed(rest_params, h):
        return model.apply({"params": {**rest_params}}, h, method=model.unembed)

    def inner(stacked, rest, tokens, attn_mask, positions):
        h = embed(rest, tokens, positions)
        aux = None
        if n_virtual > 1:
            h = interleaved_blocks(cfg, stacked, h, attn_mask, n_microbatches,
                                   n_virtual, positions=positions,
                                   freeze_split=freeze_split)
        else:
            h = gpipe_blocks(cfg, stacked, h, attn_mask, n_microbatches,
                             positions=positions, freeze_split=freeze_split,
                             with_aux=with_aux)
            if with_aux:
                h, aux = h
        logits, h_final = unembed(rest, h)
        out = (logits, h_final) if with_hidden else (logits,)
        if with_aux:
            # mean over the manual batch axes so the scalar is genuinely
            # replicated (its out_spec is P()): each data slice (and, under
            # PP x SP, each sequence shard) ran its own microbatches, so
            # this is the full-batch average — the same reduction the data
            # axis applies to the CE loss via the grad psum
            batch_axes = tuple(
                ax for ax in ("data", "sequence") if ax in mesh.axis_names
            )
            for ax in batch_axes:
                aux = jax.lax.pmean(aux, ax)
            out = out + (aux,)
        return out[0] if len(out) == 1 else out

    # Batch sharded over the mesh's "data" axis (DP x PP hybrid: each
    # data slice runs its own pipeline over the shared stage params);
    # shard_map's transpose inserts the data-axis grad psum for the
    # replicated params automatically. fsdp/tensor axes (if the mesh has
    # them) stay auto: GSPMD shards the per-stage matmuls from the stacked
    # params' PartitionSpecs and inserts the TP collectives. With a real
    # "sequence" axis (PP x SP) the t dim shards too, and ring attention
    # inside each stage binds the axis; position ids are computed on the
    # GLOBAL mask before the shard_map (a shard-local cumsum would restart
    # at 0 per shard and break left-padded batches).
    has_seq = "sequence" in mesh.axis_names
    b_spec = P("data", "sequence") if has_seq else P("data")
    out_spec = (b_spec, b_spec) if with_hidden else b_spec
    if with_aux:
        # the aux scalar is psum'd over pipe inside and identical across
        # data slices only after their mean — keep it per-data-slice
        # varying? No: P() replicates; shard_map will average-check.
        aux_spec = P()
        out_spec = (out_spec if isinstance(out_spec, tuple) else (out_spec,)) + (aux_spec,)
    smap = partial_shard_map(
        inner,
        mesh,
        in_specs=(P(PIPE_AXIS), P(), b_spec, b_spec, b_spec),
        out_specs=out_spec,
        compute_dtype=cfg.dtype,
    )

    def fwd(stacked, rest, tokens, attn_mask):
        return smap(stacked, rest, tokens, attn_mask, position_ids(attn_mask))

    return fwd


def make_gpipe_forward(
    model,  # TransformerLM (or a module exposing embed/unembed + blocks)
    cfg: TransformerConfig,
    mesh: Mesh,
    n_stages: int,
    n_microbatches: int,
    n_virtual: int = 1,
) -> Callable:
    """Build fn(params, tokens, attn_mask) -> logits running the block
    stack as a GPipe (or, with n_virtual > 1, interleaved virtual-stage)
    pipeline over `mesh`'s "pipe" axis. Params are taken in standard
    (unstacked) TransformerLM layout; stacking happens inside the jitted
    fn so the same checkpoint format serves every layout (the reference
    instead reshards checkpoints per PP stage,
    modeling_nemo_ppo.py:321-352)."""
    stacked_fwd = make_gpipe_forward_stacked(
        model, cfg, mesh, n_microbatches, n_virtual=n_virtual
    )

    def fwd(params, tokens, attn_mask):
        stacked, rest = stack_block_params_interleaved(
            params, cfg.n_layers, n_stages, n_virtual
        )
        return stacked_fwd(stacked, rest, tokens, attn_mask)

    return fwd
