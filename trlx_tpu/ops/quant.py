"""Int8 weight-only quantization for the frozen-trunk DECODE path.

Generation is bandwidth-bound (docs/trainers.md, the decode levers):
every decode step streams the full bf16 param set to emit one token per
row. Under the hydra split most of those bytes never see a gradient —
blocks [0, split), the (tied) token embedding, and the learned position
table are frozen for the whole run — so they can be held as int8 with a
per-channel f32 scale and dequantized on the fly inside the jitted decode
step (w8a16: int8 weights, bf16 activations; XLA fuses the convert+mul
into the dot's operand read, the AQT/maxtext serving pattern). Train and
score paths never see the quantized view; `method.quantize_frozen_trunk`
swaps it in for generation only.

A quantized leaf is a dict node `{"q": int8, "scale": f32}` replacing the
original array in the param pytree — jit treats it as two leaves, and
`dequantize_tree` maps it back to a dense array right inside the compiled
decode fn, so every model code path downstream is unchanged.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp

QUANT_KEYS = frozenset(("q", "scale"))


def is_quant_leaf(node: Any) -> bool:
    """True for the {"q", "scale"} dict nodes `quantize_array` produces."""
    return isinstance(node, dict) and set(node.keys()) == set(QUANT_KEYS)


def quantize_array(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Symmetric per-channel int8 quantization, channels along the LAST
    axis (kernels [in, out] -> per-output-channel; embeddings [V, d] ->
    per-feature, which serves both the gather use w[tok]*scale and the
    tied unembed use (h*scale)@q.T)."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(range(w32.ndim - 1)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequantize_array(node: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """q * scale back to f32 (flax modules cast to cfg.dtype at use, same
    as the original param_dtype=f32 leaves)."""
    return node["q"].astype(jnp.float32) * node["scale"]


def dequantize_tree(params: Any) -> Any:
    """Replace every quantized node in a param pytree with its dense
    reconstruction. Call INSIDE jit so XLA fuses the int8->f32 convert and
    scale multiply into the consuming matmul's operand read instead of
    materializing a dense copy in HBM."""
    return jax.tree_util.tree_map(
        lambda n: dequantize_array(n) if is_quant_leaf(n) else n,
        params,
        is_leaf=is_quant_leaf,
    )


def has_quantized_leaves(params: Any) -> bool:
    found = []
    jax.tree_util.tree_map(
        lambda n: found.append(True) if is_quant_leaf(n) else None,
        params,
        is_leaf=is_quant_leaf,
    )
    return bool(found)


def quantize_decode_params(params: Dict, split: int) -> Dict:
    """Build the decode-params view: the model param tree with every
    never-trained weight matrix swapped for its int8 form — blocks
    [0, split), `embed_tokens`, and `embed_pos` (frozen whenever
    split > 0, i.e. num_layers_unfrozen freezes the bottom of the stack
    plus embeddings; an untied lm_head is trainable and stays dense, as do
    ln/bias vectors, whose bytes are negligible). Everything else is
    ALIASED, not copied, so the view costs only the int8 buffers."""
    if split <= 0:
        raise ValueError("quantize_decode_params requires a hydra split > 0")

    frozen_blocks = {f"block_{i}" for i in range(split)}

    def _walk(path, node):
        if isinstance(node, dict):
            return {k: _walk(path + (k,), v) for k, v in node.items()}
        parts = [str(p) for p in path]
        in_frozen = (
            len(parts) >= 2
            and parts[0] == "lm"
            and (parts[1] in frozen_blocks or parts[1] in ("embed_tokens", "embed_pos"))
        )
        if in_frozen and hasattr(node, "ndim") and node.ndim >= 2 and jnp.issubdtype(
            jnp.asarray(node).dtype, jnp.floating
        ):
            return quantize_array(node)
        return node

    return _walk((), params)


def quantize_frozen_flat(frozen_flat: Dict, split: int) -> Dict:
    """Flat-dict (tuple-key) variant of `quantize_decode_params` for the
    trainer's partitioned param layout: quantize the decode-targeted
    frozen leaves ONCE, then rebuild the decode view every dispatch as
    merge_params(train_params, quantized_frozen) — the int8 buffers never
    go stale (the leaves they replace never see a gradient) while the
    trainable leaves stay live. Keys not under the frozen trunk are
    aliased untouched."""
    if split <= 0:
        raise ValueError("quantize_frozen_flat requires a hydra split > 0")
    frozen_blocks = {f"block_{i}" for i in range(split)}
    out = {}
    for key, leaf in frozen_flat.items():
        parts = [str(p) for p in key]
        in_frozen = (
            len(parts) >= 2
            and parts[0] == "lm"
            and (parts[1] in frozen_blocks or parts[1] in ("embed_tokens", "embed_pos"))
        )
        if in_frozen and hasattr(leaf, "ndim") and leaf.ndim >= 2 and jnp.issubdtype(
            jnp.asarray(leaf).dtype, jnp.floating
        ):
            out[key] = quantize_array(leaf)
        else:
            out[key] = leaf
    return out


def quantize_kv(x: jnp.ndarray):
    """Symmetric per-token-per-head int8 for KV-cache blocks: the scale
    axis is the HEAD dim (last), so each written token keeps its own f32
    scale per kv head — the finest granularity the paged arena can store
    without widening the block table. Returns (q int8 [..., hd],
    scale f32 [...])."""
    x32 = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of `quantize_kv`, applied in-kernel on the attention read
    (XLA fuses the convert+mul into the gather's consumer)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantized_bytes(params: Any) -> int:
    """HBM bytes of the decode view (int8 q + f32 scales + dense rest):
    what a decode step streams, for roofline accounting."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
