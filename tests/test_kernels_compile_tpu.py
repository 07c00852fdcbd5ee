"""Every Pallas entry point AOT-compiles for the TPU v5e, with no chip.

Mosaic's verdict on a kernel costs seconds of CPU here instead of minutes
of chip (`aot_tpu.py`): a kernel that only runs under `interpret=True`
cannot land again. Shapes are GPT-2 small's (12 heads x 64, vocab 50,257)
at the benchmark's and the PPO cycle's sizes, and the serve cells' own.
Compiling says the kernel is accepted, not that it is right: parity on the
chip is `chip_smoke.py`'s job. The cells' whole programs are compiled in
`test_serve_cells_compile_tpu.py`, `test_state_cells_compile_tpu.py`,
`test_lfm2_compile_tpu.py` and `test_ppo_cells_compile_tpu.py`.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    BF16, CELL, CODE_CELL, F32, I8, I32, LONGCTX_CELL, S, abstract, arena_rewrites, compile_for,
    donated_outputs, kernel_names, mosaic_calls, pallas_mode, v5e,
)
from trlx_tpu.ops import attention, fused_ce, paged_attention  # noqa: E402
from trlx_tpu.ops.paged_attention import (  # noqa: E402
    init_paged_layer,
    paged_attention_decode,
    paged_kv_gather,
    paged_kv_write,
)


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
# shape (group 4), llama-tiny, the repo's own group-2 preset, and the
# benchmark's two configurations, pythia-1.4b and gpt2-xl
PAGED_SHAPES = [(12, 12, 64), (32, 8, 128), (4, 2, 16), (16, 16, 128), (25, 25, 64)]


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
        # whole-operand copy in front of the custom call, every step. (The
        # kernel alone: the write in front of it is held below.)
        assert arena_rewrites(compiled, arena, *args[5:]) == []


# the cell's own call, the open chat cell's, 44 table entries a slot at
# `max_prompt_len` 1024 (PERF.md section 7), and the transcript cell's: 32
# rows of 28 query heads over 4 K/V heads, 480 table entries, full and banded
TRANSCRIPT = dict(n_blocks=10240, nkv=4, group=7, slots=32, n_tbl=480)
FETCH_SHAPES = {
    "cell": (dict(CELL, group=1), None, 8),
    "chat": (dict(CELL, group=1, n_tbl=44), None, 8),
    "transcript": (TRANSCRIPT, None, 2),
    "transcript-window": (TRANSCRIPT, 4096, 2),
}


@pytest.mark.parametrize("shape", sorted(FETCH_SHAPES))
def test_paged_decode_fits_its_vmem_budget_at_the_cells_shapes(v5e, shape, monkeypatch):
    """Blocks of 32 x 128 bfloat16 a K/V head: one Mosaic call named
    `paged_decode` (`paged_decode_window` with a band), the arenas read where
    they lie (they enter in `pl.ANY` and the kernel copies a tile's blocks
    itself: no copy of an arena in front of the call), tiles of 16 entries,
    and VMEM inside the budget the module docstring states. The call carries
    `vmem_limit_bytes` and Mosaic refuses a kernel that needs more: the
    scratch (2 buffers x 2 sides x 16 blocks: 8 MiB at 16 K/V heads, 2 MiB
    at 4) and the rest fit the 12 MiB it is compiled under, and a limit the
    scratch alone does not fit is refused, which shows the limit is enforced
    and not merely stated."""
    c, window, scratch_mib = FETCH_SHAPES[shape]
    n_blocks, nkv, n_tbl, b, blk, hd = *(c[k] for k in ("n_blocks", "nkv", "n_tbl", "slots")), 32, 128
    one = SingleDeviceSharding(v5e[0])
    arena, row = S((n_blocks, nkv, blk, hd), BF16), S((b, nkv, hd), BF16)
    # the call as a decode step makes it: with the step's K and V rows and their column, which it writes
    args = (S((b, nkv * c["group"], hd), BF16), arena, arena, S((b, n_tbl), I32), S((b, n_tbl * blk), I32),
            row, row, S((b,), I32))
    assert paged_attention.writes_in_kernel(arena)
    assert paged_attention._tile_entries(n_tbl, nkv, blk, hd, BF16) == 16
    assert paged_attention._VMEM_LIMIT_BYTES == 12 * 2 ** 20
    assert 4 * 16 * paged_attention._vmem_block_bytes(nkv, blk, hd, BF16) == scratch_mib * 2 ** 20

    def compile_under(limit):
        monkeypatch.setattr(paged_attention, "_VMEM_LIMIT_BYTES", limit)
        paged_attention._paged_decode_call.clear_cache()  # or the call's own jit hands back the last limit's trace
        # a fresh function object: jit's trace cache would hand back the last limit's program
        return jax.jit(lambda *a: paged_attention_decode(*a[:5], window=window, new_kv=a[5:7], column=a[7]),
                       donate_argnums=(1, 2)).trace(*abstract(args, one)).lower(lowering_platforms=("tpu",)).compile()

    compiled = compile_under(12 * 2 ** 20)
    assert kernel_names(compiled) == ["paged_decode" if window is None else "paged_decode_window"]
    assert arena_rewrites(compiled, arena) == []
    with pytest.raises(Exception, match="vmem"):
        compile_under(scratch_mib * 2 ** 20)
    paged_attention._paged_decode_call.clear_cache()  # nobody else gets the small limit's trace


@pytest.mark.parametrize("dtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_paged_write_and_read_leave_the_arena_where_it_lies(v5e, form, dtype):
    """The write and the read in ONE program, arenas donated, as a layer of
    the engine's decode step (64 rows x 1 position, the kernel: since PR 58
    a bfloat16 arena's write is the kernel's own, an int8 arena keeps
    `paged_kv_write` in front of it) and of a prefill (1 row x 256
    positions, the gather) run them: the step's keys and values land in the
    donated arena in the layout the kernel and the gather read, with no copy
    of an arena or a scale plane on the way, in ONE Mosaic call a layer.
    `arena.at[phys, :, off].set(k)` put four arena copies a layer there:
    49 ms of a 120 ms decode step on the chip (PERF.md, PR 28)."""
    n_blocks, nkv, blk, hd = (CELL[k] for k in ("n_blocks", "nkv", "blk", "hd"))
    b, t = (CELL["slots"], 1) if form == "decode" else (1, 256)
    one = SingleDeviceSharding(v5e[0])

    in_kernel = form == "decode" and dtype == BF16  # as `Attention` decides: `writes_in_kernel`

    def layer_step(layer, q, k, v, table, start, valid, key_mask):
        if in_kernel:
            assert paged_attention.writes_in_kernel(layer["k"])
            out, *arenas = paged_attention_decode(
                q[:, 0], layer["k"], layer["v"], table, key_mask, new_kv=(k[:, 0], v[:, 0]), column=start)
            return dict(zip("kv", arenas)), out
        new = paged_kv_write(layer, k, v, table, start, valid)
        if form == "decode":
            assert not paged_attention.writes_in_kernel(layer["k"])
            out = paged_attention_decode(
                q[:, 0], new["k"], new["v"], table, key_mask,
                k_scale=new.get("k_scale"), v_scale=new.get("v_scale"))
        else:
            keys, values = paged_kv_gather(new, table, BF16)
            bias = jnp.where(key_mask.astype(bool), 0.0, -1e9)[:, None, None, :]
            probs = jax.nn.softmax(
                jnp.einsum("bthd,bshd->bhts", q, keys).astype(F32) + bias, axis=-1)
            out = jnp.einsum("bhts,bshd->bthd", probs.astype(BF16), values)
        return new, out

    layer = jax.eval_shape(lambda: init_paged_layer(n_blocks, blk, nkv, hd, dtype))
    kv = S((b, t, nkv, hd), BF16)
    args = (layer, kv, kv, kv, S((b, CELL["n_tbl"]), I32), S((b,), I32), S((b, t), I32),
            S((b, CELL["n_tbl"] * blk), I32))
    compiled = jax.jit(layer_step, donate_argnums=(0,)).trace(
        *abstract(args, one)).lower(lowering_platforms=("tpu",)).compile()
    assert mosaic_calls(compiled) == (1 if form == "decode" else 0)
    assert arena_rewrites(compiled, *layer.values()) == []
    assert donated_outputs(compiled) == len(layer)


def test_the_kernel_s_write_under_a_scan_over_passes_copies_no_arena(v5e):
    """The looped stack's form (`TransformerLM.run_passes`; ouro-2.6b's cut: 8
    rows, 16 K/V heads, 4 pools of 160 blocks laid end to end): the arenas are
    the carry of `jax.lax.scan` over the passes, pass t reads and writes through
    `pass_table`, and the compiled program holds one Mosaic call in the loop's
    body and no copy of an arena: nothing but the arenas themselves is as large."""
    passes, pool, nkv, blk, hd, b, n_tbl = 4, 160, 16, 32, 128, 8, 19
    one = SingleDeviceSharding(v5e[0])
    arena = S((passes * pool, nkv, blk, hd), BF16)

    def step(layer, q, k, v, table, start, key_mask):
        def one_pass(carry, t):
            layer, q = carry
            out, *arenas = paged_attention_decode(
                q, layer["k"], layer["v"], paged_attention.pass_table(table, t, passes, passes * pool), key_mask,
                new_kv=(k, v), column=start)
            return (dict(zip("kv", arenas)), out), None
        return jax.lax.scan(one_pass, (layer, q), jnp.arange(passes))[0]

    row = S((b, nkv, hd), BF16)
    args = ({"k": arena, "v": arena}, row, row, row, S((b, n_tbl), I32), S((b,), I32), S((b, n_tbl * blk), I32))
    compiled = jax.jit(step, donate_argnums=(0,)).trace(
        *abstract(args, one)).lower(lowering_platforms=("tpu",)).compile()
    assert mosaic_calls(compiled) == 1 and kernel_names(compiled) == ["paged_decode"]
    assert arena_rewrites(compiled, arena) == []
    assert donated_outputs(compiled) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20  # no second arena anywhere (one is 168 MB)


@pytest.mark.parametrize("dtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads,window", [(48, None), (64, 512), (48, 512)], ids=["full48", "window64", "window48"])
def test_paged_decode_with_a_window_and_groups_of_six_compiles_at_the_code_cell_shape(v5e, heads, window, dtype):
    """One kernel, two group sizes, with and without a window, bfloat16 and
    int8 arenas: Mosaic accepts each, the windowed call carries its own name
    (the device trace splits the layer kinds by it), and the arena and its
    scale planes are read where they lie."""
    n_blocks, nkv, blk, hd, b, n_tbl = (CODE_CELL[k] for k in ("n_blocks", "nkv", "blk", "hd", "slots", "n_tbl"))
    one = SingleDeviceSharding(v5e[0])
    arena = S((n_blocks, nkv, blk, hd), dtype)
    args = [S((b, heads, hd), BF16), arena, arena, S((b, n_tbl), I32), S((b, n_tbl * blk), I32)]
    fn = lambda q, k, v, t, m: paged_attention_decode(q, k, v, t, m, window=window)  # noqa: E731
    if dtype == I8:
        plane = S((n_blocks, 1, nkv * blk), F32)
        args += [plane, plane]
        fn = lambda q, k, v, t, m, ks, vs: paged_attention_decode(  # noqa: E731
            q, k, v, t, m, k_scale=ks, v_scale=vs, window=window)
    compiled = compile_for(fn, args, one)
    assert kernel_names(compiled) == ["paged_decode" if window is None else "paged_decode_window"]
    assert arena_rewrites(compiled, arena, *args[5:]) == []


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_latent_write_and_read_leave_the_arena_where_it_lies(v5e, form):
    """The latent arena's write and the absorbed decode kernel in one program
    at the long-context cell's shape: Mosaic accepts the kernel under its own
    name, and the one plane (two tokens a row: 1,152 columns are whole lane
    tiles, so the arena's default layout is the declared one) is neither
    re-laid in front of the kernel nor behind the scatter."""
    from trlx_tpu.ops.paged_attention import init_paged_latent_layer, paged_attention_latent, paged_latent_write

    c = LONGCTX_CELL
    one = SingleDeviceSharding(v5e[0])
    b, t = (c["slots"], 1) if form == "decode" else (1, 8192)
    layer = jax.eval_shape(lambda: init_paged_latent_layer(c["n_blocks"] + 1, c["blk"], c["width"], BF16))

    def step(layer, latent, table, start, q, mask):
        new = paged_latent_write(layer, latent, table, start, None, values=c["values"])
        out = paged_attention_latent(q, new["latent"], table, mask, values=c["values"],
                                     scale=c["qk_dim"] ** -0.5, out_dtype=BF16)
        return new, out

    args = (layer, S((b, t, c["width"]), BF16), S((b, c["n_tbl"]), I32), S((b,), I32),
            S((b, c["heads"], c["width"]), BF16), S((b, c["n_tbl"] * c["blk"]), I32))
    compiled = jax.jit(step, donate_argnums=(0,), in_shardings=one).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert kernel_names(compiled) == ["paged_decode_latent"]
    assert arena_rewrites(compiled, layer["latent"]) == []
    assert donated_outputs(compiled) >= 1


def test_flash_forward_with_narrower_value_heads_compiles_under_its_own_name(v5e):
    """The latent layer's decompressed prefill at the cell's widest prompt:
    query/key heads of 192 against value heads of 128, one row of 8,192."""
    c = LONGCTX_CELL
    shape = lambda width: S((1, 8192, c["heads"], width), BF16)  # noqa: E731
    compiled = compile_for(lambda q, k, v, m: attention._flash_fwd_pallas(q, k, v, m, True, None, None),
                           (shape(c["qk_dim"]), shape(c["qk_dim"]), shape(c["v_dim"]), S((1, 8192), I32)),
                           SingleDeviceSharding(v5e[0]))
    assert kernel_names(compiled) == ["flash_fwd_latent"]
    assert "bf16[128,8192,128]" in compiled.as_text()  # the output is as wide as the values


def test_kda_decode_compiles_at_the_cell_s_slot_pool_and_writes_the_state_in_place(v5e):
    """`kda_decode` at `ling-3.0-flash-vl.rollout-reason`'s pool, [128 slots, 32
    heads, 128, 128] float32: Mosaic takes the in-kernel transpose and the lane
    broadcasts, the donated state is the result's (268 MB aliased, no temporary
    of that size) and the call carries its name."""
    from trlx_tpu.ops import linear_attention

    rows, heads, dim = 128, 32, 128
    one = SingleDeviceSharding(v5e[0])
    vec = lambda *shape, dtype=F32: S(shape, dtype, sharding=one)
    args = (vec(rows, heads, dim, dim), vec(rows, heads, dim), vec(rows, heads, dim), vec(rows, heads, dim),
            vec(rows, heads, dim), vec(rows, heads), vec(rows, dtype=I32))
    compiled = jax.jit(linear_attention.kda_decode, donate_argnums=(0,)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert kernel_names(compiled) == ["kda_decode"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == rows * heads * dim * dim * 4
    assert memory.temp_size_in_bytes < 1 << 20, memory.temp_size_in_bytes


def test_the_chunked_form_s_temporaries_do_not_grow_with_the_prompt(v5e):
    """`kda_chunked` over 64 heads of 128 at 2,048 and at 8,192 positions: what
    the program holds beside its arguments and results is a span's (SPAN
    positions' pair terms, inverse and float32 copies), the same at both."""
    from trlx_tpu.ops import linear_attention

    one = SingleDeviceSharding(v5e[0])

    def temporaries(t):
        vec = lambda *shape, dtype=BF16: S((1, t, 64) + shape, dtype, sharding=one)
        args = (vec(128), vec(128), vec(128), vec(128, dtype=F32), vec(dtype=F32))
        compiled = jax.jit(linear_attention.kda_chunked).trace(*args).lower(lowering_platforms=("tpu",)).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    short, long = temporaries(2048), temporaries(8192)
    print(f"temporaries at 2,048 positions {short}, at 8,192 {long}")
    assert long < 1.05 * short and long < 512e6, (short, long)


@pytest.mark.parametrize("t,heads", [(8192, 64), (1024, 32)], ids=["longctx_widest_insert", "reason_widest_insert"])
def test_kda_chunk_fwd_compiles_at_the_cells_widest_inserts_and_holds_nothing_of_a_span(v5e, pallas_mode, t, heads):
    """`kda_chunked` as a cached prefill runs it where kernels run, at
    `solar-open2-250b.rollout-longctx`'s widest insert ([1, 8192, 64, 128]) and
    `ling-3.0-flash-vl.rollout-reason`'s ([1, 1024, 32, 128]), float32 as the
    layer hands them: Mosaic takes the strided tile reads, the shifts and the
    float32 products; the call carries `kda_chunk_fwd`; beside arguments and
    results the program holds next to nothing (no pair tensor, no re-laid
    copy of an input: the XLA form's span is 0.4 GB); and at 8,192 the text
    still holds ONE `while` whose tuple carries the row's state as
    `f32[1,64,128,128]`, which is what
    `bench/metrics/readers/kv_hybrid_kernels.py` tells the recurrence by."""
    import re

    from trlx_tpu.ops import linear_attention

    one = SingleDeviceSharding(v5e[0])
    vec = lambda *shape: S(shape, F32, sharding=one)
    args = (vec(1, t, heads, 128), vec(1, t, heads, 128), vec(1, t, heads, 128), vec(1, t, heads, 128),
            vec(1, t, heads), vec(1, heads, 128, 128))
    prefill = lambda *a: linear_attention.kda_chunked(*a, forward_only=True)
    compiled = jax.jit(prefill).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    assert kernel_names(compiled) == ["kda_chunk_fwd"]
    text = compiled.as_text()
    assert not re.search(r"f32\[[0-9,]*,16,16,128\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20, compiled.memory_analysis().temp_size_in_bytes
    loops = [line for line in text.splitlines() if " while(" in line]
    if t > linear_attention.SPAN:
        assert len(loops) == 1 and f"f32[1,{heads},128,128]" in loops[0].partition(" while(")[0], loops
    else:
        assert not loops, loops  # one span: one call


def test_ssd_decode_compiles_at_the_cell_s_slot_pool_and_the_chunked_form_holds_a_chunk(v5e):
    """`ssd_decode` at `falcon-h1-34b.rollout-chat`'s pool, [128 slots, 32 heads,
    256, 128] float32: Mosaic takes the [128, 256] transpose in the kernel, the
    donated state is the result's (537 MB aliased, no temporary of that size)
    and the call carries its name; `ssd_chunked` at 1,024 positions compiles to
    ONE loop over its 8 chunks whose carried tuple holds the row's state as
    `f32[1,2,16,256,128]`, which is what `bench/metrics/readers/ssm_kernels.py`
    tells the form by in a trace (XLA keeps no scope name on an event)."""
    from trlx_tpu.ops import ssd

    rows, heads, d_state, d_head, groups = 128, 32, 256, 128, 2
    one = SingleDeviceSharding(v5e[0])
    vec = lambda *shape, dtype=F32: S(shape, dtype, sharding=one)
    args = (vec(rows, heads, d_state, d_head), vec(rows, heads, d_head), vec(rows, heads), vec(heads),
            vec(rows, groups, d_state), vec(rows, groups, d_state), vec(rows, dtype=I32))
    compiled = jax.jit(ssd.ssd_decode, donate_argnums=(0,)).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    assert kernel_names(compiled) == ["ssd_decode"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == rows * heads * d_state * d_head * 4
    assert memory.temp_size_in_bytes < 32 << 20, memory.temp_size_in_bytes  # the stacked vectors, 16.8 MB

    t = 1024
    args = (vec(1, t, heads, d_head), vec(1, t, heads), vec(heads), vec(1, t, groups, d_state),
            vec(1, t, groups, d_state))
    chunked = jax.jit(ssd.ssd_chunked).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    loops = [line for line in chunked.as_text().splitlines() if " while(" in line]
    assert len(loops) == 1 and "f32[1,2,16,256,128]" in loops[0].partition(" while(")[0], loops


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
