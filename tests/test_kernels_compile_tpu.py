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

from trlx_tpu.ops import attention, fused_ce, paged_attention  # noqa: E402
from trlx_tpu.ops.paged_attention import (  # noqa: E402
    init_paged_layer,
    paged_attention_decode,
    paged_kv_gather,
    paged_kv_write,
)

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


def _instructions(compiled):
    """(elements of the result, opcode, text) of every instruction of a
    compiled program whose result is one array."""
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m:
            yield int(np.prod([int(d) for d in m.group(1).split(",")])), m.group(2), line.strip()


def arena_rewrites(compiled, *operands) -> list:
    """The `copy` / `transpose` instructions of a compiled program whose
    result has as many elements as one of `operands` (a K/V arena or an
    int8 scale plane, under whatever shape the program views it): each is
    the whole operand moved through HBM once more than the work needs."""
    sizes = {int(np.prod(op.shape)) for op in operands}
    return [text[:120] for n, op, text in _instructions(compiled)
            if op in ("copy", "transpose") and n in sizes]


def instructions_of_at_least(compiled, elements: int) -> list:
    """The instructions of a compiled program whose result has `elements`
    elements or more, other than a buffer's update in place and what only
    names a buffer."""
    names_or_updates = ("parameter", "bitcast", "get-tuple-element", "tuple", "dynamic-update-slice")
    return [text[:160] for n, op, text in _instructions(compiled)
            if n >= elements and op not in names_or_updates]


def donated_outputs(compiled) -> int:
    """How many outputs of the program live in a donated input's buffer."""
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry_computation_layout",
                      compiled.as_text())
    return alias.group(1).count("-alias)") if alias else 0


def abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype, sharding=sharding), tree)


# pythia-1.4b.rollout-batch (bench/workloads): 1,280 blocks of 32 tokens,
# 16 kv heads of 128, 64 slots x 20 table entries
CELL = dict(n_blocks=1280, nkv=16, blk=32, hd=128, slots=64, n_tbl=20)


# the cell's own call, and the open chat cell's, 44 table entries a slot
# at `max_prompt_len` 1024 (PERF.md section 7)
@pytest.mark.parametrize("n_tbl", [CELL["n_tbl"], 44], ids=["cell", "chat"])
def test_paged_decode_fits_its_vmem_budget_at_the_cell_and_chat_shapes(v5e, n_tbl, monkeypatch):
    """64 rows over 1,280 blocks of 32, 16 heads of 128, bfloat16: one
    Mosaic call named `paged_decode`, the arena read where it lies, tiles
    of 8 entries, and VMEM inside the budget the module docstring states.
    The call carries `vmem_limit_bytes` and Mosaic refuses a kernel that
    needs more: the cell's 4 MiB of tile buffers and the rest fit half the
    12 MiB it is compiled under, and a quarter is refused, which shows the
    limit is enforced and not merely stated."""
    n_blocks, nkv, blk, hd, b = (CELL[k] for k in ("n_blocks", "nkv", "blk", "hd", "slots"))
    one = SingleDeviceSharding(v5e[0])
    arena = S((n_blocks, nkv, blk, hd), BF16)
    args = (S((b, nkv, hd), BF16), arena, arena, S((b, n_tbl), I32), S((b, n_tbl * blk), I32))
    assert paged_attention._tile_entries(n_tbl, nkv, blk, hd, BF16) == 8
    assert paged_attention._VMEM_LIMIT_BYTES == 12 * 2 ** 20
    assert 4 * 8 * paged_attention._vmem_block_bytes(nkv, blk, hd, BF16) == 4 * 2 ** 20

    def compile_under(limit):
        monkeypatch.setattr(paged_attention, "_VMEM_LIMIT_BYTES", limit)
        # a fresh function object: jit's trace cache would hand back the last limit's program
        return compile_for(lambda *a: paged_attention_decode(*a), args, one)

    compiled = compile_under(12 * 2 ** 20)
    assert kernel_names(compiled) == ["paged_decode"]
    assert arena_rewrites(compiled, arena) == []
    assert mosaic_calls(compile_under(6 * 2 ** 20)) == 1
    with pytest.raises(Exception, match="vmem"):
        compile_under(3 * 2 ** 20)


@pytest.mark.parametrize("dtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_paged_write_and_read_leave_the_arena_where_it_lies(v5e, form, dtype):
    """The write and the read in ONE program, arenas donated, as a layer of
    the engine's decode step (64 rows x 1 position, the kernel) and of a
    prefill (1 row x 256 positions, the gather) run them: the step's keys
    and values land in the donated arena in the layout the kernel and the
    gather read, with no copy of an arena or a scale plane on the way.
    `arena.at[phys, :, off].set(k)` put four arena copies a layer there:
    49 ms of a 120 ms decode step on the chip (PERF.md, PR 28)."""
    n_blocks, nkv, blk, hd = (CELL[k] for k in ("n_blocks", "nkv", "blk", "hd"))
    b, t = (CELL["slots"], 1) if form == "decode" else (1, 256)
    one = SingleDeviceSharding(v5e[0])

    def layer_step(layer, q, k, v, table, start, valid, key_mask):
        new = paged_kv_write(layer, k, v, table, start, valid)
        if form == "decode":
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


@pytest.fixture(scope="module")
def cell_engine(v5e):
    """A paged `InferenceEngine` as `pythia-1.4b.rollout-batch` builds it,
    over two layers of pythia-1.4b's widths and no weights: its programs
    are only compiled here. The engine picks the kernel by the device its
    params live on, and there are no params, so the test answers for it."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = config_from_preset("pythia-1.4b", 50304, n_layers=2, attn_impl="flash",
                             param_dtype=BF16, dtype=BF16)
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 32), I32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=128, do_sample=True,
                               eos_token_id=cfg.vocab_size + 1, pad_token_id=0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(InferenceEngine, "_param_devices", lambda self: [v5e[0]])
        engine = InferenceEngine(
            model, cfg, None, gen_cfg, kv_paging=True, num_slots=CELL["slots"],
            max_prompt_len=512, max_prefill_batch=8, prompt_bucket=128,
            kv_block_size=CELL["blk"], kv_pool_blocks=CELL["n_blocks"], kv_cache_dtype="bf16")
    assert engine.decode_path == "pallas"
    return engine, params


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_engine_programs_leave_the_arena_where_it_lies(v5e, cell_engine, program):
    """The same one level up, so that the call site in `Attention` is held
    and not only `paged_kv_write`: the engine's own decode program and one
    of its prefill programs (1 row x 256), pool donated."""
    engine, params = cell_engine
    one = SingleDeviceSharding(v5e[0])
    pool = abstract(engine._pool, one)
    params = abstract(params, one)
    if program == "decode":
        compiled = engine._decode_fn.trace(params, pool).lower(
            lowering_platforms=("tpu",)).compile()
    else:
        rows, width = 1, 256
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, CELL["n_tbl"]),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        compiled = engine._get_paged_insert(rows, width).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values())
        ).lower(lowering_platforms=("tpu",)).compile()
    n_layers = len(engine._pool["layers"])
    assert mosaic_calls(compiled) == (n_layers if program == "decode" else 0)
    if program == "decode":
        # one Pallas call a layer under the name the roofline's reader looks
        # for; the walk they share (`_live_schedule`: table and mask are the
        # step's, not a layer's) is computed once a step, not once a layer
        assert kernel_names(compiled) == ["paged_decode"] * n_layers
        scans = lambda c: c.as_text().count(" reduce-window(")  # noqa: E731  (cumsum, cummax)
        arena = S(engine._pool["layers"][0]["k"].shape, BF16)
        alone = compile_for(
            lambda *a: paged_attention_decode(*a),
            (S((CELL["slots"], CELL["nkv"], CELL["hd"]), BF16), arena, arena,
             S((CELL["slots"], CELL["n_tbl"]), I32), S((CELL["slots"], CELL["n_tbl"] * CELL["blk"]), I32)),
            one)
        assert 0 < scans(alone) == scans(compiled)
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)


# laguna-xs.2.rollout-code (bench/workloads): 7,168 blocks of 32 tokens, 8 kv
# heads of 128, 64 slots x 160 table entries, 48 query heads on full layers
# and 64 on sliding ones (window 512)
CODE_CELL = dict(n_blocks=7168, nkv=8, blk=32, hd=128, slots=64, n_tbl=160, window=512)


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


def serve_cell_engine(v5e, name: str, traffic: str, max_new: int, n_tbl: int):
    """A paged `InferenceEngine` as the cell `<name>.<traffic>` builds it, at
    the configuration file's own cut and no weights: (engine, abstract params)."""
    import json
    import os

    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.ops.sampling import GenerationConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "bench", "configs", f"{name}.json")))["bench"]
    cell = json.load(open(os.path.join(root, "bench", "workloads", f"{name}.{traffic}.json")))["engine"]
    extra = dict(bench["program"]["model_extra_configs"])
    cfg = config_from_preset(bench["program"]["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                             param_dtype=BF16, dtype=BF16)
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 32), I32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True,
                               eos_token_id=cfg.vocab_size + 1, pad_token_id=0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(InferenceEngine, "_param_devices", lambda self: [v5e[0]])
        engine = InferenceEngine(
            model, cfg, None, gen_cfg, kv_paging=True, num_slots=cell["num_slots"],
            max_prompt_len=cell["max_prompt_len"], max_prefill_batch=cell["max_prefill_batch"],
            prompt_bucket=cell["prompt_bucket"], kv_block_size=cell["kv_block_size"],
            kv_pool_blocks=cell["kv_pool_blocks"], kv_cache_dtype=cell["kv_cache_dtype"])
    assert engine.decode_path == "pallas" and engine._n_tbl == n_tbl
    return engine, params


@pytest.fixture(scope="module")
def code_cell_engine(v5e):
    """`laguna-xs.2.rollout-code`'s engine: 8 layers, 64 of 256 experts held."""
    return serve_cell_engine(v5e, "laguna-xs.2", "rollout-code", 1024, CODE_CELL["n_tbl"])


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_code_cell_programs_compile_for_the_chip_and_fit_it(v5e, code_cell_engine, pallas_mode, program):
    """`laguna-xs.2.rollout-code`'s decode step and its widest prefill (2 rows
    x 4,096, the fresh-prompt program): one paged call a layer under the name
    of its kind (2 full, 6 windowed), the prompt through the flash forward (2
    plain, 6 banded) and not a [rows, heads, 4096, 5120] score tensor, three
    grouped products an expert layer, no arena copied, and arguments plus
    temporaries inside the chip's 16 GiB."""
    engine, params = code_cell_engine
    one = SingleDeviceSharding(v5e[0])
    pool = abstract(engine._pool, one)
    params = abstract(params, one)
    if program == "decode":
        compiled = engine._decode_fn.trace(params, pool).lower(lowering_platforms=("tpu",)).compile()
        want = {"paged_decode": 2, "paged_decode_window": 6, "moe_gmm": 21}
    else:
        rows, width = 2, 4096
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, CODE_CELL["n_tbl"]),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        compiled = engine._get_paged_insert(rows, width, True).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values())
        ).lower(lowering_platforms=("tpu",)).compile()
        want = {"flash_fwd": 2, "flash_fwd_window": 6, "moe_gmm": 21}
        # nothing the size of two rows' scores against their whole tables
        assert instructions_of_at_least(compiled, 2 * 48 * 4096 * 5120) == []
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert held < 16 * 2 ** 30, held


# openpangu-ultra-moe-718b.rollout-longctx (bench/workloads): 12,288 blocks of
# 32 tokens, one latent plane of 512 + 64 values a token a layer, 64 slots x
# 288 table entries, 128 query heads of 128 + 64 against values of 128
LONGCTX_CELL = dict(n_blocks=12288, blk=32, width=576, values=512, heads=128, slots=64, n_tbl=288,
                    qk_dim=192, v_dim=128)


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


@pytest.fixture(scope="module")
def longctx_cell_engine(v5e):
    """`openpangu-ultra-moe-718b.rollout-longctx`'s engine: 5 layers, 8 of 256
    experts held, an eighth of the vocabulary."""
    return serve_cell_engine(v5e, "openpangu-ultra-moe-718b", "rollout-longctx", 1024, LONGCTX_CELL["n_tbl"])


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_longctx_cell_programs_compile_for_the_chip_and_fit_it(v5e, longctx_cell_engine, pallas_mode, program):
    """`openpangu-ultra-moe-718b.rollout-longctx`'s decode step and its widest
    prefill (one row of 8,192, the fresh-prompt program) at the published
    widths: one absorbed paged call a layer under its own name, the prompt
    through the flash forward with narrower values and not a [heads, 8192,
    9216] score tensor, three grouped products an expert layer, no arena
    copied, and arguments plus temporaries under 15.0 GB: the 9.08 GB resident
    (weights 6.82, the latent arena 2.26) and the program's own."""
    engine, params = longctx_cell_engine
    one = SingleDeviceSharding(v5e[0])
    pool = abstract(engine._pool, one)
    params = abstract(params, one)
    if program == "decode":
        compiled = engine._decode_fn.trace(params, pool).lower(lowering_platforms=("tpu",)).compile()
        want = {"paged_decode_latent": 5, "moe_gmm": 12}
    else:
        rows, width = 1, 8192
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, LONGCTX_CELL["n_tbl"]),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        compiled = engine._get_paged_insert(rows, width, True).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values())
        ).lower(lowering_platforms=("tpu",)).compile()
        want = {"flash_fwd_latent": 5, "moe_gmm": 12}
        assert instructions_of_at_least(compiled, 128 * 8192 * 9216) == []
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    print(f"{program}: arguments {memory.argument_size_in_bytes}, temporaries {memory.temp_size_in_bytes}, "
          f"held {held}")
    assert held < 15.0e9, held


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


REASON_CELL = dict(n_tbl=(1024 + 2048) // 32)


@pytest.fixture(scope="module")
def reason_cell_engine(v5e):
    """`ling-3.0-flash-vl.rollout-reason`'s engine: one period of 6 layers, 64
    of 512 experts held, an eighth of the vocabulary."""
    return serve_cell_engine(v5e, "ling-3.0-flash-vl", "rollout-reason", 2048, REASON_CELL["n_tbl"])


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_reason_cell_programs_compile_for_the_chip_and_fit_it(v5e, reason_cell_engine, pallas_mode, program):
    """`ling-3.0-flash-vl.rollout-reason`'s decode step and its widest prefill
    (one row of 1,024, the fresh-prompt program) at the published widths: one
    `kda_decode` a linear layer and one absorbed paged call for the latent one,
    the prompt's recurrence in chunks under XLA and its latent layer through
    the flash forward, three grouped products an expert layer, neither the
    arena nor a slot-state array copied, and arguments plus temporaries under
    15.0 GB: 4.73 GB of weights, 1.39 GB of slot state, 0.45 GB of arena and
    the program's own."""
    engine, params = reason_cell_engine
    one = SingleDeviceSharding(v5e[0])
    pool = abstract(engine._pool, one)
    params = abstract(params, one)
    if program == "decode":
        compiled = engine._decode_fn.trace(params, pool).lower(lowering_platforms=("tpu",)).compile()
        want = {"kda_decode": 5, "paged_decode_latent": 1, "moe_gmm": 15}
    else:
        rows, width = 1, 1024
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, REASON_CELL["n_tbl"]),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        compiled = engine._get_paged_insert(rows, width, True).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values())
        ).lower(lowering_platforms=("tpu",)).compile()
        want = {"flash_fwd_latent": 1, "moe_gmm": 15}
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    # the convolutions' tails (9 MB a layer) are shifted, so written anew, every step by
    # their nature; the arena and the recurrent matrices must stay where they lie
    arenas = [a for layer in engine._pool["layers"] for name, a in layer.items() if name != "tails"]
    assert len(arenas) == 5 + 1
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    print(f"{program}: arguments {memory.argument_size_in_bytes}, temporaries {memory.temp_size_in_bytes}, "
          f"held {held}")
    assert held < 15.0e9, held


KV_HYBRID_CELL = dict(n_tbl=(8192 + 1024) // 32)


@pytest.fixture(scope="module")
def kv_hybrid_cell_engine(v5e):
    """`solar-open2-250b.rollout-longctx`'s engine: one period of 4 layers (G K
    K K), 40 of 320 experts held, an eighth of the vocabulary."""
    return serve_cell_engine(v5e, "solar-open2-250b", "rollout-longctx", 1024, KV_HYBRID_CELL["n_tbl"])


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_kv_hybrid_cell_programs_compile_for_the_chip_and_fit_it(v5e, kv_hybrid_cell_engine, pallas_mode, program):
    """`solar-open2-250b.rollout-longctx`'s decode step and its widest prefill
    (one row of 8,192, the fresh-prompt program) at the published widths: one
    `kda_decode` a linear layer over 64 heads and one `paged_decode` for the
    GQA layer (8 query heads a K/V head, no rotation in front), the prompt's
    recurrence in chunks under XLA a span of 1,024 positions at a time (the
    test below holds its temporaries to a span's) and its GQA layer through the
    flash forward, three grouped products an expert layer, neither the arena
    nor a recurrent matrix copied, and arguments plus temporaries under 15.5
    GB: 6.62 GB of weights, 0.83 GB of slot state, 1.61 GB of arena and the
    program's own."""
    engine, params = kv_hybrid_cell_engine
    one = SingleDeviceSharding(v5e[0])
    pool = abstract(engine._pool, one)
    params = abstract(params, one)
    if program == "decode":
        compiled = engine._decode_fn.trace(params, pool).lower(lowering_platforms=("tpu",)).compile()
        want = {"kda_decode": 3, "paged_decode": 1, "moe_gmm": 12}
    else:
        rows, width = 1, 8192
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, KV_HYBRID_CELL["n_tbl"]),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        compiled = engine._get_paged_insert(rows, width, True).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values())
        ).lower(lowering_platforms=("tpu",)).compile()
        want = {"flash_fwd": 1, "moe_gmm": 12}
        assert instructions_of_at_least(compiled, 64 * 8192 * 8192) == []  # no [heads, 8192, 8192] score tensor
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for name, a in layer.items() if name != "tails"]
    assert len(arenas) == 2 + 3
    # a recurrent matrix pool [64, 64, 128, 128] has as many elements as one of the chunked form's
    # inputs over 8,192 positions: the pools are told by their shape, K and V by their size
    assert arena_rewrites(compiled, *engine._pool["layers"][0].values()) == []
    assert [i for i in instructions_of_at_least(compiled, 64 * 64 * 128 * 128)
            if "= f32[64,64,128,128]" in i and (" copy(" in i or " transpose(" in i)] == []
    assert donated_outputs(compiled) >= len(arenas)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    print(f"{program}: arguments {memory.argument_size_in_bytes}, temporaries {memory.temp_size_in_bytes}, "
          f"held {held}")
    assert held < 15.5e9, held


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


# lfm2-8b-a1b.ppo-hh (bench/workloads): d 2048, experts of width 1792, 8 of
# 32 held, 4 a token; a train step's 16 x 1024 tokens (65,536 dispatch rows)
# and a decode step's 64
LFM2 = dict(vocab_size=16384, n_layers=10, moe_local_experts=8, attn_impl="flash")


@pytest.fixture
def pallas_mode(monkeypatch):
    """The kernel rule looks at the process's devices, which are CPUs here:
    the test answers for it, as `cell_engine` does for the engine."""
    monkeypatch.setattr(attention, "kernel_mode", lambda: "pallas")


def _expert_stacks(params) -> list:
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if any("expert_" in str(getattr(k, "key", k)) for k in path) and leaf.ndim == 2]


@pytest.mark.parametrize("tokens", [16 * 1024, 64])
def test_expert_layer_compiles_and_leaves_its_stacks_where_they_lie(v5e, pallas_mode, tokens):
    """`SparseMoE` forward and backward at the cell's shapes: the three
    grouped products carry their names, and no step re-lays an expert stack
    or its gradient: the float32 leaves are `[fan_in, experts x fan_out]`,
    the kernels read a column block of the bfloat16 cast and write a column
    block of the gradient (a `[fan_in, experts, fan_out]` stack cost a
    transposing copy of every gradient, 117 MB each, PR 29)."""
    from trlx_tpu.models import config_from_preset
    from trlx_tpu.models.transformer import SparseMoE

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    layer = SparseMoE(cfg)
    one = SingleDeviceSharding(v5e[0])
    x = S((max(tokens // 1024, 1), min(tokens, 1024), cfg.d_model), BF16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, BF16))["params"])
    stacks = _expert_stacks(params)
    assert sorted(s.shape for s in stacks) == [(1792, 8 * 2048), (2048, 8 * 1792), (2048, 8 * 1792)]

    def loss(p, h):
        return (layer.apply({"params": p}, h).astype(F32) ** 2).sum()

    args = abstract((params, x), one)
    fwd = jax.jit(lambda p, h: layer.apply({"params": p}, h)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(kernel_names(fwd)) == ["moe_gmm"] * 3
    assert arena_rewrites(fwd, *stacks) == []
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(kernel_names(bwd)) == ["moe_gmm"] * 3 + ["moe_gmm_dlhs"] * 3 + ["moe_tgmm"] * 3
    assert arena_rewrites(bwd, *stacks) == []


def _lfm2_decode_step(v5e, leaves=F32):
    """One cached step of the cell's 10-layer model, 64 rows over a cache of
    1024, through the K/V tables and the convolution states, compiled for
    one v5e chip with parameters of type `leaves`: the program, the
    parameters' shapes and the cache's."""
    from trlx_tpu.models import config_from_preset, init_kv_cache
    from trlx_tpu.models.transformer import TransformerLM

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    b, total = 64, 1024
    tokens = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    params = jax.tree_util.tree_map(lambda a: S(a.shape, leaves), params)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, b, total))
    assert [sorted(layer) for layer in cache["layers"]] == [
        ["conv"] if kind == "conv" else ["k", "v"] for kind in cfg.layer_types]

    def step(p, tok, c, mask):
        return model.apply({"params": p}, tok, c, mask, False, method=TransformerLM.decode_step)

    compiled = jax.jit(step, donate_argnums=(2,)).trace(
        *abstract((params, S((b, 1), I32), cache, S((b, 1), I32)), one)).lower(
        lowering_platforms=("tpu",)).compile()
    return compiled, params, cache


def test_lfm2_decode_step_compiles_without_copying_an_expert_stack(v5e, pallas_mode):
    compiled, params, _ = _lfm2_decode_step(v5e)
    assert kernel_names(compiled).count("moe_gmm") == 3 * 8
    assert arena_rewrites(compiled, *_expert_stacks(params)) == []


def test_lfm2_decode_step_reads_its_kv_cache_once_for_all_query_heads(v5e, pallas_mode):
    """The two attention layers contract 32 query heads against a cache of 8
    kv heads. Repeating K and V to the query heads first made every step
    write and read `[64, 1024, 8, 4, 64]` broadcasts, float32 and bfloat16
    (5.83 GB a step by the compiler's count, over the sampler's bfloat16
    copy of the parameters); the grouped contraction reads the cache where
    it lies (3.03 GB): nothing the size of a repeated cache is left."""
    compiled, _, cache = _lfm2_decode_step(v5e, BF16)
    k = next(layer["k"] for layer in cache["layers"] if "k" in layer)
    assert k.shape == (64, 1024, 8, 64)
    repeated = int(np.prod(k.shape)) * 4  # 8 kv heads -> 32 query heads
    assert instructions_of_at_least(compiled, repeated) == []
    assert compiled.cost_analysis()["bytes accessed"] < 3.5e9


def test_lfm2_train_step_fits_the_chip_at_batch_16(v5e, pallas_mode, capsys):
    """The cell's train step in outline (windowed head over the 128 response
    positions, gradients of the top two blocks, AdamW), compiled for one
    v5e chip at 16 x 1024: the compiler's own account of its memory, beside
    the float32 leaves it is handed, has to leave room in 16 GB."""
    import optax
    from flax.traverse_util import flatten_dict, unflatten_dict

    from trlx_tpu.models import CausalLMWithValueHead, config_from_preset
    from trlx_tpu.models.policy import trainable_mask
    from trlx_tpu.utils.modeling import logprobs_of_labels

    cfg = config_from_preset("lfm2-8b-a1b", **LFM2)
    model = CausalLMWithValueHead(cfg)
    one = SingleDeviceSharding(v5e[0])
    b, t, new = 16, 1024, 128
    probe = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), probe, jnp.ones_like(probe))["params"])
    flat, mask = flatten_dict(params), flatten_dict(trainable_mask(params, cfg, 2))
    train = {k: v for k, v in flat.items() if mask[k]}
    frozen = {k: v for k, v in flat.items() if not mask[k]}
    assert not any("expert_bias" in k for k in train)
    opt = optax.adamw(6e-6)
    opt_state = jax.eval_shape(opt.init, train)

    def train_step(train, frozen, opt_state, tokens, attn_mask):
        def loss(train):
            logits, values, _ = model.apply(
                {"params": unflatten_dict({**train, **frozen})}, tokens, attn_mask,
                window=(t - new - 1, new), method=CausalLMWithValueHead.forward)
            return -logprobs_of_labels(logits, tokens[:, t - new:]).mean() + (values ** 2).mean()

        grads = jax.grad(loss)(train)
        updates, opt_state_new = opt.update(grads, opt_state, train)
        return optax.apply_updates(train, updates), opt_state_new

    compiled = jax.jit(train_step, donate_argnums=(0, 2)).trace(
        *abstract((train, frozen, opt_state, S((b, t), I32), S((b, t), I32)), one)).lower(
        lowering_platforms=("tpu",)).compile()
    assert arena_rewrites(compiled, *_expert_stacks(params)) == []
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(v.shape)) * 4 for v in flat.values())
    with capsys.disabled():
        print(f"\nlfm2-8b-a1b train step at {b} x {t} for v5e: arguments "
              f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB, "
              f"float32 leaves {held / 1e9:.2f} GB")
    # beside this program the process holds the reference copy of the top
    # blocks (0.84 GB) and the sampler's bfloat16 view while it runs
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5e9


# pythia-1.4b.ppo-hh (bench/workloads): chunks of 16 x (896 + 128) tokens,
# batches of 8, 64 rollouts a cycle, 2 blocks trained. Every width is the
# cell's; the depth is cut to 2 frozen blocks under the 2 trained ones (the
# 22 of the cell are one block's program 22 times, and a minute to compile)
PPO_HH = dict(vocab_size=50304, attn_impl="flash", n_layers=4)


def _cell_trainer(checkpoint_dir, preset, extra, *, batch_size, num_rollouts, chunk_size, max_new,
                  seq_length=1024):
    """A `PPOTrainer` whose scorer, loss and trunk-cache fill are a PPO
    cell's: built at test size, then handed the cell's model at the cell's
    widths, so `_build_score_fn`, `make_loss_fn` and `_build_trunk_cache_fn`
    trace the programs the cell runs over shapes and no array."""
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.models import CausalLMWithValueHead, config_from_preset
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=seq_length, batch_size=batch_size, tracker=None,
                   checkpoint_dir=str(checkpoint_dir)),
        method=dict(num_rollouts=num_rollouts, chunk_size=chunk_size, ppo_epochs=4,
                    gen_kwargs=dict(max_new_tokens=max_new, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples),
                         devices=jax.devices()[:1])
    cfg = config_from_preset(preset, **extra)
    trainer.model, trainer.model_cfg, trainer.split = CausalLMWithValueHead(cfg), cfg, cfg.n_layers - 2
    # the trainer's mesh is this process's CPU; the programs are placed
    # where the test compiles them
    trainer._trunk_cache_sharding = lambda shape=None: None
    assert trainer._trunk_cache_available()
    return trainer


@pytest.fixture(scope="module")
def ppo_hh_trainer(tmp_path_factory):
    return _cell_trainer(tmp_path_factory.mktemp("ppo_hh"), "pythia-1.4b", PPO_HH,
                         batch_size=8, num_rollouts=64, chunk_size=16, max_new=128)


def _ppo_hh_params(trainer, with_ref=False):
    """(trainable, frozen) flat float32 leaves of the cell's model, as
    shapes; with the reference branch's subtree where asked."""
    from flax.traverse_util import flatten_dict

    from trlx_tpu.models.policy import ref_param_subtree, trainable_mask

    probe = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(
        lambda: trainer.model.init(jax.random.PRNGKey(0), probe, jnp.ones_like(probe))["params"])
    flat = flatten_dict(params)
    mask = flatten_dict(trainable_mask(params, trainer.model_cfg, 2))
    train = {k: v for k, v in flat.items() if mask[k]}
    frozen = {k: v for k, v in flat.items() if not mask[k]}
    if not with_ref:
        return train, frozen
    return train, frozen, jax.eval_shape(
        lambda p: ref_param_subtree(p, trainer.model_cfg, trainer.split), params)


def _traced_score(trainer, device, rows, width, hands_out):
    """The one program behind `_score_fn`, from where the trainer makes it,
    traced over a chunk of the cell for one described chip."""
    programs, ljit = {}, type(trainer)._ljit
    trainer._ljit = lambda fn, name, **kw: programs.setdefault(name, ljit(trainer, fn, name, **kw))
    trainer._score_hands_out_trunk_state = lambda: hands_out
    trainer._build_score_fn()
    assert trainer._score_with_trunk_state is hands_out and list(programs) == ["score"]
    assert (trainer._score_fn is programs["score"]) is not hands_out
    return programs["score"].trace(*abstract(
        (*_ppo_hh_params(trainer, with_ref=True), S((rows, width), I32)), SingleDeviceSharding(device)))


def test_dense_ppo_cell_trunk_cache_fill_compiles_under_its_name(v5e, pallas_mode, ppo_hh_trainer):
    """`jit_trunk_cache_fill` over one chunk of the cell, 16 x 1,024 tokens:
    the frozen blocks' flash forwards and no head, the state in bfloat16,
    the forward's own dtype."""
    trainer = ppo_hh_trainer
    one = SingleDeviceSharding(v5e[0])
    train, frozen = _ppo_hh_params(trainer)
    fill = trainer._build_trunk_cache_fn()
    traced = fill.trace(*abstract((train, frozen, S((16, 1024), I32)), one))
    assert traced.out_info.shape == (16, 1024, 2048) and traced.out_info.dtype == BF16
    lowered = traced.lower(lowering_platforms=("tpu",))
    assert "module @jit_trunk_cache_fill" in lowered.as_text()[:200]
    compiled = lowered.compile()
    assert kernel_names(compiled) == ["flash_fwd"] * trainer.split


def test_dense_ppo_cell_train_step_resumes_from_the_trunk_cache(v5e, pallas_mode, ppo_hh_trainer):
    """The cell's train step in outline (the trainer's own loss, gradients
    of the top two blocks, AdamW) over a batch of 8 that names its rows of
    the cycle's cache `[64, 1024, 2048]`: the gather, the two trained
    blocks forward and backward and the windowed head lower and compile
    for one v5e chip, and no frozen block runs: two flash forwards, where
    the whole forward of the same step runs one a block."""
    import optax

    from trlx_tpu.data import PPORLBatch

    trainer = ppo_hh_trainer
    one = SingleDeviceSharding(v5e[0])
    train, frozen = _ppo_hh_params(trainer)
    loss_fn = trainer.make_loss_fn()
    opt = optax.adamw(6e-6)
    opt_state = jax.eval_shape(opt.init, train)
    b, q, new = 8, 896, 128
    batch = PPORLBatch(
        query_tensors=S((b, q), I32), response_tensors=S((b, new), I32),
        logprobs=S((b, new), F32), values=S((b, new), F32), rewards=S((b, new), F32),
        trunk_rows=S((b,), I32), trunk_cache=S((64, q + new, 2048), BF16))

    def train_step(train, frozen, opt_state, batch):
        grads = jax.grad(lambda p: loss_fn(p, frozen, batch)[0])(train)
        updates, opt_state = opt.update(grads, opt_state, train)
        return optax.apply_updates(train, updates), opt_state

    def flash_forwards(batch):
        compiled = jax.jit(train_step, donate_argnums=(0, 2)).trace(
            *abstract((train, frozen, opt_state, batch), one)).lower(
            lowering_platforms=("tpu",)).compile()
        return sum(name.startswith("flash_fwd") for name in kernel_names(compiled)), compiled

    resumed, compiled = flash_forwards(batch)
    whole, _ = flash_forwards(batch.replace(trunk_rows=None, trunk_cache=None))
    assert (resumed, whole) == (2, trainer.model_cfg.n_layers)
    # the cache is an argument the step reads and hands back to nobody
    assert donated_outputs(compiled) == len(jax.tree_util.tree_leaves((train, opt_state)))


def test_lfm2_score_program_with_the_trunk_state_compiles_and_says_what_it_holds(
        v5e, pallas_mode, tmp_path, capsys):
    """`lfm2-8b-a1b.ppo-hh` collects in one chunk of 64 x 1,024, so its
    score program hands out the state entering block 8 as a sixth output
    (`_score_hands_out_trunk_state`): under the name the device trace knows
    (`jit_score`), compiled for one v5e chip, its outputs are the
    five-output program's and 64 x 1,024 x 2,048 bfloat16 states, 268 MB
    that stand on the device from the score to the cycle's last step in
    the fill's place."""
    b, t = 64, 1024
    trainer = _cell_trainer(tmp_path, "lfm2-8b-a1b", LFM2, batch_size=16, num_rollouts=b,
                            chunk_size=b, max_new=128)
    assert trainer._score_hands_out_trunk_state()
    five, six = (_traced_score(trainer, v5e[0], b, t, hands_out) for hands_out in (False, True))
    assert [(o.shape, o.dtype) for o in six.out_info] == [
        *((o.shape, o.dtype) for o in five.out_info), ((b, t, trainer.model_cfg.d_model), BF16)]
    five_bytes = sum(int(np.prod(o.shape)) * o.dtype.itemsize for o in five.out_info)
    assert five_bytes == 3 * b * (t - 1) * 4 + 2 * 4
    lowered = six.lower(lowering_platforms=("tpu",))
    assert "module @jit_score " in lowered.as_text()[:200]
    memory = lowered.compile().memory_analysis()
    with capsys.disabled():
        print(f"\nlfm2-8b-a1b score at {b} x {t} for v5e with the trunk state: outputs "
              f"{memory.output_size_in_bytes / 1e6:.1f} MB ({five_bytes / 1e6:.1f} MB without it), "
              f"temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB, arguments "
              f"{memory.argument_size_in_bytes / 1e9:.2f} GB")
    want = five_bytes + b * t * trainer.model_cfg.d_model * 2
    # (the compiler rounds every output buffer up to its tile)
    assert want <= memory.output_size_in_bytes <= want + 64 * 1024


def test_gpt2_xl_score_keeps_its_weight_prefetches_with_the_trunk_state(v5e, pallas_mode, tmp_path):
    """`gpt2-xl.ppo-sentiments` scores one chunk of 128 x 104, whose whole
    residual stream (43 MB) fits a v5e's fast memory. Handed out as a plain
    sixth output the state made the compiler keep that stream there and
    stop prefetching the frozen blocks' MLP weights (sliced copies joined by
    `ConcatBitcast`): 0.676 against 0.566 s a chunk on the chip (PERF.md
    section 6, PR 40). `score` hands it out behind a barrier for that; this
    holds the plan, at the cell's widths and a depth of 4 frozen blocks:
    the six-output program prefetches what the five-output program does."""
    trainer = _cell_trainer(tmp_path, "gpt2-xl", dict(vocab_size=50257, attn_impl="flash", n_layers=6),
                            batch_size=32, num_rollouts=128, chunk_size=128, max_new=40)
    assert trainer._score_hands_out_trunk_state()

    def prefetched_weights(hands_out):
        traced = _traced_score(trainer, v5e[0], 128, 104, hands_out)
        return traced.lower(lowering_platforms=("tpu",)).compile().as_text().count("ConcatBitcast")

    five, six = prefetched_weights(False), prefetched_weights(True)
    assert five >= 4 * 6 and six >= five, (five, six)
