"""The serve cells' own programs, compiled for one v5e chip with no chip.

`pythia-1.4b.rollout-batch`, `laguna-xs.2.rollout-code` and
`openpangu-ultra-moe-718b.rollout-longctx`: each cell's engine is built once
a module at the configuration file's widths over shapes and no weights, and
its decode step and its widest prefill are lowered for the TPU: the kernels
by name, no arena copied, and what the program holds inside the chip's 16 GB.
The cells with a recurrent state a slot are in
`test_state_cells_compile_tpu.py`.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    arena_rewrites, BF16, CELL, CODE_CELL, compile_engine_program, compile_for, donated_outputs,
    held_bytes, I32, instructions_of_at_least, kernel_names, LONGCTX_CELL, mosaic_calls,
    pallas_mode, S, serve_cell_engine, v5e,
)
from trlx_tpu.ops.paged_attention import paged_attention_decode  # noqa: E402


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
    compiled = compile_engine_program(engine, params, v5e[0], None if program == "decode" else (1, 256))
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
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"paged_decode": 2, "paged_decode_window": 6, "moe_gmm": 21}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (2, 4096, True))
        want = {"flash_fwd": 2, "flash_fwd_window": 6, "moe_gmm": 21}
        # nothing the size of two rows' scores against their whole tables
        assert instructions_of_at_least(compiled, 2 * 48 * 4096 * 5120) == []
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    assert held < 16 * 2 ** 30, held


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
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"paged_decode_latent": 5, "moe_gmm": 12}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, 8192, True))
        want = {"flash_fwd_latent": 5, "moe_gmm": 12}
        assert instructions_of_at_least(compiled, 128 * 8192 * 9216) == []
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    print(f"{program}: held {held}")
    assert held < 15.0e9, held
