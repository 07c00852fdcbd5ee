"""`smallthinker-21b-a3b.rollout-transcript`'s own programs, compiled for one
v5e chip with no chip: the cell's engine is built at the configuration file's
widths over shapes and no weights, and its decode step and its widest prefill
(one row of 14,336, the fresh-prompt program) are lowered for the TPU: the
kernels by name (a group of 7 query heads a K/V head and a band of 4,096 are
shapes neither paged kernel nor flash forward had compiled at), no arena
copied, no attention scores of the whole prompt, and what the program holds
inside the chip's memory beside the weights and the pool.
"""

import pytest

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    arena_rewrites, compile_engine_program, donated_outputs, held_bytes, instructions_of_at_least,
    kernel_names, pallas_mode, serve_cell_engine, v5e,
)

WIDEST, CACHE = 14336, 15360


@pytest.fixture(scope="module")
def transcript_cell_engine(v5e):
    """The cell's engine: 8 layers (full, 3 banded, twice), all 64 experts, the
    whole vocabulary; 32 slots x 480 table entries."""
    return serve_cell_engine(v5e, "smallthinker-21b-a3b", "rollout-transcript", 1024, CACHE // 32)


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_transcript_cell_programs_compile_for_the_chip_and_fit_it(v5e, transcript_cell_engine, pallas_mode, program):
    """The decode step: the paged kernel on the two full layers, the windowed
    one on the six banded layers, three grouped products an expert layer over
    32 x 6 rows. The widest prefill: the flash forward on the full layers, the
    banded one on the others, the experts 4,096 tokens at a time (four blocks,
    the last of 2,048: twelve calls a layer) under a routing made over the
    whole prompt before the attention. In both no arena is copied; in the
    prefill nothing but the head has the elements of a [width, width] score matrix; and
    arguments plus temporaries stay under 15.75 GiB: the 13.3 GB resident
    (weights 7.93, the pool 5.37) and the program's own."""
    engine, params = transcript_cell_engine
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"paged_decode": 2, "paged_decode_window": 6, "moe_gmm": 24}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, WIDEST, True))
        want = {"flash_fwd": 2, "flash_fwd_window": 6, "moe_gmm": 96}
        # (the head, [2560, 151936], has 1.9 times the elements of such a matrix: the final norm's
        # scale folded into it is the only thing that large)
        assert [i for i in instructions_of_at_least(compiled, WIDEST * WIDEST) if "[2560,151936]" not in i] == []
    # the routing made on the block's input keeps its scope in the compiled instructions' `op_name`: the
    # device trace names events by instruction, so this text is what maps an event to the routing
    assert compiled.as_text().count("moe_route_block_input/") >= 8
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert len(arenas) == 16 and arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    assert 13.3e9 < held < 15.75 * 2 ** 30, held
