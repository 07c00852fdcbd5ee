"""`ouro-2.6b.rollout-math`'s own programs, compiled for one v5e chip with no
chip: the cell's engine is built at the configuration file's widths (all 48
layers, 4 passes, the whole vocabulary) over shapes and no weights, and its
decode step and its widest prefill (one row of 256) are lowered for the TPU:
ONE body of 48 layers under a loop of 4 passes (48 paged kernel calls in the
text, 192 a step), the arena's four pools carried through the passes and
patched where they lie (no copy of a layer's arena in either program), and
what the program holds inside the chip's memory beside the weights and the pool.
"""

import re

import pytest

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    arena_rewrites, compile_engine_program, donated_outputs, held_bytes, kernel_names, loop_body_instructions,
    pallas_mode, serve_cell_engine, v5e,
)

WIDEST, NEW = 256, 352


@pytest.fixture(scope="module")
def loop_cell_engine(v5e):
    """The cell's engine: 8 slots x 19 table entries, 160 blocks (the zero block among them)."""
    return serve_cell_engine(v5e, "ouro-2.6b", "rollout-math", NEW, (WIDEST + NEW) // 32)


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_loop_cell_programs_compile_for_the_chip_and_fit_it(v5e, loop_cell_engine, pallas_mode, program):
    engine, params = loop_cell_engine
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    # a layer's arena is 4 pools of 160 blocks end to end: 8.05 GB of K and V in all
    assert len(arenas) == 96 and arenas[0].shape == (4 * 160, 16, 32, 128)
    assert sum(a.nbytes for a in arenas) == 160 * 32 * 1_572_864
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        assert kernel_names(compiled).count("paged_decode") == 48  # one body, 4 trips
        # the step's keys and values reach the arena from that call itself (PR 58): no other Mosaic call, and
        # the program's temporaries no more than with `paged_kv_write` in front of it (1.324 GB at PR 57)
        assert kernel_names(compiled) == ["paged_decode"] * 48 and engine._kv_write_form() == "kernel"
        assert compiled.memory_analysis().temp_size_in_bytes <= 1.324e9  # 1,314,187,776 B (my AOT compile, PR 58)
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, WIDEST, False))
        assert kernel_names(compiled) == []  # the dense insert: a prompt of 256 scores against its 640 columns
    # the passes are ONE loop over one body, not four copies of the stack
    assert len(re.findall(r" while\([^\n]*run_passes/while", compiled.as_text())) == 1
    # no copy of a layer's arena, in the program or inside the loop over passes
    assert arena_rewrites(compiled, *arenas) == []
    sizes = {a.size for a in arenas}
    assert [line[:120] for n, op, line in loop_body_instructions(compiled) if n in sizes
            and op in ("copy", "transpose")] == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    # 13.4 GB resident (weights 5.34, the pool 8.05) and the program's own, under the chip's 15.75 GiB
    assert 13.3e9 < held < 15.75 * 2 ** 30, held
