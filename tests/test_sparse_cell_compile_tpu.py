"""`dots3-note-prev.rollout-longdoc`'s own programs, compiled for one v5e chip
with no chip: the cell's engine is built at the configuration file's widths
over shapes and no weights, and its decode step and its widest prefill
(one row of 24,576, the fresh-prompt program) are lowered for the TPU: the
kernels by name (a prefill's query blocks are one traced body: one call a
layer), no arena copied, no index scores and no attention scores of the
whole prompt, and what the program holds inside the chip's memory beside the
weights and the pool.
"""

import pytest

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    arena_rewrites, compile_engine_program, donated_outputs, held_bytes, instructions_of_at_least,
    kernel_names, pallas_mode, serve_cell_engine, v5e,
)

WIDEST, CACHE = 24576, 25088


@pytest.fixture(scope="module")
def longdoc_cell_engine(v5e):
    """The cell's engine: 5 layers (full, 3 sliding, full), 32 of 256 experts
    held, an eighth of the vocabulary; 16 slots x 784 table entries."""
    return serve_cell_engine(v5e, "dots3-note-prev", "rollout-longdoc", 512, CACHE // 32)


@pytest.mark.parametrize("program", ["decode", "paged_insert"])
def test_longdoc_cell_programs_compile_for_the_chip_and_fit_it(v5e, longdoc_cell_engine, pallas_mode, program):
    """The decode step: the banded latent kernel on the three sliding layers,
    the index's scores through the table on the two full ones (whose chosen
    latents a gather reads: no third kernel), three grouped products an expert
    layer. The widest prefill, 12 query blocks of 2,048 that are one body of
    a loop a layer: ONE banded forward a sliding layer, ONE call of the
    index's scores and ONE of the per-head forward under the mask of the
    chosen a full layer (a block's eight groups of 16 heads, each over keys
    and values decompressed for it, 2 x 101 MB at this width, are one body of
    a loop inside the blocks'), whatever the prompt's width (the first
    block's scores are computed and change nothing: everything attendable is
    chosen); the experts 4,096 tokens at a time, six calls a product. In
    both no arena is copied; in the prefill nothing has the elements of a
    [width, width] score matrix, let alone of one a head, nor of all heads'
    keys; and arguments plus temporaries stay under 15.75 GiB: the 11.93 GB
    resident (weights 8.17, the pool 3.75) and the program's own."""
    engine, params = longdoc_cell_engine
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"paged_decode_latent_window": 3, "paged_index_scores": 2, "moe_gmm": 12}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, WIDEST, True))
        want = {"flash_fwd_latent_window": 3, "sparse_index_scores": 2, "sparse_latent_fwd": 2, "moe_gmm": 72}
        assert instructions_of_at_least(compiled, WIDEST * WIDEST) == []  # (a sliding layer's arena has 0.72 of that)
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    arenas = [a for layer in engine._pool["layers"] for a in layer.values()]
    assert len(arenas) == 7 and arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    assert 11.9e9 < held < 15.75 * 2 ** 30, held
