"""The programs of the serve cells that keep a recurrent state a slot,
compiled for one v5e chip with no chip.

`ling-3.0-flash-vl.rollout-reason` and `solar-open2-250b.rollout-longctx`:
Kimi-delta linear layers beside a paged cache; `falcon-h1-34b.rollout-chat`: a
Mamba-2 state a slot AND K/V a token in every layer. As in
`test_serve_cells_compile_tpu.py`: the kernels by name, neither the arena nor
a recurrent matrix copied, and what the program holds inside the chip.
"""

import pytest

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    arena_rewrites, compile_engine_program, donated_outputs, held_bytes, instructions_of_at_least,
    kernel_names, pallas_mode, serve_cell_engine, v5e,
)


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
    the prompt's recurrence one `kda_chunk_fwd` a linear layer (a prompt of one
    span) and its latent layer through the flash forward, three grouped
    products an expert layer, neither the
    arena nor a slot-state array copied, and arguments plus temporaries under
    15.0 GB: 4.73 GB of weights, 1.39 GB of slot state, 0.45 GB of arena and
    the program's own."""
    engine, params = reason_cell_engine
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"kda_decode": 5, "paged_decode_latent": 1, "moe_gmm": 15}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, 1024, True))
        want = {"flash_fwd_latent": 1, "kda_chunk_fwd": 5, "moe_gmm": 15}
    names = kernel_names(compiled)
    assert {n: names.count(n) for n in set(names)} == want
    # the convolutions' tails (9 MB a layer) are shifted, so written anew, every step by
    # their nature; the arena and the recurrent matrices must stay where they lie
    arenas = [a for layer in engine._pool["layers"] for name, a in layer.items() if name != "tails"]
    assert len(arenas) == 5 + 1
    assert arena_rewrites(compiled, *arenas) == []
    assert donated_outputs(compiled) >= len(arenas)
    held = held_bytes(compiled)
    print(f"{program}: held {held}")
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
    recurrence one `kda_chunk_fwd` a linear layer inside the scan over spans of
    1,024 positions (one call in the text, eight spans at run time) and its GQA
    layer through the flash forward, three grouped products an expert layer,
    neither the arena
    nor a recurrent matrix copied, and arguments plus temporaries under 15.5
    GB: 6.62 GB of weights, 0.83 GB of slot state, 1.61 GB of arena and the
    program's own."""
    engine, params = kv_hybrid_cell_engine
    if program == "decode":
        compiled = compile_engine_program(engine, params, v5e[0])
        want = {"kda_decode": 3, "paged_decode": 1, "moe_gmm": 12}
    else:
        compiled = compile_engine_program(engine, params, v5e[0], (1, 8192, True))
        want = {"flash_fwd": 1, "kda_chunk_fwd": 3, "moe_gmm": 12}
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
    held = held_bytes(compiled)
    print(f"{program}: held {held}")
    assert held < 15.5e9, held


CHAT_CELL = dict(n_tbl=(1024 + 512) // 32)


def test_chat_cell_programs_compile_for_the_chip_and_fit_it(v5e, pallas_mode):
    """`falcon-h1-34b.rollout-chat`'s decode step and its widest prefill (one
    row of 1,024, the fresh-prompt program) at the published widths: in EVERY
    layer one `ssd_decode` over the slot pool and one `paged_decode` at 5 query
    heads a K/V head, the prompt's recurrence in chunks of 128 under XLA beside
    the flash forward, neither the arena nor a recurrent matrix copied (the
    state pools [128, 32, 256, 128] are told by their shape), and arguments plus
    temporaries under 15.0 GB: 8.79 GB of weights, 2.16 GB of slot state, 1.61
    GB of arena and the program's own (the prefill's logits over the whole
    vocabulary at its one row's last position among them). One test, so that the
    engine's 3.8 GB pool on the host lives no longer than its two compiles."""
    engine, params = serve_cell_engine(v5e, "falcon-h1-34b", "rollout-chat", 512, CHAT_CELL["n_tbl"])
    pools = [a for layer in engine._pool["layers"] for name, a in layer.items() if name != "tails"]
    assert len(pools) == 4 * 3 and engine._kernel_unsupported is None
    for program, prefill, want in (("decode", None, {"ssd_decode": 4, "paged_decode": 4}),
                                   ("paged_insert", (1, 1024, True), {"flash_fwd": 4})):
        compiled = compile_engine_program(engine, params, v5e[0], prefill)
        names = kernel_names(compiled)
        assert {n: names.count(n) for n in set(names)} == want, program
        assert arena_rewrites(compiled, engine._pool["layers"][0]["k"]) == [], program
        assert [i for i in instructions_of_at_least(compiled, 128 * 32 * 256 * 128)
                if "= f32[128,32,256,128]" in i and (" copy(" in i or " transpose(" in i)] == [], program
        assert donated_outputs(compiled) >= len(pools), program
        held = held_bytes(compiled)
        print(f"{program}: held {held}")
        assert held < 15.0e9, (program, held)
