"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware (the reference has no
distributed tests at all — SURVEY.md §4).

Tier-1 runs with JAX_PLATFORMS=cpu already; the config update below is for
a bare `pytest` on a machine that has a chip, which would otherwise take it.
Both settings must land before the backend initializes.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Tier-1's files in the order its workers start them: the driver runs six
# xdist workers by file (`-n 6 --dist loadfile`), a file's seconds are a floor
# under the run's, and the run ends when the last long file does, so the long
# files go out at second 0 and the short ones fill the tail. Seconds are each
# file's own `pytest <file>` on the builder's 8 cores, alone or beside two
# others (my CPU runs, PR 44; `PERF.md` section 7 has the table). A file that
# is not listed keeps its alphabetical place behind these; one that takes
# over a minute belongs here, and one that passes 250 s is split (ROADMAP D10).
LONGEST_FIRST = [
    "test_trunk_cache.py",                 # 211 s (206 s alone with PR 49's 69 cases)
    "test_lfm2_compile_tpu.py",            # 192 s
    "test_ling_flash.py",                  # 169 s
    "test_dots3_note.py",                  # 196 s (my CPU run, PR 51)
    "test_pangu_mla.py",                   # 165 s
    "test_laguna.py",                      # 160 s
    "test_serve_cells_compile_tpu.py",     # 145 s
    "test_paged_attention.py",             # 145 s
    "test_trainers.py",                    # 133 s
    "test_engine_step_ahead.py",           # 129 s
    "test_state_cells_compile_tpu.py",     # 139 s (98 s before PR 48's chat cell)
    "test_sparse_cell_compile_tpu.py",     # 120 s (my CPU run, PR 51: the decode step and one row of 24,576)
    # (PR 49: pythia's two `score` programs at [16, 1024] compile for 4-7 min
    # each at any depth and took this file to 1,128 s of a 1,245 s run: that
    # case is marked slow and its lowering stays)
    "test_ppo_cells_compile_tpu.py",       # 126 s
    "test_onef1b_trainers.py",             # 120 s
    "test_peft.py",                        # 103 s
    "test_onef1b.py",                      # 103 s
    "test_lfm2_moe.py",                    # 101 s
    "test_model_families.py",              # 100 s
    "test_pipeline_parallel.py",           # 94 s
    "test_seq2seq.py",                     # 94 s
    "test_moe.py",                         # 90 s
    "test_attention.py",                   # 85 s
    "test_pipeline_sequence.py",           # 82 s
    "test_forward_entry.py",               # 80 s
    "test_sequence_parallel.py",           # 79 s
    "test_transcript_cell_compile_tpu.py",  # 75 s (my CPU run, PR 55: the decode step and one row of 14,336)
    "test_smallthinker.py",                # 76 s (my CPU run, PR 55)
    "test_loop_cell_compile_tpu.py",       # 70 s (my CPU run, PR 57: the looped decode step and one row of 256)
    "test_tracing_control.py",             # 69 s
    "test_solar_open2.py",                 # 68 s
    "test_pipeline_tp.py",                 # 64 s
    "test_paged_kv.py",                    # 63 s
    "test_resume.py",                      # 63 s
    "test_falcon_h1.py",                   # 62 s
]


def pytest_configure(config):
    # pytest-xdist 3.7 and later re-sort loadfile's files by their number of
    # cases, whatever order they were collected in: `test_sequence_parallel.py`
    # (7 cases, the second longest) started at ~930 s of 1,470. The order
    # above is the measured one, so theirs is turned off (without xdist the
    # option is nobody's).
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
