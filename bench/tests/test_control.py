"""The lower-precision control, at the rehearsal size: the program's own
int8 paths switched on (`run.py --control`) have to come out differently
from the sound program on the same seeds.

- serve: an int8 KV cache. A logprob cannot tell it from a bfloat16 cache
  (values average over the keys attended to; PERF.md section 2 has both
  readings at the cell's own size), so `correct` holds the cache to the
  bytes of the stated type: exact for the sound program, far off for int8.
- ppo: int8 weights for the frozen trunk in the sampler's view. The sampler's
  logprob error against the reference rises on every seed. At this size one
  layer of two is quantized and the rise is small; at the cells' own size
  (22 of 24 layers) PERF.md has the readings the limit was set from.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_control.py -q
"""

import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEEDS = [101, 2_147_483_747, 3_000_000_203]


def _run(cell, seed, control):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", "3", "--trace", "0", "--rehearse-cpu"] + (["--control"] if control else [])
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    checks = {}
    for what, value, verdict in re.findall(
            r"\[bench\] check (.*?): (\S+) \(limit .*?\) (ok|NOT CORRECT)", proc.stdout):
        checks[what] = (value, verdict == "ok")
    assert checks, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.returncode, checks


def _find(checks, prefix):
    return next(v for k, v in checks.items() if k.startswith(prefix))


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_kv_cache_is_not_correct(seed):
    rc, sound = _run("pythia-1.4b.rollout-batch", seed, control=False)
    assert rc == 0 and all(ok for _, ok in sound.values()), sound
    rc, control = _run("pythia-1.4b.rollout-batch", seed, control=True)
    value, ok = _find(control, "bytes of the arrays the engine's pool holds")
    assert rc != 0 and not ok and float(value) > 0.2
    assert float(_find(sound, "bytes of the arrays the engine's pool holds")[0]) < 0.02


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_trunk_raises_the_samplers_error(seed):
    _, sound = _run("pythia-1.4b.ppo-hh", seed, control=False)
    _, control = _run("pythia-1.4b.ppo-hh", seed, control=True)
    key = "sampler_logprob_rms"
    assert float(_find(control, key)[0]) > 1.2 * float(_find(sound, key)[0])
    # the scorer never sees the sampler's view: unchanged
    assert _find(control, "scorer_logprob_rms") == _find(sound, "scorer_logprob_rms")


def test_open_loop_rehearsal():
    """The serve job's open loop (no cell of BENCHMARK.json uses it yet):
    every request answered, both tails taken, the outputs correct."""
    rc, checks = _run("rehearsal.serve-open", 77, control=False)
    assert rc == 0 and all(ok for _, ok in checks.values()), checks
    assert _find(checks, "end-to-end metrics the cell names but the run could not take")[1]
