"""The backlog window of the `serve` job: whole passes over a pool of one
request a slot, from one decode step's end to another's.

- `backlog_window` on logs small enough to read by hand: it closes on
  k x pool admissions and on a step's end, for two lengths;
- `traffic.lengths` with the pool as long as the slots: any `pool`
  consecutive requests of the cycled sequence are one multiset, whatever the
  seed;
- `series_stat` on a two-humped series: the mean is the series' arithmetic,
  the median flips between the humps;
- the rehearsal of the cell itself, two seeds and two lengths: the check on
  admissions holds and the windows hold the same prompt widths a pass.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_backlog_window.py -q
"""

import ast
import collections
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from benchlib import traffic  # noqa: E402
from benchlib.files import load_module  # noqa: E402

serve = load_module("jobs/serve.py")
series_stat = load_module("metrics/readers/series_stat.py")

# One thread's logs, by hand. Steps end at 1, 2, 3, ... s; one request is
# admitted before every second step (at 2.5, 4.5, ...), as in a pool of 4 slots
# whose requests run 8 steps. The ramp ends at 3.2: the window opens at the end
# of the step that ends at 4 (index 3). The 4th admission since then begins at
# 10.5 and is decoded by the step that ends at 11 (index 10): one pass, 7 s.
STEP_ENDS = [float(t) for t in range(1, 41)]
ADMISSIONS = [(t + 0.5, 1) for t in range(2, 40, 2)]


@pytest.mark.parametrize("seconds, want", [
    (5.0, (3, 10, 1, 4)),    # one pass of 4 is 7 s long
    (7.5, (3, 18, 2, 8)),    # one pass is too short: two, 15 s
    (15.0, (3, 18, 2, 8)),   # exactly as long as asked
    (15.5, (3, 26, 3, 12)),
])
def test_window_closes_on_whole_passes_and_a_steps_end(seconds, want):
    got = serve.backlog_window(STEP_ENDS, ADMISSIONS, 3.2, 4, seconds)
    assert got == want
    first, last, k, admitted = got
    assert admitted == k * 4 and STEP_ENDS[last] - STEP_ENDS[first] >= seconds
    # a pass here is 8 steps, and the window one step short of k of them: one pass fewer is too short
    assert k == 1 or 8 * (k - 1) - 1 < seconds


@pytest.mark.parametrize("step_ends, admissions", [
    (STEP_ENDS[:10], ADMISSIONS),      # the closing step has not ended yet
    (STEP_ENDS, ADMISSIONS[:4]),       # not enough admitted yet
    (STEP_ENDS[:3], ADMISSIONS),       # the ramp is not over
])
def test_window_is_open_while_the_logs_fall_short(step_ends, admissions):
    assert serve.backlog_window(step_ends, admissions, 3.2, 4, 5.0) is None


def test_window_reports_an_admission_that_overshoots_a_pass():
    """Three requests a call pass 4 at 6: the job's check on k x pool fails."""
    first, last, k, admitted = serve.backlog_window(
        STEP_ENDS, [(t, 3) for t, _ in ADMISSIONS], 3.2, 4, 1.0)
    assert (k, admitted) == (1, 6) and admitted != k * 4


@pytest.mark.parametrize("seed", [101, 2_147_483_747, 3_000_000_203])
def test_any_pool_of_consecutive_requests_is_one_multiset(seed):
    mix = json.load(open(os.path.join(BENCH, "traffic", "rollout-batch.json")))
    cell = json.load(open(os.path.join(BENCH, "workloads", "pythia-1.4b.rollout-batch.json")))
    pool = mix["pool"]
    assert pool == cell["engine"]["num_slots"]
    assert mix["rehearse"]["pool"] == cell["rehearse_engine"]["num_slots"]
    bucket = cell["engine"]["prompt_bucket"]
    lens = traffic.lengths(mix["prompt_len"], pool, np.random.default_rng(seed))
    widths = [serve.round_up(n, bucket) for n in lens]
    want = collections.Counter(
        serve.round_up(n, bucket)
        for n in traffic.lengths(mix["prompt_len"], pool, np.random.default_rng(1)))
    for start in range(0, 3 * pool, 7):
        assert collections.Counter(widths[i % pool] for i in range(start, start + pool)) == want
    # the issue's shares of the four width buckets, at 64 mid-quantiles
    assert [want[w] for w in (128, 256, 384, 512)] == [16, 28, 12, 8]


@pytest.mark.parametrize("stat, want, moved", [
    ("mean", 1010.0 / 7, 950.0 / 7),  # moves by a seventh of the gap between the humps
    ("median", 170.0, 110.0),         # flips from one hump to the other
])
def test_series_stat_on_a_two_humped_series(stat, want, moved):
    """Steps behind no prefill take 0.11 s, steps behind one 0.17 s, about
    half each. One step changing humps flips the median; only the mean times
    the count is the window."""
    params = {"series": "engine.step_s", "stat": stat, "scale": 1000.0}
    for n_low, value in [(3, want), (4, moved)]:
        m = {"series": {"engine.step_s": [0.11] * n_low + [0.17] * (7 - n_low)}, "constants": {}}
        assert series_stat.read(m, params, None) == pytest.approx(value)


def test_series_stat_with_nothing_to_read_returns_nothing():
    assert series_stat.read({"series": {"engine.decode_step_s": []}},
                            {"series": "engine.decode_step_s", "stat": "median", "scale": 1e3},
                            None) is None


def _rehearse(seed, seconds):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           "pythia-1.4b.rollout-batch", "--seed", str(seed), "--seconds", str(seconds),
           "--trace", "0", "--rehearse-cpu"]
    proc = subprocess.run(cmd, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout


@pytest.fixture(scope="module")
def windows():
    """(seed, seconds) -> what the run logged of its window."""
    out = {}
    for seed, seconds in [(101, 1.0), (3_000_000_203, 2.5)]:
        text = _rehearse(seed, seconds)
        admitted, passes = re.search(
            r"check requests admitted inside the window against (\d+) passes over the pool: "
            r"(\d+) \(limit == \d+\) ok", text).group(2, 1)
        line = re.search(r"\[bench\] window ([\d.]+) s, (\d+) passes: (\d+) tokens from (\d+) steps "
                         r"\((\d+) of them.*by \(rows, width\) (\{.*?\})", text)
        out[(seed, seconds)] = {
            "admitted": int(admitted), "passes": int(passes), "window_s": float(line.group(1)),
            "tokens": int(line.group(3)), "steps": int(line.group(4)),
            "short_steps": int(line.group(5)), "by_shape": ast.literal_eval(line.group(6))}
    return out


def test_rehearsed_windows_hold_whole_passes(windows):
    pool = json.load(open(os.path.join(BENCH, "traffic", "rollout-batch.json")))["rehearse"]["pool"]
    for (seed, seconds), w in windows.items():
        assert w["admitted"] == w["passes"] * pool
        assert w["window_s"] >= seconds
        assert w["short_steps"] == 0 and w["tokens"] == w["steps"] * pool


def test_two_seeds_windows_hold_the_same_widths_a_pass(windows):
    a, b = windows.values()
    assert a["passes"] != b["passes"]  # two lengths
    per_pass = [{shape: n / w["passes"] for shape, n in w["by_shape"].items()} for w in (a, b)]
    assert per_pass[0] == per_pass[1]
    assert all(float(n).is_integer() for n in per_pass[0].values())
