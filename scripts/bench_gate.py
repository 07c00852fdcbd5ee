#!/usr/bin/env python
"""Continuous bench regression gate: run bench.py, diff the stdout JSON
against the committed BENCH_trajectory.json, fail loudly on regression.

Chip results live in the driver's PERF_LEDGER.jsonl and appear once per
PR; between them a gross regression (a recompile per cycle, a serialized
pipeline) would go unseen. This gate is the CPU tripwire for that:
tier-1 CI runs `bench_gate.py --smoke` on every push,
compares the measured smoke metrics against the committed trajectory
with generous per-metric tolerances (CPU CI boxes are noisy — the gate
is a tripwire for *gross* regressions like an accidental recompile per
cycle or a serialized pipeline, not a 5% microbenchmark), and exits
nonzero naming the regressed metric.

Usage:
    python scripts/bench_gate.py --smoke            # gate (CI)
    python scripts/bench_gate.py --smoke --update   # (re)seed trajectory
    python scripts/bench_gate.py --smoke --runs 3   # best-of-3

The committed trajectory also keeps an append-only `history` of every
--update, so the smoke numbers form a trajectory over PRs rather than a
single overwritten point.

Exit codes: 0 pass / trajectory updated; 1 regression (metric named on
stdout); 2 infrastructure problems (bench crashed, missing trajectory,
unparseable output).
"""

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_TRAJECTORY = os.path.join(REPO, "BENCH_trajectory.json")

# metric name -> spec dict:
#   key            — key in bench.py's stdout JSON
#   direction      — "higher_better" (throughput-style; fails when the
#                    value drops more than `max_regression` below the
#                    baseline) or "lower_better" (count-style; fails
#                    when the value rises more than `max_increase`
#                    above the baseline)
#   max_regression — higher_better tolerance. 0.6 = fail only below 40%
#                    of the committed baseline: wide enough for
#                    shared-CPU CI jitter on the ~0.1s smoke timing
#                    window, narrow enough to catch an injected
#                    per-cycle stall or a lost overlap schedule (both
#                    cut smoke throughput by >2x).
#   max_increase   — lower_better tolerance. 0.0 = ANY increase fails
#                    (a compile landing inside the timed window is a
#                    retrace storm — deterministic, not CI noise); the
#                    HBM watermark gets 50% headroom because the
#                    live-arrays fallback on CPU CI jitters with GC
#                    timing, while a leaked params copy doubles it.
# Gating aggregates across --runs with best-of: max for higher_better,
# min for lower_better (both absorb one-off CI hiccups).
GATED_METRICS: Dict[str, Any] = {
    "ppo_samples_per_sec_per_chip": {"key": "value", "max_regression": 0.6},
    "tokens_per_sec_per_chip": {"key": "tokens_per_sec_per_chip",
                                "max_regression": 0.6},
    "mfu_estimate": {"key": "mfu_estimate", "max_regression": 0.6},
    "serving_decode_tokens_per_s": {"key": "serving_decode_tokens_per_s",
                                    "max_regression": 0.6},
    "timed_window_compiles": {"key": "timed_window_compiles",
                              "direction": "lower_better",
                              "max_increase": 0.0},
    "peak_hbm_bytes": {"key": "peak_hbm_bytes",
                       "direction": "lower_better",
                       "max_increase": 0.5},
}

# a baseline below this is below the metric's own rounding granularity
# (smoke-CPU mfu_estimate rounds to 1e-4) — ratios against it are noise,
# so such metrics are reported as skipped rather than gated
MIN_MEANINGFUL_BASELINE = 1e-3


def extract_metrics(bench_stdout: str) -> Dict[str, float]:
    """Pull the gated metrics out of bench.py's single-line stdout JSON
    (scans from the last line backwards so stray prints don't break
    parsing)."""
    payload: Optional[Dict[str, Any]] = None
    for line in reversed(bench_stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            payload = json.loads(line)
            break
        except ValueError:
            continue
    if payload is None:
        raise ValueError("no JSON object found in bench output")
    out: Dict[str, float] = {}
    for metric, spec in GATED_METRICS.items():
        if spec["key"] in payload:
            out[metric] = float(payload[spec["key"]])
    if not out:
        raise ValueError(f"bench JSON carried none of the gated keys: "
                         f"{sorted(s['key'] for s in GATED_METRICS.values())}")
    return out


def compare(baseline: Dict[str, Any],
            current: Dict[str, float]) -> List[Dict[str, Any]]:
    """Diff `current` against the trajectory's `metrics` section; return
    one failure record per regressed metric (empty list = gate passes).
    A metric missing from either side is skipped — the gate only judges
    what both sides measured. higher_better metrics fail on a drop past
    `max_regression`; lower_better (count-type) metrics fail on a rise
    past `max_increase` — with a zero baseline (the steady state for
    timed-window compiles), any nonzero measurement fails."""
    failures: List[Dict[str, Any]] = []
    base_metrics = baseline.get("metrics", {})
    for metric, spec in GATED_METRICS.items():
        base = base_metrics.get(metric)
        if base is None or metric not in current:
            continue
        base_value = float(base["value"])
        cur = current[metric]
        direction = base.get("direction",
                             spec.get("direction", "higher_better"))
        if direction == "lower_better":
            allowed = float(base.get("max_increase",
                                     spec.get("max_increase", 0.0)))
            ceiling = base_value * (1.0 + allowed)
            if cur > ceiling:
                failures.append({
                    "metric": metric,
                    "baseline": base_value,
                    "current": cur,
                    "direction": "lower_better",
                    "allowed_max": round(ceiling, 4),
                })
            continue
        allowed = float(base.get("max_regression",
                                 spec.get("max_regression", 0.6)))
        if base_value < float(base.get("min_meaningful",
                                       MIN_MEANINGFUL_BASELINE)):
            sys.stderr.write(
                f"[bench-gate] skipping {metric}: baseline {base_value:g} "
                f"below meaningful floor\n")
            continue
        ratio = cur / base_value
        if ratio < (1.0 - allowed):
            failures.append({
                "metric": metric,
                "baseline": base_value,
                "current": cur,
                "ratio": round(ratio, 4),
                "allowed_min_ratio": round(1.0 - allowed, 4),
            })
    return failures


def run_bench(smoke: bool, timeout_s: float) -> Dict[str, float]:
    cmd = [sys.executable, os.path.join(REPO, "bench.py")]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout_s,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:] + "\n")
        raise RuntimeError(f"bench.py exited {proc.returncode}")
    return extract_metrics(proc.stdout)


def load_trajectory(path: str) -> Optional[Dict[str, Any]]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def update_trajectory(path: str, current: Dict[str, float],
                      smoke: bool) -> None:
    traj = load_trajectory(path) or {"history": []}
    traj["cmd"] = ("JAX_PLATFORMS=cpu python bench.py"
                   + (" --smoke" if smoke else ""))
    traj["metrics"] = {}
    for metric in current:
        spec = GATED_METRICS[metric]
        if spec.get("direction") == "lower_better":
            traj["metrics"][metric] = {
                "value": current[metric],
                "max_increase": spec["max_increase"],
                "direction": "lower_better",
            }
        else:
            traj["metrics"][metric] = {
                "value": current[metric],
                "max_regression": spec["max_regression"],
                "direction": "higher_better",
            }
    traj.setdefault("history", []).append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "metrics": dict(current),
    })
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(traj, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="run bench.py --smoke (tiny model, 1 cycle)")
    ap.add_argument("--update", action="store_true",
                    help="write the measured metrics as the new baseline "
                         "instead of gating")
    ap.add_argument("--trajectory", default=DEFAULT_TRAJECTORY,
                    help="path to the committed trajectory JSON")
    ap.add_argument("--runs", type=int, default=2,
                    help="bench runs; the BEST value per metric is gated "
                         "(absorbs one-off CI hiccups)")
    ap.add_argument("--timeout-s", type=float, default=480.0,
                    help="per-run subprocess timeout")
    args = ap.parse_args(argv)

    runs: List[Dict[str, float]] = []
    for i in range(max(args.runs, 1)):
        try:
            m = run_bench(args.smoke, args.timeout_s)
        except Exception as e:
            sys.stderr.write(f"[bench-gate] run {i + 1} failed: {e}\n")
            continue
        sys.stderr.write(f"[bench-gate] run {i + 1}: "
                         + json.dumps(m) + "\n")
        runs.append(m)
    if not runs:
        print("BENCH GATE ERROR: every bench run failed")
        return 2
    current = {
        metric: (min if GATED_METRICS[metric].get("direction")
                 == "lower_better" else max)(
            r[metric] for r in runs if metric in r)
        for metric in GATED_METRICS
        if any(metric in r for r in runs)
    }

    if args.update:
        update_trajectory(args.trajectory, current, args.smoke)
        print(json.dumps({"updated": args.trajectory, "metrics": current}))
        return 0

    traj = load_trajectory(args.trajectory)
    if traj is None:
        print(f"BENCH GATE ERROR: no trajectory at {args.trajectory}; "
              f"seed it with: python scripts/bench_gate.py "
              f"{'--smoke ' if args.smoke else ''}--update")
        return 2
    failures = compare(traj, current)
    if failures:
        for f in failures:
            if f.get("direction") == "lower_better":
                print(f"BENCH REGRESSION: {f['metric']} = {f['current']:g} "
                      f"rose above baseline {f['baseline']:g} "
                      f"(allowed <= {f['allowed_max']:g})")
            else:
                print(f"BENCH REGRESSION: {f['metric']} = {f['current']:g} "
                      f"is {f['ratio']:.0%} of baseline {f['baseline']:g} "
                      f"(allowed >= {f['allowed_min_ratio']:.0%})")
        return 1
    print(json.dumps({"bench_gate": "pass", "metrics": current,
                      "baseline": {k: v["value"]
                                   for k, v in traj["metrics"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
