"""Hyperparameter sweeps over dotted-key param spaces.

Parity: `python -m trlx.sweep --config configs/sweeps/ppo_sweep.yml
examples/ppo_sentiments.py` (reference trlx/sweep.py). The reference builds
a Ray Tune search space from a yaml file ({strategy, values} per dotted
config key, sweep.py:17-100) and fans trials out over GPU workers with
results reported to W&B. TPU-native rebuild: same yaml contract, trials run
as subprocesses (fresh XLA state, crash isolation); each trial invokes the
example script with a JSON hparams argv (the same contract the reference
examples use: `json.loads(sys.argv[1])`), metrics land in JSONL via the
builtin tracker, and the sweep ends with a ranked table +
sweep_results.json instead of a W&B report.

Fan-out (the Ray Tune worker role): `tune_config.num_workers` runs that
many trials CONCURRENTLY in slot-based subprocesses; slot s overlays
`tune_config.worker_env[s]` onto its trials' environment — the dispatch
hook for separate accelerators/slices (point each slot at its own slice
via TPU_VISIBLE_DEVICES or coordinator env vars). The default stays 1:
one TPU chip is one exclusive device, so concurrent local trials would
only contend.

Usage:
    python -m trlx_tpu.sweep --config sweep.yml examples/randomwalks/ppo_randomwalks.py

sweep.yml:
    tune_config:
        mode: max
        metric: reward/mean
        search_alg: random        # random | grid | tpe (model-based)
        num_samples: 8            # trials (ignored for grid)
        num_workers: 2            # concurrent trial slots (default 1)
        worker_env:               # optional per-slot env overlays
            - {TPU_VISIBLE_DEVICES: "0"}
            - {TPU_VISIBLE_DEVICES: "1"}
    method.init_kl_coef:
        strategy: loguniform
        values: [0.0001, 0.1]
    optimizer.kwargs.lr:
        strategy: choice
        values: [1.0e-4, 3.0e-4]
"""

import argparse
import itertools
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np
import yaml

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


# ---------------------------------------------------------------------------
# Param space (reference sweep.py:17-100, sans the q* quantized variants'
# ray objects — sampling happens right here)
# ---------------------------------------------------------------------------


def sample_strategy(value: Dict[str, Any], rng: np.random.Generator):
    strategy, values = value["strategy"], value["values"]
    if strategy == "uniform":
        return float(rng.uniform(values[0], values[1]))
    if strategy == "quniform":
        lo, hi, q = values
        return float(np.round(rng.uniform(lo, hi) / q) * q)
    if strategy == "loguniform":
        lo, hi = values[:2]
        return float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
    if strategy == "qloguniform":
        lo, hi, q = values[:3]
        return float(np.round(np.exp(rng.uniform(np.log(lo), np.log(hi))) / q) * q)
    if strategy == "randn":
        mean, sd = values
        return float(rng.normal(mean, sd))
    if strategy == "qrandn":
        mean, sd, q = values
        return float(np.round(rng.normal(mean, sd) / q) * q)
    if strategy == "randint":
        return int(rng.integers(values[0], values[1]))
    if strategy == "qrandint":
        lo, hi, q = values
        return int(np.round(rng.integers(lo, hi) / q) * q)
    if strategy == "lograndint":
        lo, hi = values[:2]
        return int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
    if strategy in ("choice", "grid", "grid_search"):
        return values[rng.integers(len(values))]
    raise ValueError(f"Unknown search strategy '{strategy}'")


def enumerate_grid(param_space: Dict[str, Dict]) -> List[Dict[str, Any]]:
    """Cartesian product over every key's `values` (grid mode)."""
    keys = list(param_space)
    value_lists = [param_space[k]["values"] for k in keys]
    return [dict(zip(keys, combo)) for combo in itertools.product(*value_lists)]


def sample_trials(
    param_space: Dict[str, Dict], search_alg: str, num_samples: int, seed: int = 0
) -> List[Dict[str, Any]]:
    if search_alg in ("grid", "grid_search"):
        return enumerate_grid(param_space)
    if search_alg != "random":
        raise ValueError(
            f"search_alg '{search_alg}' unsupported here (random | grid); "
            "model-based search goes through make_searcher"
        )
    rng = np.random.default_rng(seed)
    return [
        {k: sample_strategy(v, rng) for k, v in param_space.items()}
        for _ in range(num_samples)
    ]


# ---------------------------------------------------------------------------
# Searchers (the reference's Ray Tune search_alg role, sweep.py:103-130 —
# bayesopt/BOHB there; TPE here, dependency-free)
# ---------------------------------------------------------------------------


class RandomSearcher:
    """suggest() ~ the prior; observations ignored."""

    def __init__(self, param_space: Dict[str, Dict], num_samples: int, seed: int = 0):
        self.space = param_space
        self.num_samples = num_samples
        self.rng = np.random.default_rng(seed)

    def suggest(self) -> Dict[str, Any]:
        return {k: sample_strategy(v, self.rng) for k, v in self.space.items()}

    def observe(self, hparams: Dict[str, Any], score: float) -> None:
        pass


class GridSearcher:
    def __init__(self, param_space: Dict[str, Dict]):
        self.trials = enumerate_grid(param_space)
        self.num_samples = len(self.trials)
        self._i = 0

    def suggest(self) -> Dict[str, Any]:
        t = self.trials[self._i % len(self.trials)]
        self._i += 1
        return t

    def observe(self, hparams: Dict[str, Any], score: float) -> None:
        pass


_LOG_STRATEGIES = ("loguniform", "qloguniform", "lograndint")
_INT_STRATEGIES = ("randint", "qrandint", "lograndint")


class TPESearcher:
    """Tree-structured Parzen Estimator (Bergstra et al. 2011), per-dim
    independent — the standard Hyperopt formulation, ~100 lines and no
    external packages (the reference reaches for Ray's bayesopt/BOHB,
    trlx/sweep.py:103-130). Completed trials split into a good (top
    `gamma` quantile) and bad set; each continuous dim gets a Gaussian
    KDE per set (log-space for log strategies), each categorical dim a
    Laplace-smoothed histogram; candidates drawn from the good model are
    ranked by the density ratio g(x)/b(x). Until `n_startup` observations
    it falls back to prior sampling. Maximizes `score` — run_sweep
    negates for mode=min. Robust to concurrency: suggest() just uses
    whatever observations exist."""

    def __init__(self, param_space: Dict[str, Dict], num_samples: int,
                 seed: int = 0, gamma: float = 0.25, n_candidates: int = 24,
                 n_startup: Optional[int] = None):
        self.space = param_space
        self.num_samples = num_samples
        self.rng = np.random.default_rng(seed)
        self.gamma = gamma
        self.n_candidates = n_candidates
        self.n_startup = (
            n_startup if n_startup is not None else max(4, num_samples // 4)
        )
        self.obs: List[tuple] = []  # (hparams, score)

    def observe(self, hparams: Dict[str, Any], score: float) -> None:
        if np.isfinite(score):
            self.obs.append((hparams, float(score)))

    def suggest(self) -> Dict[str, Any]:
        if len(self.obs) < self.n_startup:
            return {k: sample_strategy(v, self.rng) for k, v in self.space.items()}
        ranked = sorted(self.obs, key=lambda o: o[1], reverse=True)
        n_good = max(1, int(np.ceil(self.gamma * len(ranked))))
        good = [h for h, _ in ranked[:n_good]]
        bad = [h for h, _ in ranked[n_good:]] or good
        return {
            k: self._suggest_dim(k, spec, good, bad)
            for k, spec in self.space.items()
        }

    def _suggest_dim(self, key: str, spec: Dict, good: List[Dict], bad: List[Dict]):
        strategy, values = spec["strategy"], spec["values"]
        if strategy in ("choice", "grid", "grid_search"):
            def pdf(v, group):
                hits = sum(1 for h in group if h[key] == v)
                return (hits + 1.0) / (len(group) + len(values))

            gv = [h[key] for h in good]
            best = max(values, key=lambda v: pdf(v, good) / pdf(v, bad))
            # exploration: an rng draw from the good histogram half the time
            if gv and self.rng.random() < 0.5:
                return gv[self.rng.integers(len(gv))]
            return best

        log = strategy in _LOG_STRATEGIES
        to_x = (lambda v: np.log(v)) if log else (lambda v: float(v))
        from_x = (lambda x: float(np.exp(x))) if log else (lambda x: float(x))
        if strategy in ("randn", "qrandn"):
            mean, sd = values[:2]
            lo, hi = mean - 4 * sd, mean + 4 * sd
        elif strategy in _INT_STRATEGIES:
            # the prior (rng.integers / exp-uniform int) treats the upper
            # bound as EXCLUSIVE — clip suggestions to values[1] - 1 so
            # TPE can never propose an out-of-space integer
            lo, hi = to_x(values[0]), to_x(max(values[1] - 1, values[0]))
        else:
            lo, hi = to_x(values[0]), to_x(values[1])
        g = np.asarray([to_x(h[key]) for h in good])
        b = np.asarray([to_x(h[key]) for h in bad])
        span = max(hi - lo, 1e-12)

        def per_point_bw(xs):
            # Hyperopt's heuristic: each kernel's width is the distance to
            # its nearest sorted neighbors — wide in sparse regions
            # (exploration), narrow in dense ones (exploitation)
            if len(xs) == 1:
                return np.asarray([span])
            order = np.argsort(xs)
            d = np.diff(xs[order])
            widths = np.maximum(
                np.concatenate([d[:1], d]), np.concatenate([d, d[-1:]])
            )
            bw = np.empty_like(widths)
            bw[order] = widths
            # adaptive floor: near-duplicate observations must not collapse
            # their kernels (an exploitation death spiral — every candidate
            # lands on the same point); shrink the floor only as real
            # coverage grows
            return np.clip(bw, span / (2.0 * len(xs)), span)

        bw_g, bw_b = per_point_bw(g), per_point_bw(b)
        # candidates: mostly good-KDE draws, a quarter from the prior so a
        # lucky early cluster cannot lock the search out of better basins
        n_prior = max(1, self.n_candidates // 4)
        ci = self.rng.integers(len(g), size=self.n_candidates - n_prior)
        cand = np.concatenate([
            np.clip(g[ci] + self.rng.normal(0, 1, len(ci)) * bw_g[ci], lo, hi),
            self.rng.uniform(lo, hi, n_prior),
        ])

        def density(xs, bw, x):
            # KDE mixed with the uniform prior as one pseudo-component
            # (Hyperopt's formulation): nonzero tails everywhere, so
            # prior-drawn candidates compete on real density ratios
            kde = (
                np.exp(-0.5 * ((x[:, None] - xs[None, :]) / bw[None, :]) ** 2)
                / (bw[None, :] * np.sqrt(2 * np.pi))
            ).sum(1)
            return (kde + 1.0 / span) / (len(xs) + 1)

        ratio = density(g, bw_g, cand) / density(b, bw_b, cand)
        x = float(cand[int(np.argmax(ratio))])
        v = from_x(x)
        if strategy in ("quniform", "qloguniform", "qrandn", "qrandint"):
            q = values[2]
            v = float(np.round(v / q) * q)
        if strategy in _INT_STRATEGIES:
            v = int(np.round(v))
        return v


def make_searcher(param_space: Dict[str, Dict], search_alg: str,
                  num_samples: int, seed: int = 0):
    if search_alg in ("grid", "grid_search"):
        return GridSearcher(param_space)
    if search_alg == "random":
        return RandomSearcher(param_space, num_samples, seed)
    if search_alg == "tpe":
        return TPESearcher(param_space, num_samples, seed)
    raise ValueError(
        f"search_alg '{search_alg}' unsupported (random | grid | tpe)"
    )


# ---------------------------------------------------------------------------
# Trial execution + metric harvesting
# ---------------------------------------------------------------------------


def read_metric(logging_dir: str, metric: str, mode: str) -> float:
    """Best (per `mode`) value of `metric` across every JSONL run file in
    the trial's logging dir."""
    best = None
    for fname in os.listdir(logging_dir):
        if not fname.endswith(".metrics.jsonl"):
            continue
        with open(os.path.join(logging_dir, fname)) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if metric in row:
                    v = float(row[metric])
                    if best is None or (v > best if mode == "max" else v < best):
                        best = v
    return best if best is not None else float("-inf" if mode == "max" else "inf")


def launch_trial(script: str, hparams: Dict[str, Any], trial_dir: str, env=None):
    """Start one trial subprocess (fresh XLA/JAX state, crash isolation —
    the role Ray workers play in the reference). Returns (Popen, stdout
    file handle)."""
    os.makedirs(trial_dir, exist_ok=True)
    hparams = dict(hparams)
    hparams["train.logging_dir"] = trial_dir
    hparams["train.tracker"] = "jsonl"
    with open(os.path.join(trial_dir, "hparams.json"), "w") as f:
        json.dump(hparams, f, indent=2)
    out = open(os.path.join(trial_dir, "stdout.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, script, json.dumps(hparams)],
        stdout=out, stderr=subprocess.STDOUT, env=env,
    )
    return proc, out




def run_sweep(
    script: str,
    config: Dict[str, Any],
    output_dir: str = "sweep_results",
    seed: int = 0,
    env: Dict[str, str] = None,
    num_workers: int = None,
) -> Dict[str, Any]:
    tune_config = dict(config.pop("tune_config"))
    metric = tune_config["metric"]
    mode = tune_config.get("mode", "max")
    search_alg = tune_config.get("search_alg", "random")
    searcher = make_searcher(
        config, search_alg, int(tune_config.get("num_samples", 8)), seed=seed
    )
    n_trials = searcher.num_samples
    sign = 1.0 if mode == "max" else -1.0  # searchers maximize

    if num_workers is None:
        num_workers = int(tune_config.get("num_workers", 1))
    num_workers = max(num_workers, 1)
    worker_env: List[Dict[str, str]] = tune_config.get("worker_env") or []

    stamp = time.strftime("%Y%m%d-%H%M%S")
    sweep_dir = os.path.join(output_dir, f"sweep-{stamp}")
    os.makedirs(sweep_dir, exist_ok=True)
    logger.info(
        f"Sweep: {n_trials} trials ({search_alg}) of {script} -> {sweep_dir} "
        f"({num_workers} worker slot(s))"
    )

    # Slot-based fan-out (the distributed-trial role Ray Tune plays in the
    # reference, trlx/sweep.py:267-348): up to `num_workers` trials run
    # concurrently; slot s inherits worker_env[s] on top of `env`, which is
    # how trials dispatch onto separate TPU slices/hosts (point each slot's
    # env at a different slice — e.g. TPU_VISIBLE_DEVICES, or
    # COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID for remote launchers).
    # num_workers=1 is the single-chip default: a chip is one exclusive
    # device, so concurrent local trials would only contend. Trials are
    # PROPOSED lazily so a model-based searcher (tpe) conditions each
    # suggestion on every completed observation.
    results = []
    launched = 0
    running: Dict[int, Any] = {}  # slot -> (i, hparams, proc, out, trial_dir)
    try:
        while launched < n_trials or running:
            while launched < n_trials and len(running) < num_workers:
                slot = next(s for s in range(num_workers) if s not in running)
                i, hparams = launched, searcher.suggest()
                launched += 1
                trial_dir = os.path.join(sweep_dir, f"trial_{i:03d}")
                trial_env = dict(env) if env is not None else dict(os.environ)
                if slot < len(worker_env):
                    trial_env.update({k: str(v) for k, v in worker_env[slot].items()})
                logger.info(f"[trial {i + 1}/{n_trials} @ slot {slot}] {hparams}")
                proc, out = launch_trial(script, hparams, trial_dir, env=trial_env)
                running[slot] = (i, hparams, proc, out, trial_dir)
            for slot in list(running):
                i, hparams, proc, out, trial_dir = running[slot]
                code = proc.poll()
                if code is None:
                    continue
                out.close()
                del running[slot]
                if code != 0:
                    # e.g. the trial could not get a chip this or another
                    # process holds: it exits within seconds and says so
                    with open(os.path.join(trial_dir, "stdout.log"), errors="replace") as f:
                        tail = f.read()[-400:].strip()
                    logger.error(f"[trial {i + 1}/{n_trials}] exited with code {code}: {tail}")
                score = read_metric(trial_dir, metric, mode)
                searcher.observe(hparams, sign * score)
                results.append({
                    "trial": i, "hparams": hparams, "returncode": code, metric: score,
                })
                logger.info(f"[trial {i + 1}/{n_trials}] {metric} = {score}")
            if running:
                time.sleep(0.5)
    finally:
        # never orphan trial subprocesses (they may hold TPU slices) or
        # leak their stdout handles on an exception/KeyboardInterrupt
        for i, hparams, proc, out, trial_dir in running.values():
            if proc.poll() is None:
                logger.warning(f"terminating trial {i} (sweep aborted)")
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()  # reap: no zombies from a long-lived caller
            out.close()
    results.sort(key=lambda r: r["trial"])
    if results and all(r["returncode"] != 0 for r in results):
        raise RuntimeError(
            f"all {len(results)} trials failed; see trial_*/stdout.log under {sweep_dir}"
        )

    reverse = mode == "max"
    ranked = sorted(results, key=lambda r: r[metric], reverse=reverse)
    summary = {
        "script": script,
        "metric": metric,
        "mode": mode,
        "search_alg": search_alg,
        "best": ranked[0] if ranked else None,
        "results": ranked,
    }
    with open(os.path.join(sweep_dir, "sweep_results.json"), "w") as f:
        json.dump(summary, f, indent=2)
    write_report(sweep_dir, summary, config, results)

    _print_table(ranked, metric)
    return summary


def write_report(sweep_dir: str, summary: Dict[str, Any],
                 param_space: Dict[str, Dict], results: List[Dict]) -> str:
    """Self-contained markdown sweep report (the reference ends its sweeps
    with a W&B report built by create_report, trlx/sweep.py:222-265; this
    one needs no service): header, best trial, ranked table,
    incremental-best curve, and a per-parameter analysis comparing the
    top-quartile trials' parameter range against the searched space."""
    metric, mode = summary["metric"], summary["mode"]
    ranked = summary["results"]
    lines = [
        f"# Sweep report — `{os.path.basename(summary['script'])}`",
        "",
        f"- metric: **{metric}** ({mode})",
        f"- search: {summary['search_alg']}, {len(results)} trials",
        f"- generated: {time.strftime('%Y-%m-%d %H:%M:%S')}",
        "",
        "## Best trial",
        "",
    ]
    if summary["best"]:
        best = summary["best"]
        lines += [
            f"`{metric} = {best[metric]:.6g}` (trial {best['trial']})",
            "",
            "```json",
            json.dumps(best["hparams"], indent=2),
            "```",
            "",
        ]
    lines += ["## Ranked trials", "",
              f"| rank | trial | {metric} | hparams |",
              "|---|---|---|---|"]
    for rank, r in enumerate(ranked[:20]):
        lines.append(
            f"| {rank} | {r['trial']} | {r[metric]:.6g} | "
            f"`{json.dumps(r['hparams'])}` |"
        )

    # incremental best over launch order
    lines += ["", "## Incremental best", "", "| trial | best so far |", "|---|---|"]
    by_launch = sorted(results, key=lambda r: r["trial"])
    best_so_far = None
    better = (lambda a, b: a > b) if mode == "max" else (lambda a, b: a < b)
    for r in by_launch:
        v = r[metric]
        if np.isfinite(v) and (best_so_far is None or better(v, best_so_far)):
            best_so_far = v
        lines.append(f"| {r['trial']} | {best_so_far if best_so_far is not None else '—'} |")

    # per-parameter: top-quartile range vs searched space
    n_top = max(1, len(ranked) // 4)
    top = ranked[:n_top]
    lines += ["", f"## Parameter analysis (top {n_top} trial(s))", "",
              "| param | strategy | searched | top-quartile |",
              "|---|---|---|---|"]
    for key, spec in param_space.items():
        vals = [r["hparams"][key] for r in top if key in r["hparams"]]
        if not vals:
            continue
        if spec["strategy"] in ("choice", "grid", "grid_search"):
            from collections import Counter

            counts = Counter(vals)
            desc = ", ".join(f"{v}×{c}" for v, c in counts.most_common())
        else:
            desc = f"[{min(vals):.4g}, {max(vals):.4g}]"
        lines.append(
            f"| `{key}` | {spec['strategy']} | `{spec['values']}` | {desc} |"
        )
    path = os.path.join(sweep_dir, "sweep_report.md")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    logger.info(f"Sweep report: {path}")
    return path


def _print_table(ranked: List[Dict], metric: str, max_rows: int = 20):
    try:
        from rich.console import Console
        from rich.table import Table

        table = Table("rank", "trial", metric, "hparams", title="Sweep results")
        for rank, r in enumerate(ranked[:max_rows]):
            table.add_row(
                str(rank), str(r["trial"]), f"{r[metric]:.5g}", json.dumps(r["hparams"])
            )
        Console().print(table)
    except ImportError:
        for rank, r in enumerate(ranked[:max_rows]):
            logger.info(f"#{rank} trial={r['trial']} {metric}={r[metric]:.5g} {r['hparams']}")


def main():
    parser = argparse.ArgumentParser(
        description="Sweep hyperparameters of an example script "
        "(reference: python -m trlx.sweep)"
    )
    parser.add_argument("script", type=str, help="Path to the example script")
    parser.add_argument("--config", type=str, required=True, help="Param-space yaml")
    parser.add_argument("--output-dir", type=str, default="sweep_results")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--num-workers", type=int, default=None,
        help="Concurrent trial slots (default: tune_config.num_workers or 1; "
        "pair with tune_config.worker_env to dispatch slots onto separate "
        "TPU slices)",
    )
    args = parser.parse_args()

    with open(args.config) as f:
        config = yaml.safe_load(f)
    run_sweep(args.script, config, args.output_dir, args.seed,
              num_workers=args.num_workers)


if __name__ == "__main__":
    main()
