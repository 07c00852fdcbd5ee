"""Finding the benchmark's files by the names in BENCHMARK.json."""

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
_modules = {}


def load_json(relpath: str) -> dict:
    """A JSON file under bench/, by its path relative to bench/."""
    with open(os.path.join(BENCH_DIR, relpath)) as f:
        return json.load(f)


def load_module(relpath: str):
    """A Python file under bench/ as a module, by path: jobs, references and
    metric readers are found by the name a data file gives, never imported by
    a name written into the harness."""
    if relpath not in _modules:
        path = os.path.join(BENCH_DIR, relpath)
        name = "bench_" + relpath[:-3].replace("/", "_").replace("-", "_").replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None or not os.path.exists(path):
            raise FileNotFoundError(f"no benchmark module at bench/{relpath}")
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
        _modules[relpath] = module
    return _modules[relpath]


def merge(base: dict, over) -> dict:
    """`base` with the nested overrides of `over` (which may be None)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(workload: str):
    """(BENCHMARK.json entry, cell file, configuration file, traffic file)
    for a cell name. A cell that BENCHMARK.json does not list can still be
    run by name while it is being proved: its files are enough."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load_json(f"workloads/{workload}.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is not None:
        for key in ("config", "traffic", "chips"):
            if entry[key] != cell[key]:
                raise ValueError(
                    f"{workload}: BENCHMARK.json says {key}={entry[key]!r}, "
                    f"the cell's file {cell[key]!r}")
    cfg_entry = next((c for c in bench["configs"] if c["name"] == cell["config"]), None)
    cfg_path = cfg_entry["file"] if cfg_entry else f"bench/configs/{cell['config']}.json"
    with open(os.path.join(ROOT, cfg_path)) as f:
        raw = json.load(f)
    # what the benchmark adds sits under `bench`; the published keys beside it
    # are the configuration's `sizes`
    config = {**raw["bench"], "sizes": {k: v for k, v in raw.items() if k != "bench"}}
    traffic = load_json(f"traffic/{cell['traffic']}.json")
    return bench, cell, config, traffic
