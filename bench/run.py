"""The benchmark's one command:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

One process: loads the cell's files by name, makes weights and traffic from
the seed, warms the cell's shapes (set-up), measures for `--seconds`, checks
the program's outputs against the configuration's plain reference, and prints
one JSON object as the last line of standard output. Off a TPU it fails and
names the platform. `--rehearse-cpu` walks the same code at the tiny presets
the files name, on the CPU, and prints no result line.

`--trace 0` measures: the end-to-end metrics, the profiler never started.
`--trace 1` traces a run of its own: a profiler window inside the measured
one, the per-layer metrics and the breakdown, no end-to-end metric.
`--trace 2` does exactly what `--trace 0` does until the measured window has
closed and its numbers are taken, then starts the program's tracing
(`trlx_tpu.observability.tracing`), runs a little more of the same traffic
under it, and prints both kinds of metric in one line.
"""

import argparse
import os
import sys
import time
import types

T_START = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]


def per_layer_names(bench: dict, cell: dict) -> list:
    """What a traced run of the cell reports: the cell file's metrics, then
    every per-layer entry of BENCHMARK.json whose `workloads` names the cell."""
    listed = [e["name"] for e in bench["per_layer"] if cell["name"] in e.get("workloads", ())]
    return list(dict.fromkeys(cell["per_layer"] + listed))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny CPU walk-through of the same code; prints no result")
    parser.add_argument("--control", action="store_true",
                        help="switch on the program's lower-precision path (int8 KV / int8 "
                             "trunk): the run that `correct` has to refuse")
    parser.add_argument("--dump-trace", metavar="PATH",
                        help="with --trace 1 or 2: write the trace's planes, lines and heaviest "
                             "event names as JSON, for whoever writes the next reader")
    args = parser.parse_args()

    from benchlib import device, files, result
    from trlx_tpu.utils import logging as program_logging

    program_logging.set_verbosity(program_logging.WARNING)  # the program logs every chunk at INFO

    bench, cell, config, traffic = files.load_cell(args.workload)
    device.setup_compile_cache()
    info = device.require_device(cell["chips"], args.rehearse_cpu)
    compiles = device.CompileLog()

    def log(msg):
        print(f"[bench] {msg}", flush=True)

    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse_cpu, control=args.control,
        t_start=T_START, compiles=compiles, log=log,
        peaks=None if args.rehearse_cpu else device.peaks_for(info["kind"]))
    log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} trace {args.trace} "
        f"on {info}")
    out = files.load_module(f"jobs/{cell['job']}.py").run(ctx)

    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    breakdown, metrics = None, {}
    if args.trace != 1:
        metrics = {name: out["end_to_end"][name] for name in cell["end_to_end"]
                   if name in out["end_to_end"]}
        missing = [n for n in cell["end_to_end"] if n not in metrics]
        out["checks"].equal(f"end-to-end metrics the cell names but the run could not take {missing}",
                            len(missing), 0)
    if args.trace:
        m = out["measurements"]
        if m.get("trace") is None:
            raise SystemExit("[bench] FAIL: a traced run took no trace")
        reduce = files.load_module("trace/reduce.py")
        if args.dump_trace and args.rehearse_cpu:
            reduce.dump(m["trace"], args.dump_trace)
        if args.rehearse_cpu and not reduce.device_planes(m["trace"]):
            log(f"REHEARSAL: a CPU trace has no device plane; host spans seen: "
                f"{sorted({n for n, _, _ in reduce.host_spans(m['trace'])})}")
            return 0 if out["checks"].ok else 1
        if args.dump_trace:
            reduce.dump(m["trace"], args.dump_trace)
        summary = reduce.summary(m["trace"])
        info = {**info, "busy_s": summary["busy_s"], "window_s": summary["window_s"]}
        breakdown = summary["breakdown"]
        for name in per_layer_names(bench, cell):
            spec = files.load_json(f"metrics/{name}.json")
            value = files.load_module(f"metrics/readers/{spec['reader']}.py").read(
                m, spec["params"], ctx)
            units[name] = spec["unit"]
            if value is None:
                log(f"per-layer metric {name}: nothing to read, left out")
            else:
                metrics[name] = value
    log(f"set-up {out['end_to_end']['setup_s']:.1f} s; compiles: {compiles.summary()}")
    out["checks"].to_stderr()
    if args.rehearse_cpu:
        log(f"REHEARSAL on the CPU finished (checks ok: {out['checks'].ok}): not a result; "
            f"metric names that a chip run would report: {sorted(metrics)}")
        return 0 if out["checks"].ok else 1
    info["memory_peak_bytes"] = device.memory_peak_bytes()
    result.print_result(out["checks"].ok, out["attempted"], out["failed"], metrics, units,
                        info, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
