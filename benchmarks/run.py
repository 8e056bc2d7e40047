"""Run one cell of `BENCHMARK.json` once, in this process, on the chip(s).

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, as data:
  workload      -> its entry in BENCHMARK.json (config, traffic, chips)
  config        -> benchmarks/configs/<config>.json, whose "model" names the
                   adapter benchmarks/models/<model>.py, the plain reference
                   benchmarks/reference/<model>.py and the tables of leaves
                   and counts benchmarks/tables/<model>.py
  traffic       -> benchmarks/traffic/<traffic>.json, whose "kind" names the
                   runner benchmarks/harness/<kind>.py
  per-layer     -> benchmarks/layer_metrics/<metric>.py, one reader each
There is no CPU mode: without a TPU whose `device_kind` is in
benchmarks/peaks.json the run ends non-zero before any work. The last line
of stdout is the result: one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metric_cells(metric: dict, bench: dict) -> set:
    """The cells a metric is reported in: its own `workloads`, else every
    cell."""
    return set(metric.get("workloads") or
               [w["name"] for w in bench["workloads"]])


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import common

    try:
        return _run(args, root, common)
    except common.BenchError as exc:
        print(f"benchmarks/run.py: {exc}", file=sys.stderr)
        return 2


def _run(args, root: str, common) -> int:
    bdir = os.path.join(root, "benchmarks")
    bench = common.load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise common.BenchError(f"no workload {args.workload!r} in "
                                f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = common.load_json(os.path.join(root, entry["file"]))
    traffic = common.load_json(
        os.path.join(bdir, "traffic", cell["traffic"] + ".json"))

    t_parsed = time.perf_counter()
    devices, peaks = common.require_chips(root, int(cell["chips"]))
    t_chips = time.perf_counter()
    cache_dir = common.place_compile_cache(root)
    adapter = common.load_model_file(root, "models", config["model"])
    runner = common.load_module(
        os.path.join(bdir, "harness", traffic["kind"] + ".py"),
        "benchmarks_runner_" + traffic["kind"])
    common.say("run", workload=cell["name"], seed=args.seed,
               seconds=args.seconds, trace=args.trace,
               platform=devices[0].platform,
               device_kind=repr(devices[0].device_kind),
               chips=len(devices), compile_cache=cache_dir,
               start_to_parsed_s=round(t_parsed - T_START, 2),
               reach_the_chip_s=round(t_chips - t_parsed, 2),
               load_files_s=round(time.perf_counter() - t_chips, 2))
    ctx = {
        "root": root, "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "devices": devices, "chips": len(devices), "peaks": peaks,
        "adapter": adapter, "t_start": T_START,
        "compile_counter": common.CompileCounter(),
        "trace_dir": os.path.join(root, ".bench_trace", cell["name"]),
    }
    rec = runner.run(ctx)

    name = cell["name"]
    metrics = {}
    t_reduce = time.perf_counter()
    if args.trace:
        for m in bench["per_layer"]:
            if name not in _metric_cells(m, bench):
                continue
            reader = common.load_module(
                os.path.join(bdir, "layer_metrics", m["name"] + ".py"),
                "benchmarks_metric_" + m["name"])
            value = reader.reduce(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(rec.end_to_end, setup_s=rec.setup_s)
        for m in bench["end_to_end"]:
            if name in _metric_cells(m, bench) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    import jax

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(rec.memory_peak_bytes)}
    line = {"correct": bool(rec.correct), "attempted": int(rec.attempted),
            "failed": int(rec.failed), "metrics": metrics, "device": device}
    if args.trace:
        from benchmarks.harness import trace as trace_mod

        if rec.trace is None or not rec.trace.devices:
            raise common.BenchError("the traced run recorded no device op")
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = trace_mod.breakdown(rec.trace)
    common.say("done", setup_s=round(rec.setup_s, 3),
               reference_s=round(rec.reference_s, 3),
               reduce_s=round(time.perf_counter() - t_reduce, 3),
               wall_s=round(time.perf_counter() - T_START, 3))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
