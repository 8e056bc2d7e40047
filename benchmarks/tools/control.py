"""The control of `correct`, on the chip at a cell's own size: the plain
reference computed with float8 (e4m3) operands, the step below the bfloat16
operands the configurations state, put in the program's place. It has to
come out as NOT correct. The benchmark's own runs never run this.

    chiprun --chips 1 -- python3 benchmarks/tools/control.py \\
        --workload serve.internlm2-1.8b.chat --seeds 11,12,13 --seconds 10

Serving: one process runs the cell's window once a seed (short, at the
cell's own load), then reads on the same prompts and served tokens both the
program's widest gap and the control's. Training needs no window: the
reference's three steps in float32 and with float8 operands, compared as
the program would be. Prints one JSON line a seed and a summary line.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sound-from", default=None,
                    help="training: a session's summary.json whose runs "
                         "printed the float32 reference of these seeds")
    args = ap.parse_args()
    from benchmarks.harness import common, traffic_gen

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = common.load_json(os.path.join(ROOT, entry["file"]))
    traffic = common.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    devices, peaks = common.require_chips(ROOT, int(cell["chips"]))
    common.place_compile_cache(ROOT)
    adapter = common.load_model_file(ROOT, "models", config["model"])
    ref = common.load_model_file(ROOT, "reference", config["model"])
    counter = common.CompileCounter()
    known = {}
    if args.sound_from:
        for run in common.load_json(args.sound_from):
            for note in run["notes"]:
                if note.startswith("[reference] "):
                    known[int(run["seed"])] = json.loads(
                        note[len("[reference] "):])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        if traffic["kind"] == "train":
            from benchmarks.harness import train

            hp = adapter.hyperparams(config, "train")
            batch, seq = int(traffic["batch"]), int(traffic["seq"])
            first = traffic_gen.train_tokens(
                hp["vocab_size"], seed, 3 * batch, seq).reshape(
                    3, batch, seq + 1)
            sound = known.get(seed) or train.reference_three_steps(
                ref, hp, seed, first, traffic, devices)
            low = train.reference_three_steps(
                ref, hp, seed, first, traffic, devices,
                quant=ref.fp8_operands)
            row = {"seed": seed, "control": train.compare(low, sound),
                   "limits": traffic["check"]}
        else:
            runner = common.load_module(os.path.join(
                ROOT, "benchmarks", "harness", traffic["kind"] + ".py"),
                "benchmarks_runner_" + traffic["kind"])
            ctx = {"root": ROOT, "cell": cell, "config": config,
                   "traffic": traffic, "seed": seed, "seconds": args.seconds,
                   "trace": False, "devices": devices, "chips": len(devices),
                   "peaks": peaks, "adapter": adapter,
                   "t_start": time.perf_counter(),
                   "compile_counter": counter, "control": ref.fp8_operands,
                   "trace_dir": os.path.join(ROOT, ".bench_trace", "control")}
            rec = runner.run(ctx)
            chk = rec.stamps["check"]
            row = {"seed": seed, "sound_gap": chk["widest_gap"],
                   "control_gap": chk["control_gap"],
                   "tokens": chk["tokens"], "attempted": rec.attempted,
                   "failed": rec.failed,
                   "limit": traffic["check"]["gap_limit"],
                   "sound_per_request": chk["per_request"],
                   "control_per_request": chk["control_per_request"]}
        row["wall_s"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".json"), "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
