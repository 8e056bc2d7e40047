"""A second reading beside the gap that decides `correct`, for a
configuration whose sound gaps lie too near its control's for the margins
the other cells' limits keep (a router's near ties flip a chosen expert
between bfloat16 and float32): the relative error of the program's LOGITS
against the plain reference's, row by row, on a seeded sequence through the
program's own paged prefill path, beside the same for the float8 control.
A continuous number, which a flipped argmax does not move. The benchmark's
own runs never run this; the adapter must offer `program_logits`.

    chiprun --chips 1 -- python3 benchmarks/tools/logit_error.py \\
        --workload serve.dots.vlm1.inst.longdocs --seeds 41,42 --tokens 2048
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _row_errors(got, want):
    import jax.numpy as jnp

    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)
    best = jnp.max(want, axis=-1)
    picked = jnp.take_along_axis(
        want, jnp.argmax(got, axis=-1)[:, None], axis=-1)[:, 0]
    return {"rel_err_median": float(jnp.median(err)),
            "rel_err_p95": float(jnp.percentile(err, 95)),
            "rel_err_max": float(jnp.max(err)),
            "same_first_token_share": float(jnp.mean(
                jnp.argmax(got, -1) == jnp.argmax(want, -1))),
            "widest_gap": float(jnp.max(best - picked))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=2048)
    args = ap.parse_args()
    import numpy as np

    from benchmarks.harness import common, serving

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = common.load_json(os.path.join(ROOT, entry["file"]))
    traffic = common.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    common.require_chips(ROOT, int(cell["chips"]))
    common.place_compile_cache(ROOT)
    adapter = common.load_model_file(ROOT, "models", config["model"])
    ref = common.load_model_file(ROOT, "reference", config["model"])
    hp = adapter.hyperparams(config, "serve")
    engine = traffic["engine"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        tokens = np.random.default_rng(seed).integers(
            0, hp["vocab_size"], args.tokens).astype(np.int32)
        got = np.asarray(adapter.program_logits(
            config, hp, seed, tokens, int(engine["prefill_chunk"]),
            int(engine["block_size"])))
        gc.collect()
        seqs = [(tokens, 0, len(tokens))]
        want = serving.reference_logits(ref, hp, seed, seqs, len(tokens))[0]
        low = serving.reference_logits(ref, hp, seed, seqs, len(tokens),
                                       quant=ref.fp8_operands)[0]
        print(json.dumps({"seed": seed, "tokens": len(tokens),
                          "program": _row_errors(got, want),
                          "control": _row_errors(low, want),
                          "wall_s": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
