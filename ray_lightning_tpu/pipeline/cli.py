"""``python -m ray_lightning_tpu perf`` — the hot-loop overlap proof.

Runs the CPU-measurable prefetch/warm-start comparison
(pipeline/overlap.py) and prints ONE structured JSON line. ``--smoke``
is the format.sh gate: a slow-loader run must show pipeline occupancy
> 0 (the prefetcher demonstrably kept batches resident ahead of the
step) — exit 1 otherwise. docs/PERFORMANCE.md explains the numbers.
"""
from __future__ import annotations

import argparse
import json
import sys


def add_perf_parser(sub) -> None:
    p = sub.add_parser(
        "perf",
        help="measure the device-prefetch overlap win + warm-start "
             "compile metrics with a synthetic slow loader (CPU-safe)")
    p.add_argument("--steps", type=int, default=40,
                   help="timed optimizer steps per leg")
    p.add_argument("--depth", type=int, default=2,
                   help="prefetch buffer depth for the overlapped leg")
    p.add_argument("--delay-ms", type=float, default=None,
                   help="synthetic per-batch loader delay; default "
                        "calibrates to the measured step time")
    p.add_argument("--cache-dir", default=None,
                   help="persistent compile cache dir for the "
                        "warm-start legs (default: jax's configured one)")
    p.add_argument("--smoke", action="store_true",
                   help="gate mode: exit 1 unless pipeline occupancy > 0")
    # parses into the SAME namespace as the parent --json (see plan_p)
    p.add_argument("--json", action="store_true", dest="as_json",
                   default=argparse.SUPPRESS)


def run_perf(args) -> int:
    from ray_lightning_tpu.pipeline.overlap import measure_prefetch_overlap

    result = measure_prefetch_overlap(
        steps=args.steps,
        depth=args.depth,
        delay_s=(args.delay_ms / 1e3 if args.delay_ms is not None else None),
        cache_dir=args.cache_dir,
    )
    print(json.dumps(result), flush=True)
    if args.smoke and result["pipeline_occupancy"] <= 0.0:
        print("perf smoke FAILED: prefetch pipeline occupancy is 0 — the "
              "prefetcher never had a batch resident ahead of the step",
              file=sys.stderr)
        return 1
    return 0
