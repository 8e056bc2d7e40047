"""Hot-loop overlap: keep the device dispatch queue non-empty.

The fit loop pays three host-blocking costs the hardware never asked
for: the per-step ``device_put`` of the next batch, the cold ``jax.jit``
compile on every (re)start, and the all-ranks stall of a blocking
checkpoint. This package removes them (docs/PERFORMANCE.md):

  * `DevicePrefetcher` — a bounded N-buffer stage that overlaps host
    batch assembly + sharded device placement with the previous step's
    compute, so the jitted step's input is resident when it dispatches;
  * `compile_cache` — AOT ``lower().compile()`` warm start for the
    train/eval steps plus the persistent XLA compilation cache in one
    resolved directory, so restart N recompiles nothing and compile
    time is a first-class metric (`CompileStats`);
  * `overlap` — the CPU-measurable proof harness: a deliberately slow
    synthetic loader must show prefetch hiding the host time (bench.py
    leg, ``python -m ray_lightning_tpu perf --smoke`` format.sh gate).

Async checkpointing — the third overlap — lives with the checkpoint
format itself (checkpoint/io.py `save_checkpoint(block=False)`): a
no-donation device snapshot decouples the write from the donated train
state, and a background finalizer publishes meta.json + digest the
moment the state write commits.
"""
from ray_lightning_tpu.pipeline.compile_cache import (
    CompileStats,
    WarmStep,
    enable_persistent_cache,
    resolve_cache_dir,
)
from ray_lightning_tpu.pipeline.prefetch import (
    DevicePrefetcher,
    PrefetchStats,
)

__all__ = [
    "DevicePrefetcher",
    "PrefetchStats",
    "CompileStats",
    "WarmStep",
    "enable_persistent_cache",
    "resolve_cache_dir",
]
