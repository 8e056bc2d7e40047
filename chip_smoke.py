"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which holds the chip(s) for its whole run, drives the two
main paths once through the entry points a user calls, at the full 0.5B
width of the repo's Llama (D=2048, 16/8 heads of 128, F=5632, bf16; depth
8) with random weights made from a seed:

  train-1   Trainer.fit(LlamaModule, DataLoader) on one device
  kernels   every Mosaic kernel against its XLA reference, on the chip
  serve-1   ServeDriver with one inline replica answering 16 requests
  train-4   the same job under FSDP(4) and ShardedMesh(fsdp=2, tensor=2)
  serve-4   four inline replicas, one per chip      (4+ devices only)

It has no CPU mode: it exits non-zero before any leg unless jax's default
backend is "tpu", and it catches no leg's exception, so any failed
check ends the process non-zero with a traceback and no result line.
Times are printed as information (compile seconds, step or tick
milliseconds); nothing here is a benchmark and nothing is claimed.

    python chip_smoke.py

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

tests/test_chip_smoke.py imports the leg functions and runs them at
`LlamaConfig.tiny` on the CPU mesh; that rehearsal proves the control
flow, never a device number.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """Everything a leg needs to know about how big to run."""

    model: Dict[str, Any]        # LlamaConfig fields
    seq: int                     # training sequence length
    batch: int                   # global training batch
    train_steps: int
    engine: Dict[str, Any]       # EngineConfig fields
    n_requests: int
    prompt_len: int
    max_new: int
    #: the lane `DecodeEngine` must report for both attention paths
    serve_path: str
    #: the train step must run the Mosaic flash kernel
    expect_mosaic: bool

    @classmethod
    def full(cls) -> "SmokeSize":
        """bench.py `_bench_cfg` width, the default remat + scan layer
        stack, fused CE so the step holds no [B, S, V] logits."""
        return cls(
            model=dict(vocab_size=32768, dim=2048, n_layers=8, n_heads=16,
                       n_kv_heads=8, hidden_dim=5632, max_seq_len=2048,
                       fused_ce=True, ce_chunk_tokens=2048),
            seq=2048, batch=8, train_steps=8,
            engine=dict(capacity=8, block_size=16, blocks_per_slot=64,
                        prefill_chunk=128),
            n_requests=16, prompt_len=128, max_new=64,
            serve_path="paged-pallas", expect_mosaic=True)

    @classmethod
    def tiny(cls) -> "SmokeSize":
        """`LlamaConfig.tiny`: the CPU rehearsal. Its head_dim (16) is
        below what the paged kernels tile, so serving reports the
        reference lane, and nothing compiles through Mosaic."""
        return cls(
            model=dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       remat=False),
            seq=32, batch=8, train_steps=3,
            engine=dict(capacity=4, block_size=4, blocks_per_slot=8,
                        prefill_chunk=8),
            n_requests=6, prompt_len=6, max_new=5,
            serve_path="reference-gather", expect_mosaic=False)


class SmokeFailure(AssertionError):
    """A leg's check did not hold. Raised, never caught here."""


def _require(ok: bool, why: str) -> None:
    """`assert` that survives ``python -O``."""
    if not ok:
        raise SmokeFailure(why)


def _say(leg: str, **fields: Any) -> None:
    """One informational line per leg: the device it ran on comes first,
    as jax reports it."""
    import jax

    d = jax.devices()[0]
    head = (f"[{leg}] platform={d.platform} device_kind={d.device_kind!r} "
            f"devices={len(jax.devices())}")
    print(head + "".join(f" {k}={v}" for k, v in fields.items()), flush=True)


def _median_ms(stamps: List[float]) -> float:
    """Median gap between consecutive host timestamps, in ms."""
    gaps = np.diff(np.asarray(stamps))
    return round(float(np.median(gaps)) * 1e3, 2) if gaps.size else float("nan")


# ---- train ----------------------------------------------------------------


def leg_train(size: SmokeSize, strategy, label: str = "train-1",
              reference_loss: Optional[float] = None) -> Dict[str, Any]:
    """`Trainer.fit(LlamaModule, DataLoader(seeded tokens))` for a few
    optimizer steps under ``strategy``. ``reference_loss`` (the
    one-device run's first-step loss on the same seed and global batch)
    pins the sharded runs to it within 1e-2 relative."""
    import jax

    from ray_lightning_tpu import DataLoader, Trainer
    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.models.llama import LlamaConfig, LlamaModule
    from ray_lightning_tpu.ops import dispatch

    cfg = LlamaConfig(**size.model)
    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, cfg.vocab_size,
        (size.batch * size.train_steps, size.seq + 1)).astype(np.int32)
    loader = DataLoader({"tokens": tokens}, batch_size=size.batch,
                        prefetch=True)

    class _Losses(Callback):
        def __init__(self):
            self.losses: List[float] = []
            self.stamps: List[float] = []

        def on_train_batch_end(self, trainer, module, metrics, batch_idx):
            # log_every_n_steps=1: metrics are host floats, so the stamp
            # is taken after the device finished the step
            self.losses.append(float(metrics["loss"]))
            self.stamps.append(time.perf_counter())

    seen = _Losses()
    module = LlamaModule(cfg, warmup_steps=2, total_steps=100)
    trainer = Trainer(
        strategy=strategy, max_epochs=1, max_steps=size.train_steps,
        log_every_n_steps=1, enable_checkpointing=False,
        enable_progress_bar=False, seed=0, callbacks=[seen])
    trainer.fit(module, loader)

    leaves = jax.tree.leaves(trainer.state.params)
    platform = jax.devices()[0].platform
    param_devices = set()
    for leaf in leaves:
        _require(all(d.platform == platform for d in leaf.devices()),
                 f"{label}: a param leaf is not on a {platform} device")
        param_devices |= leaf.devices()
    n_mesh = trainer.strategy.mesh.size
    _require(len(param_devices) == n_mesh,
             f"{label}: params live on {len(param_devices)} device(s), "
             f"the mesh has {n_mesh}")
    losses = seen.losses
    _require(trainer.global_step == len(losses) == size.train_steps
             and bool(np.isfinite(losses).all()),
             f"{label}: {trainer.global_step} steps, losses {losses}")
    ln_v = math.log(cfg.vocab_size)
    _require(abs(losses[0] - ln_v) < 1.0,
             f"{label}: first loss {losses[0]:.4f} is not within 1.0 of "
             f"ln V = {ln_v:.4f}")
    if reference_loss is not None:
        rel = abs(losses[0] - reference_loss) / abs(reference_loss)
        _require(rel < 1e-2,
                 f"{label}: first loss {losses[0]:.5f} vs one-device "
                 f"{reference_loss:.5f} (rel {rel:.2e})")
    mosaic = "tpu_custom_call" in (trainer._train_step.compiled_text() or "")
    if size.expect_mosaic:
        _require(not dispatch.interpret_mode(),
                 f"{label}: pallas kernels are in interpret mode")
        _require(mosaic, f"{label}: no tpu_custom_call in the compiled "
                         "train step (flash gave way to the XLA path)")
    _say(label, ran=f"Trainer.fit/{type(strategy).__name__}",
         mesh={a: n for a, n in trainer.strategy.mesh.shape.items()
               if n > 1},
         steps=len(losses), loader=loader.path,
         mosaic_kernels=mosaic,
         compile_s=round(trainer.callback_metrics["compile_time_s"], 2),
         step_ms_informational=_median_ms(seen.stamps),
         first_loss=round(losses[0], 4), last_loss=round(losses[-1], 4))
    return {"first_loss": losses[0],
            "compile_s": trainer.callback_metrics["compile_time_s"],
            "param_devices": len(param_devices)}


# ---- kernels --------------------------------------------------------------


def leg_kernels(size: SmokeSize) -> Dict[str, float]:
    """Every kernel against its XLA reference where it really runs; the
    paged pair at the serving leg's geometry. A miss is fatal."""
    from ray_lightning_tpu.ops.parity import (
        TOLERANCE, PagedGeometry, kernel_parity_errors,
    )

    m, e = size.model, size.engine
    t0 = time.perf_counter()
    errors = kernel_parity_errors(PagedGeometry(
        capacity=e["capacity"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["dim"] // m["n_heads"],
        block_size=e["block_size"], blocks_per_slot=e["blocks_per_slot"],
        prefill_chunk=e["prefill_chunk"]))
    bad = {k: v for k, v in errors.items() if not v <= TOLERANCE}
    _require(not bad, f"kernels: parity beyond {TOLERANCE}: {bad}")
    _say("kernels", ran="ops.parity.kernel_parity_errors",
         wall_s=round(time.perf_counter() - t0, 2),
         **{k: f"{v:.2e}" for k, v in errors.items()})
    return errors


# ---- serve ----------------------------------------------------------------


def leg_serve(size: SmokeSize, n_replicas: int = 1,
              label: str = "serve-1") -> Dict[str, Any]:
    """`ServeDriver` with ``n_replicas`` inline replicas (one per local
    device) answering seeded requests, greedy and sampled mixed."""
    import jax

    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver,
    )
    from ray_lightning_tpu.serve.engine import EngineConfig
    from ray_lightning_tpu.serve.scheduler import Request

    cfg = LlamaConfig(**size.model)
    rng = np.random.default_rng(1)
    prompts = rng.integers(
        0, cfg.vocab_size, (size.n_requests, size.prompt_len)
    ).astype(np.int32)
    params = jax.jit(Llama(cfg).init)(
        jax.random.key(1), prompts[:1])["params"]
    requests = [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=size.max_new,
                temperature=0.8 if i % 2 else 0.0,
                top_k=40 if i % 4 == 3 else None, seed=100 + i)
        for i, p in enumerate(prompts)]

    driver = ServeDriver(cfg, params, ReplicaGroupConfig(
        n_replicas=n_replicas, backend="inline",
        engine=EngineConfig(**size.engine)))
    t0 = time.perf_counter()
    driver.start()                      # builds + warms every replica
    warm_s = time.perf_counter() - t0
    engines = [driver.replicas[r].engine for r in sorted(driver.replicas)]
    for req in requests:
        driver.submit(req)
    stamps = [time.perf_counter()]
    while driver.busy():
        driver.tick()
        stamps.append(time.perf_counter())
    result = driver.stop()

    for eng in engines:
        _require(
            eng.attention_path == eng.prefill_path == size.serve_path,
            f"{label}: decode lane {eng.attention_path!r}, prefill lane "
            f"{eng.prefill_path!r}, expected {size.serve_path!r}")
        _require(eng.compile_count == 1,
                 f"{label}: step compiled {eng.compile_count} times")
    pool_devices = set()
    for eng in engines:
        pool_devices |= eng.pool_k.devices()
    want_devices = min(n_replicas, jax.local_device_count())
    _require(len(pool_devices) == want_devices,
             f"{label}: {n_replicas} replica pools on "
             f"{len(pool_devices)} device(s), expected {want_devices}")
    for req in requests:
        out = result.outputs[req.rid]
        _require(len(out) == size.max_new
                 and all(0 <= t < cfg.vocab_size for t in out),
                 f"{label}: {req.rid} returned {len(out)} tokens "
                 f"(asked {size.max_new}): {out}")
    _say(label, ran=f"ServeDriver/inline x{n_replicas}",
         requests=len(requests), tokens=result.stats["n_tokens"],
         attention_path=engines[0].attention_path,
         prefill_path=engines[0].prefill_path,
         compile_count=[e.compile_count for e in engines],
         pool_devices=sorted(d.id for d in pool_devices),
         warm_s=round(warm_s, 2),
         tick_ms_informational=_median_ms(stamps))
    return {"warm_s": warm_s, "pool_devices": len(pool_devices)}


# ---- the run --------------------------------------------------------------


def run(size: SmokeSize, n_devices: int) -> Dict[str, Any]:
    """Every leg that ``n_devices`` allows, strictly one after another in
    this process. Any failed check propagates."""
    from ray_lightning_tpu import FSDP, ShardedMesh, SingleDevice

    def sharded(label: str, strategy) -> Dict[str, Any]:
        return leg_train(size, strategy, label=label,
                         reference_loss=legs["train-1"]["first_loss"])

    plan: List[tuple[str, Callable[[], Any]]] = [
        ("train-1", lambda: leg_train(size, SingleDevice())),
        ("kernels", lambda: leg_kernels(size)),
        ("serve-1", lambda: leg_serve(size)),
    ]
    if n_devices >= 4:
        plan += [
            ("train-4/fsdp",
             lambda: sharded("train-4/fsdp", FSDP(num_workers=4))),
            ("train-4/fsdp2xtensor2",
             lambda: sharded("train-4/fsdp2xtensor2", ShardedMesh(
                 fsdp=2, tensor=2, num_workers=4))),
            ("serve-4",
             lambda: leg_serve(size, n_replicas=4, label="serve-4")),
        ]
    legs: Dict[str, Any] = {}
    for label, leg in plan:
        legs[label] = leg()
        # a Trainer and its module reference each other: collect the
        # cycle now so the finished leg's device buffers are gone
        # before the next leg allocates
        gc.collect()
    return legs


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: jax's default backend is {backend!r}, not "
              "'tpu'; this script has no CPU mode", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    devices = jax.devices()
    legs = run(SmokeSize.full(), len(devices))
    print(json.dumps({
        "legs_passed": sorted(legs),
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_s": {k: round(v["compile_s"], 2)
                      for k, v in legs.items() if "compile_s" in v},
        "serve_warm_s": {k: round(v["warm_s"], 2)
                         for k, v in legs.items() if "warm_s" in v},
        "claim": None,
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
