"""Trainer: owns the jitted SPMD train/eval loops.

The reference borrowed this entirely from PyTorch Lightning and only hosted
it remotely (DDPSpawnPlugin.new_process invoked at reference
ray_ddp.py:238-241). The rebuild owns the loop, TPU-first:

  * ONE compiled program per step: `jax.value_and_grad` + optax update fused
    under `jax.jit`, full TrainState donated so params/opt-state update in
    place in HBM;
  * sharding by annotation: the Strategy places state/batches on the mesh,
    XLA emits the collectives (grad psum over `data`, FSDP all-gather /
    reduce-scatter over `fsdp`) — no process group, no explicit allreduce;
  * static shapes: dataloaders drop ragged tails so the step compiles once;
  * gradient accumulation via `lax.scan` over a microbatch axis (no Python
    loop inside jit);
  * metrics come back as device scalars and are fetched lazily to avoid a
    host sync per step.

API parity (C2 of SURVEY §7.1): fit/validate/test/predict, callbacks,
checkpointing, early stopping — everything the reference's BoringModel
exercises (reference tests/utils.py:26-93).
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu.checkpoint import (
    restore_checkpoint,
    save_checkpoint,
    wait_for_checkpoints,
)
from ray_lightning_tpu.checkpoint.io import read_meta
from ray_lightning_tpu.core.callbacks import (
    Callback,
    ModelCheckpoint,
    ProgressLogger,
)
from ray_lightning_tpu.core.data import DataModule
from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.core.state import TrainState
from ray_lightning_tpu.parallel.strategy import SingleDevice, Strategy
from ray_lightning_tpu.pipeline.compile_cache import (
    WarmStep,
    enable_persistent_cache,
)
from ray_lightning_tpu.pipeline.prefetch import (
    DevicePrefetcher,
    prefetch_to_device,
)
from ray_lightning_tpu.telemetry import TelemetryConfig
from ray_lightning_tpu.telemetry import goodput as _goodput
from ray_lightning_tpu.telemetry.profiler import (
    ProfileConfig,
    ProfilerController,
)
from ray_lightning_tpu.telemetry.spans import (
    NULL_RECORDER,
    PH_CKPT,
    PH_DISPATCH,
    PH_EVAL,
    PH_METRICS,
    PH_RESHARD,
    PH_STEP,
    TelemetryRecorder,
    annotate,
    tracing,
)
from ray_lightning_tpu.utils import get_logger, seed_everything

log = get_logger(__name__)


class Trainer:
    def __init__(
        self,
        strategy: Optional[Strategy] = None,
        max_epochs: int = 1,
        max_steps: int = -1,
        callbacks: Optional[List[Callback]] = None,
        limit_train_batches: Optional[int] = None,
        limit_val_batches: Optional[int] = None,
        limit_test_batches: Optional[int] = None,
        check_val_every_n_epoch: int = 1,
        val_check_interval: Optional[int] = None,
        log_every_n_steps: int = 50,
        accumulate_grad_batches: int = 1,
        gradient_clip_val: Optional[float] = None,
        precision: str = "f32",  # "f32" | "bf16" (cast float inputs)
        seed: Optional[int] = None,
        default_root_dir: Optional[str] = None,
        enable_checkpointing: bool = True,
        enable_progress_bar: bool = True,
        num_sanity_val_steps: int = 0,
        prefetch_to_device: int = 2,
        warm_start: bool = True,
        compile_cache_dir: Optional[str] = None,
        guard: Any = None,
        telemetry: Any = None,
        profile: Any = None,
    ):
        self.strategy = strategy or SingleDevice()
        self.max_epochs = max_epochs
        self.max_steps = max_steps
        self.limit_train_batches = limit_train_batches
        self.limit_val_batches = limit_val_batches
        self.limit_test_batches = limit_test_batches
        self.check_val_every_n_epoch = max(1, check_val_every_n_epoch)
        #: mid-epoch validation every N optimizer steps (long-epoch /
        #: streaming LLM runs where epoch boundaries are meaningless)
        self.val_check_interval = val_check_interval
        self.log_every_n_steps = log_every_n_steps
        self.accumulate_grad_batches = max(1, accumulate_grad_batches)
        self.gradient_clip_val = gradient_clip_val
        self.precision = precision
        self.seed = seed
        self.default_root_dir = default_root_dir or os.path.join(
            os.getcwd(), "rlt_logs"
        )
        self.num_sanity_val_steps = num_sanity_val_steps
        #: device-prefetch buffer depth (pipeline/prefetch.py): a
        #: background stage overlaps host batch assembly + sharded
        #: device_put with the previous step's compute. 0 disables
        #: (fully synchronous placement, bitwise-identical training).
        self.prefetch_to_device = max(0, prefetch_to_device)
        #: AOT-compile the train step at fit start (lower().compile(),
        #: pipeline/compile_cache.py) so compile time is a reported
        #: metric, not a mysteriously slow first batch; the eval step
        #: warms on its first batch. Shape drift falls back to lazy jit.
        self.warm_start = warm_start
        #: persistent XLA compilation cache dir, honoured only while
        #: JAX_COMPILATION_CACHE_DIR is unset (pipeline/compile_cache.py
        #: resolver; default <checkout>/.jax_cache). Restarts then
        #: deserialize the step instead of recompiling.
        self.compile_cache_dir = compile_cache_dir
        #: trainguard (resilience/guard.py): True / GuardConfig compiles
        #: finiteness + loss-spike checks INTO the train step — an
        #: anomalous update is discarded by a tree-select, the counters
        #: ride the existing metric outputs (no new host syncs), and a
        #: GuardCallback escalates sustained anomalies / SDC verdicts.
        self.guard = guard
        #: trainguard rollback marker payload (set by the supervisor's
        #: worker wrapper): after a corruption rollback, resume advances
        #: the data order past the poisoned window instead of replaying
        #: it. Applied in _init_state when the restore point is behind
        #: the marker's detection step.
        self.resume_skip_past: Optional[Dict[str, Any]] = None
        #: telemetry (telemetry/, docs/OBSERVABILITY.md): True /
        #: TelemetryConfig arms the host-side span recorder — data wait,
        #: H2D, dispatch, metric fetch, ckpt stall, compile, eval spans
        #: into a bounded ring flushed as per-rank JSONL on the logging
        #: cadence. Host bookkeeping only: telemetry=off compiles the
        #: byte-identical device program (test-pinned).
        self.telemetry = telemetry
        #: on-demand jax.profiler capture (telemetry/profiler.py):
        #: ProfileConfig(step window / marker file / SIGUSR1), rank-scoped
        self.profile = profile
        self.telemetry_recorder = NULL_RECORDER
        self._profiler: Optional[ProfilerController] = None
        self._telemetry_flush_every = 50
        self._fit_start_perf: Optional[float] = None
        self._fit_start_step = 0
        self._launch_s = 0.0

        self.callbacks: List[Callback] = list(callbacks or [])
        if enable_checkpointing and not any(
            isinstance(c, ModelCheckpoint) for c in self.callbacks
        ):
            self.callbacks.append(ModelCheckpoint())
        if enable_progress_bar and not any(
            isinstance(c, ProgressLogger) for c in self.callbacks
        ):
            self.callbacks.append(ProgressLogger(log_every_n_steps))

        # run state
        self.state: Optional[TrainState] = None
        self.module: Optional[TpuModule] = None
        self.tx: Optional[optax.GradientTransformation] = None
        self.callback_metrics: Dict[str, Any] = {}
        self.current_epoch = 0
        self.global_step = 0
        self.should_stop = False
        self.has_validation = False
        self._last_val_step = -1
        # mid-epoch bookkeeping for checkpoint/resume: whether we are
        # inside a partially-consumed train epoch, how many batches of the
        # current epoch ran, and how many to skip after a mid-epoch resume
        self._mid_epoch = False
        self._epoch_batches_done = 0
        self._resume_skip_batches = 0
        self.last_batch_size: Optional[int] = None
        self._train_step = None
        self._eval_step = None
        self._base_rng = None
        self.is_fitted = False

    # ------------------------------------------------------------------ fit

    @property
    def checkpoint_callback(self) -> Optional[ModelCheckpoint]:
        for c in self.callbacks:
            if isinstance(c, ModelCheckpoint):
                return c
        return None

    def fit(
        self,
        module: TpuModule,
        train_dataloaders: Optional[Iterable] = None,
        val_dataloaders: Optional[Iterable] = None,
        datamodule: Optional[DataModule] = None,
        ckpt_path: Optional[str] = None,
    ) -> Dict[str, Any]:
        seed = seed_everything(self.seed)
        self._base_rng = jax.random.key(seed)
        self.module = module
        module.trainer = self
        if self.guard:
            # normalize (True -> defaults) and attach the escalation/SDC
            # callback; lazy import keeps core free of resilience deps
            # when the guard is off
            from ray_lightning_tpu.resilience.guard import (
                GuardCallback,
                GuardConfig,
            )

            self.guard = GuardConfig.coerce(self.guard)
            if not any(isinstance(c, GuardCallback) for c in self.callbacks):
                self.callbacks.append(GuardCallback(self.guard))
        # mesh first: configure_model may close over it (ring attention).
        self.strategy.setup(module)
        module.setup()
        self._setup_telemetry()

        if datamodule is not None:
            datamodule.setup()
            train_dataloaders = datamodule.train_dataloader()
            val_dataloaders = val_dataloaders or datamodule.val_dataloader()
        if train_dataloaders is None:
            raise ValueError("fit() needs train_dataloaders or a datamodule")
        self.has_validation = val_dataloaders is not None
        example_batch, train_dataloaders = self._peek(train_dataloaders)

        # persistent cache BEFORE any step compiles: a restarted worker
        # (resilience supervisor) then deserializes every program
        # instead of recompiling it
        enable_persistent_cache(self.compile_cache_dir)
        self.tx = self._build_tx(module)
        self.state = self._init_state(module, example_batch, ckpt_path)
        self._train_step = self._make_train_step(module)
        self._eval_step = self._make_eval_step(module, module.validation_step)
        self._fit_start_perf = time.perf_counter()
        self._fit_start_step = self.global_step

        module.on_fit_start(self)
        self._invoke("on_fit_start")
        fit_error: Optional[BaseException] = None
        try:
            # warm start AFTER on_fit_start: the heartbeat sender is now
            # running, so a long AOT compile reports itself as a live
            # "compile" span instead of a silent pre-loop stall
            if self.warm_start:
                self._warm_start_train_step(example_batch)
            if self.num_sanity_val_steps and self.has_validation:
                self._run_eval_epoch(
                    val_dataloaders, limit=self.num_sanity_val_steps, sanity=True
                )
            self._fit_loop(train_dataloaders, val_dataloaders)
        except BaseException as exc:  # surface to callbacks, then re-raise
            fit_error = exc
            self._invoke("on_exception", exc)
            raise
        finally:
            # join in-flight async checkpoint writes before anything can
            # read the files or the process exits. A deferred write error
            # must not displace an in-flight training exception — but on
            # the success path it IS the failure (best_model_path must
            # never point at an unfinalized checkpoint), so re-raise.
            try:
                wait_for_checkpoints()
            except Exception:  # noqa: BLE001
                if fit_error is None:
                    raise
                log.exception("async checkpoint write failed")
            # Parity C5: the driver-side module object holds trained weights.
            if self.state is not None:
                module.params = self.state.params
            if self._profiler is not None:
                self._profiler.close()
            self._finalize_telemetry(completed=fit_error is None)
        module.on_fit_end(self)
        self._invoke("on_fit_end")
        self.is_fitted = True
        return dict(self.callback_metrics)

    def _fit_loop(self, train_loader, val_loader) -> None:
        for epoch in range(self.current_epoch, self.max_epochs):
            self.current_epoch = epoch
            if hasattr(train_loader, "set_epoch"):
                train_loader.set_epoch(epoch)
            self.module.on_train_epoch_start(self)
            self._invoke("on_train_epoch_start")
            self._run_train_epoch(train_loader, val_loader)
            run_val = (
                self.has_validation
                and (epoch + 1) % self.check_val_every_n_epoch == 0
                # mid-epoch interval may have just validated this
                # exact step — don't run twice on identical weights
                and self.global_step != self._last_val_step
            )
            if run_val:
                metrics = self._run_eval_epoch(
                    val_loader, limit=self.limit_val_batches
                )
                self._last_val_step = self.global_step
                self.callback_metrics.update(metrics)
                self.module.on_validation_epoch_end(self, metrics)
                self._invoke("on_validation_epoch_end", metrics)
            self.module.on_train_epoch_end(self)
            self._invoke("on_train_epoch_end")
            if self.should_stop or self._hit_max_steps():
                break

    def _run_train_epoch(self, loader, val_loader=None) -> None:
        pending: Dict[str, Any] = {}
        # Mid-epoch resume: fast-forward past already-consumed batches so a
        # checkpoint saved by every_n_train_steps/val_check_interval resumes
        # the SAME epoch at the right offset (loaders reshuffle
        # deterministically per epoch via set_epoch, so offsets are stable).
        skip = self._resume_skip_batches
        self._resume_skip_batches = 0
        self._mid_epoch = True
        self._epoch_batches_done = skip
        it = iter(loader)
        for _ in range(skip):
            if next(it, None) is None:
                break
        completed = False
        # Device prefetch (pipeline/prefetch.py): cast + sharded placement
        # run up to `depth` batches ahead on a producer thread, so the
        # step's input is resident when it dispatches. The skip above
        # already advanced the raw iterator, so a mid-epoch resume never
        # pays placement for batches it will drop. Order is preserved —
        # training is bitwise-identical to the synchronous path.
        rec = self.telemetry_recorder
        t_prev: Optional[float] = None
        stream = prefetch_to_device(
            it, self._place_train_batch, depth=self.prefetch_to_device,
            recorder=rec)
        try:
            # start=skip: callbacks must see the true intra-epoch batch
            # index after a mid-epoch resume
            for batch_idx, (bs, device_batch) in enumerate(stream,
                                                           start=skip):
                if (
                    self.limit_train_batches is not None
                    # count from epoch start, not resume point, so a
                    # resumed epoch sees limit - already_consumed more
                    and self._epoch_batches_done >= self.limit_train_batches
                ):
                    # the limit DEFINES the epoch length (PTL semantics),
                    # so hitting it is epoch completion, not a mid-epoch cut
                    completed = True
                    break
                self.last_batch_size = bs
                device_batch = self._invoke_batch_start(
                    device_batch, batch_idx)
                rec.set_step(self.global_step)
                with rec.span(PH_DISPATCH, step=self.global_step):
                    self.state, metrics = self._train_step(
                        self.state, device_batch, self._base_rng
                    )
                self.global_step += 1
                self._epoch_batches_done += 1
                if rec.enabled:
                    # per-step host wall (batch boundary to batch
                    # boundary) — the measured side of the drift report
                    t_now = time.perf_counter()
                    if t_prev is not None:
                        rec.record(PH_STEP, t_prev, t_now - t_prev,
                                   step=self.global_step)
                    t_prev = t_now
                if self._profiler is not None:
                    self._profiler.on_step(self.global_step)
                pending = metrics
                # Lazy metric fetch: only sync on the logging cadence.
                if self.global_step % max(1, self.log_every_n_steps) == 0:
                    with rec.span(PH_METRICS, step=self.global_step):
                        host = _to_host(metrics)
                    # the integer counts the module logged in the step
                    # just read, on the profiler's clock (as the serving
                    # side's `rlt.serve.account`; nothing outside a session)
                    if tracing():
                        with annotate("train.account", step=self.global_step,
                                      **_logged_counts(metrics, host)):
                            pass
                    self.callback_metrics.update(host)
                    pending = host
                # telemetry persistence on its own configured cadence
                # (TelemetryConfig.flush_every_n_steps): the ring drains
                # to JSONL and the goodput ledger refreshes, so a killed
                # worker leaves an almost-current account of where its
                # wall went even under a sparse logging cadence
                if (rec.enabled and self.global_step
                        % self._telemetry_flush_every == 0):
                    rec.flush()
                    self._write_telemetry_ledger(completed=False)
                self._invoke("on_train_batch_end", pending, batch_idx)
                if (self.val_check_interval and self.has_validation
                        and val_loader is not None
                        and self.global_step % self.val_check_interval == 0):
                    metrics = self._run_eval_epoch(
                        val_loader, limit=self.limit_val_batches)
                    self._last_val_step = self.global_step
                    self.callback_metrics.update(metrics)
                    self.module.on_validation_epoch_end(self, metrics)
                    self._invoke("on_validation_epoch_end", metrics)
                if self.should_stop or self._hit_max_steps():
                    break
            else:
                completed = True
        finally:
            # a mid-epoch exit of ANY kind (max_steps, early stop, a
            # preemption drain raising out of a callback) must join the
            # producer thread — never leak it holding the loader
            if isinstance(stream, DevicePrefetcher):
                stream.close()
                self.callback_metrics.update(stream.stats.to_metrics())
        if completed:
            # every batch of this epoch was consumed — subsequent saves
            # (epoch-boundary validation / on_train_epoch_end) resume at
            # the NEXT epoch
            self._mid_epoch = False
        if pending:
            self.callback_metrics.update(_to_host(pending))

    def _run_eval_epoch(
        self, loader, limit: Optional[int] = None, sanity: bool = False
    ) -> Dict[str, float]:
        """Eval totals accumulate ON DEVICE (batch-size-weighted sums of
        the replicated step metrics — each += is a tiny async dispatch, no
        transfer) and are fetched with ONE host sync at epoch end; a
        per-batch `device_get` would stall the pipeline once per batch,
        ruinous for real validation sets at 8B scale."""
        if hasattr(loader, "set_epoch"):
            loader.set_epoch(self.current_epoch)
        totals: Dict[str, Any] = {}
        weights = 0.0
        with self.telemetry_recorder.span(PH_EVAL):
            # recorder here too: an eval epoch starved on its loader
            # shows as itemized data_wait, not as opaque "eval" time
            # (the recorder credits the enclosing eval span, so the
            # buckets never double-count)
            stream = prefetch_to_device(
                loader, self._place_eval_batch,
                depth=self.prefetch_to_device,
                recorder=self.telemetry_recorder)
            try:
                for batch_idx, (bs, device_batch) in enumerate(stream):
                    if limit is not None and batch_idx >= limit:
                        break
                    metrics = self._eval_step(self.state.params,
                                              device_batch)
                    for k, v in metrics.items():
                        # accumulate in f32 — a bf16 step metric summed
                        # over hundreds of batches would round away the
                        # increments
                        scaled = jnp.asarray(v).astype(jnp.float32) * bs
                        totals[k] = (totals[k] + scaled if k in totals
                                     else scaled)
                    weights += bs
            finally:
                if isinstance(stream, DevicePrefetcher):
                    stream.close()
            if (isinstance(self._eval_step, WarmStep)
                    and self._eval_step.stats.total_s):
                self.callback_metrics.update(
                    self._eval_step.stats.to_metrics("val_"))
            if sanity or weights == 0:
                return {}
            host = _to_host(totals)
            return {k: float(v) / weights for k, v in host.items()}

    # ------------------------------------------------------- validate & co.

    def validate(self, module: Optional[TpuModule] = None, dataloaders=None,
                 datamodule: Optional[DataModule] = None) -> Dict[str, float]:
        module = self._attach(module)
        if datamodule is not None:
            datamodule.setup()
            dataloaders = datamodule.val_dataloader()
        self._eval_step = self._make_eval_step(module, module.validation_step)
        dataloaders = self._ensure_state(module, dataloaders)
        metrics = self._run_eval_epoch(dataloaders, limit=self.limit_val_batches)
        self.callback_metrics.update(metrics)
        return metrics

    def test(self, module: Optional[TpuModule] = None, dataloaders=None,
             datamodule: Optional[DataModule] = None) -> Dict[str, float]:
        module = self._attach(module)
        if datamodule is not None:
            datamodule.setup()
            dataloaders = datamodule.test_dataloader()
        self._eval_step = self._make_eval_step(module, module.test_step)
        dataloaders = self._ensure_state(module, dataloaders)
        metrics = self._run_eval_epoch(dataloaders, limit=self.limit_test_batches)
        self.callback_metrics.update(metrics)
        return metrics

    def predict(self, module: Optional[TpuModule] = None, dataloaders=None,
                datamodule: Optional[DataModule] = None) -> List[Any]:
        module = self._attach(module)
        if datamodule is not None:
            datamodule.setup()
            dataloaders = datamodule.predict_dataloader()
        dataloaders = self._ensure_state(module, dataloaders)
        step = jax.jit(lambda p, b: module.predict_step(p, b))
        outs = []
        for batch in dataloaders:
            batch = self._cast(batch)
            device_batch = self.strategy.shard_batch(batch)
            outs.append(_gather_out(step(self.state.params, device_batch)))
        return outs

    # --------------------------------------------------------- checkpoints

    def save_checkpoint(self, path: str, block: bool = True) -> str:
        assert self.state is not None, "nothing to save; fit first"
        ckpt_meta = {
            "epoch": self.current_epoch,
            "global_step": self.global_step,
            # mid-epoch saves (every_n_train_steps / val_check_interval)
            # record the batch offset so resume replays the REST of the
            # epoch instead of silently skipping it
            "mid_epoch": self._mid_epoch,
            "epoch_batch": self._epoch_batches_done,
            "module_class": type(self.module).__name__,
            "hparams": self.module.hparams,
        }
        # trainguard blessing: stamp the anomaly-free-window verdict so
        # a corruption rollback can target the last GOOD restore point
        # (latest_checkpoint(good_only=True)). Guard off => trivially
        # blessed. The counter fetch below is save-cadenced host work, a
        # rounding error next to the checkpoint write it accompanies.
        blessed = True
        if self.guard and not isinstance(
                getattr(self.state, "guard", ()), tuple):
            from ray_lightning_tpu.resilience.guard import bless_verdict

            g = jax.device_get(self.state.guard)
            upd = int(jax.device_get(self.state.step))
            blessed = bless_verdict(self.guard, g, upd)
            ckpt_meta["guard"] = {
                "skipped_steps": int(np.asarray(g.skipped)),
                "streak": int(np.asarray(g.streak)),
                "last_anomaly": int(np.asarray(g.last_anomaly)),
            }
        ckpt_meta["blessed"] = blessed
        checkpoint = {
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            "step": self.state.step,
        }
        # topology provenance (docs/ELASTIC.md): stamp the writing mesh
        # and per-leaf layouts so a cross-topology restore
        # (elastic.reshard) can validate the move; checkpoints without
        # these stamps restore with NO cross-mesh validation (the
        # writing mesh is unknowable) and the elastic supervisor
        # refuses to resize onto them
        from ray_lightning_tpu.checkpoint.io import sharding_provenance

        ckpt_meta.update(
            sharding_provenance(self.strategy.mesh, checkpoint))
        self.module.on_save_checkpoint(checkpoint)
        self._invoke("on_save_checkpoint", checkpoint)
        # the span measures exactly what the TRAINING thread paid: the
        # full write when blocking, the snapshot + any join-wait on a
        # previous in-flight write when async
        with self.telemetry_recorder.span(PH_CKPT, meta={"path": path}):
            out = save_checkpoint(path, checkpoint, ckpt_meta, block=block)
        # checkpoint-overlap accounting: how long the TRAINING thread
        # stalled on checkpoint I/O (the async path's win is ~0 here)
        from ray_lightning_tpu.checkpoint.io import io_stats

        self.callback_metrics.update(io_stats())
        return out

    # ------------------------------------------------------------ plumbing

    def _attach(self, module: Optional[TpuModule]) -> TpuModule:
        module = module or self.module
        if module is None:
            raise ValueError("no module; pass one or fit first")
        self.module = module
        module.trainer = self
        if self.strategy.mesh is None:
            self.strategy.setup(module)
        else:
            # mesh already built (e.g. validate(moduleB) after
            # fit(moduleA)): rebind so param_specs/mesh come from the
            # module actually being run.
            self.strategy.bind_module(module)
        module.setup()
        return module

    def _ensure_state(self, module: TpuModule, loader):
        """Build eval-only state; returns the loader to ITERATE — when
        init peeked batch 0 off a one-shot iterator, the returned loader
        is the re-stitched chain that still contains it (callers must
        rebind, or the first batch silently disappears from eval)."""
        if self.state is not None:
            return loader
        if module.params is None:
            if loader is None:
                raise ValueError("module has no params and no data to init from")
            batch, loader = self._peek(loader)
            batch = self._cast(batch)
            rng = jax.random.key(seed_everything(self.seed))
            module.params = module.init_params(rng, batch)
        params = self.strategy.shard_params(module.params)
        step0 = jax.device_put(
            jnp.zeros((), jnp.int32), self.strategy.replicated()
        )
        self.state = TrainState(step=step0, params=params, opt_state=())
        if self._eval_step is None:
            self._eval_step = self._make_eval_step(module, module.validation_step)
        return loader

    def _build_tx(self, module: TpuModule) -> optax.GradientTransformation:
        tx = module.configure_optimizers()
        if self.gradient_clip_val:
            tx = optax.chain(optax.clip_by_global_norm(self.gradient_clip_val), tx)
        return tx

    def _init_state(
        self, module: TpuModule, example_batch, ckpt_path: Optional[str]
    ) -> TrainState:
        example_batch = self._cast(example_batch)
        # Dedicated init stream: must not collide with fold_in(rng, step=0)
        # used by the first training step.
        rng = jax.random.fold_in(self._base_rng, 0x696E6974)  # "init"

        if module.params is not None:
            # Pre-loaded weights (load_from_checkpoint / warm start).
            params = self.strategy.shard_params(module.params)
        else:
            # Shard-aware init: eval_shape → shardings → jit init with
            # out_shardings, so an 8B-param model never materializes
            # unsharded on one device.
            init_fn = lambda r: module.init_params(r, example_batch)
            abstract = jax.eval_shape(init_fn, rng)
            shardings = self.strategy.param_shardings(abstract)
            params = jax.jit(init_fn, out_shardings=shardings)(rng)

        # Optimizer state: explicitly sharded (mu/nu follow their params —
        # ZeRO semantics; scalars replicate). jit alone does NOT propagate
        # sharding here: tx.init is shape-only, so XLA drops the input
        # dependency and would leave the state on one device.
        abstract_opt = jax.eval_shape(self.tx.init, params)
        opt_shardings = self.strategy.opt_state_shardings(abstract_opt, params)
        opt_state = jax.jit(self.tx.init, out_shardings=opt_shardings)(params)
        # step is committed to the mesh (replicated) so the whole TrainState
        # lives on one device set — restored checkpoints keep that layout.
        step0 = jax.device_put(
            jnp.zeros((), jnp.int32), self.strategy.replicated()
        )
        state = TrainState(step=step0, params=params, opt_state=opt_state)
        if ckpt_path:
            meta = read_meta(ckpt_path)
            target = {"params": state.params,
                      "opt_state": state.opt_state, "step": state.step}
            move = self._reshard_move(meta)
            if move is not None:
                # cross-topology restore (docs/ELASTIC.md): the
                # checkpoint was written on a DIFFERENT mesh — validate
                # the move against its provenance and account the load
                # as a `reshard` span (goodput bucket reshard_s), so an
                # elastic shrink/grow is visible in `report`
                log.warning(
                    "resharding restore: checkpoint %s written on mesh "
                    "%s, restoring onto %s", ckpt_path,
                    move["from_mesh"], move["to_mesh"])
                with self.telemetry_recorder.span(PH_RESHARD, meta=move):
                    restored = restore_checkpoint(ckpt_path, target)
            else:
                restored = restore_checkpoint(ckpt_path, target)
            saved_epoch = int(meta.get("epoch", -1))
            if meta.get("mid_epoch", False):
                # checkpoint taken inside a partially-trained epoch:
                # resume the SAME epoch, skipping the consumed batches
                self.current_epoch = max(0, saved_epoch)
                self._resume_skip_batches = int(meta.get("epoch_batch", 0))
            else:
                self.current_epoch = saved_epoch + 1
            self.global_step = int(meta.get("global_step", 0))
            module.on_load_checkpoint(restored)
            self._invoke("on_load_checkpoint", restored)
            state = TrainState(
                step=restored["step"],
                params=restored["params"],
                opt_state=restored["opt_state"],
            )
        # outside the restore branch on purpose: a rollback that found
        # no blessed checkpoint resumes from SCRATCH and must still
        # advance past the poisoned window instead of replaying it
        self._apply_rollback_skip()
        if self.guard:
            from ray_lightning_tpu.resilience.guard import init_guard_state

            # fresh guard scalars even after a restore: the EMA re-warms
            # in warmup_steps, which beats resuming a pre-anomaly EMA
            # that no longer matches the restored loss scale
            state = state.replace(guard=jax.device_put(
                init_guard_state(), self.strategy.replicated()))
        return state

    def _reshard_move(self, meta: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """When the checkpoint's recorded writing mesh differs from the
        strategy's current mesh, validate the cross-topology move
        (elastic.reshard) and return its summary; None for a same-mesh
        restore. A provenance-carrying checkpoint whose move is ILLEGAL
        raises ReshardError here — at setup, with the leaf and axis
        named — instead of surfacing as a silent mislayout or an orbax
        shape error mid-restore.

        A LEGACY checkpoint (no ``mesh_spec`` stamp) also returns None:
        its writing mesh is unknowable, so a cross-mesh resume can
        neither be detected nor validated — the storage layer places
        the global arrays onto whatever layout this run built, and a
        warning marks the blind spot. The elastic supervisor refuses to
        RESIZE onto such a checkpoint outright (`_begin_reshard`)."""
        src = meta.get("mesh_spec")
        if not src or self.strategy.mesh is None:
            if meta and src is None and self.strategy.mesh is not None:
                log.warning(
                    "checkpoint carries no sharding provenance (written "
                    "before elastic/): restoring WITHOUT cross-mesh "
                    "validation — if the writing mesh differed from %s "
                    "this restore reshards silently; re-save once to "
                    "stamp provenance (docs/ELASTIC.md)",
                    dict(self.strategy.mesh.shape))
            return None
        cur = {str(k): int(v) for k, v in self.strategy.mesh.shape.items()}
        src = {str(k): int(v) for k, v in src.items()}
        if {k: v for k, v in src.items() if v > 1} == \
                {k: v for k, v in cur.items() if v > 1}:
            return None
        from ray_lightning_tpu.elastic.reshard import validate_reshard

        return validate_reshard(meta, cur)

    def _apply_rollback_skip(self) -> None:
        """After a trainguard rollback (resume_skip_past set by the
        supervisor from the rollback marker): the restore point is the
        last BLESSED checkpoint, behind the detection step — advance the
        data order past the poisoned window instead of replaying it.
        Also applies to a scratch resume (no blessed checkpoint found):
        the clean prefix of the epoch is sacrificed along with the
        window, which is the safe trade — suspect data is never
        retrained."""
        rsp = self.resume_skip_past
        if not rsp or int(rsp.get("detected_step", -1)) <= self.global_step:
            return  # stale marker from an older incident: resume is past it
        if int(rsp.get("epoch", -1)) != self.current_epoch:
            log.warning(
                "trainguard rollback: poisoned window spans an epoch "
                "boundary (detected epoch %s, resuming epoch %d) — "
                "replaying instead of skipping", rsp.get("epoch"),
                self.current_epoch)
            return
        target = int(rsp.get("epoch_batch", 0))
        if target > self._resume_skip_batches:
            log.warning(
                "trainguard rollback: advancing data order past the "
                "poisoned window — epoch %d resumes at batch %d "
                "(instead of %d)", self.current_epoch, target,
                self._resume_skip_batches)
            self._resume_skip_batches = target

    def _make_train_step(self, module: TpuModule):
        tx = self.tx
        accum = self.accumulate_grad_batches
        guard_cfg = self.guard if (self.guard and self.guard.enabled) \
            else None
        if guard_cfg is not None:
            from ray_lightning_tpu.resilience.guard import apply_guard

        def loss_fn(params, batch, rng):
            out = module.training_step(params, batch, rng)
            if isinstance(out, tuple):
                loss, metrics = out
            else:
                loss, metrics = out, {}
            metrics = {**metrics, **module.pop_logged()}
            return loss, metrics

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def step(state: TrainState, batch, base_rng):
            rng = jax.random.fold_in(base_rng, state.step)
            if accum == 1:
                (loss, metrics), grads = grad_fn(state.params, batch, rng)
            else:
                # batch leading axis = accum microbatches; scan-accumulate.
                def body(carry, micro):
                    sum_grads, i = carry
                    (l, m), g = grad_fn(
                        state.params, micro, jax.random.fold_in(rng, i)
                    )
                    sum_grads = jax.tree.map(jnp.add, sum_grads, g)
                    return (sum_grads, i + 1), (l, m)

                zero = jax.tree.map(jnp.zeros_like, state.params)
                (grads, _), (losses, metricses) = jax.lax.scan(
                    body, (zero, 0), batch
                )
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = losses.mean()
                metrics = jax.tree.map(lambda m: m.mean(axis=0), metricses)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            metrics = {"loss": loss, "grad_norm": grad_norm, **metrics}
            if guard_cfg is not None:
                # trainguard tier 1 (resilience/guard.py): an anomalous
                # update (non-finite loss/grad or a loss spike vs the
                # EMA) is discarded by a tree-select — params/opt-state/
                # step pass through unchanged; the flag and counters are
                # ordinary metric scalars riding the existing lazy fetch
                params, opt_state, new_step, gstate, gmetrics = \
                    apply_guard(guard_cfg, state.guard, state.step, loss,
                                grad_norm, params, state.params,
                                opt_state, state.opt_state)
                return (
                    state.replace(step=new_step, params=params,
                                  opt_state=opt_state, guard=gstate),
                    {**metrics, **gmetrics},
                )
            return (
                state.replace(
                    step=state.step + 1, params=params, opt_state=opt_state
                ),
                metrics,
            )

        # The state's layout is a fixed point of the step: left to
        # itself GSPMD may hand back a small replicated leaf (a norm
        # gain under fsdp x tensor) SHARDED, and step 2 then feeds the
        # AOT executable an input layout it was not compiled for
        # (ValueError; under plain jit, a second compile). Metrics stay
        # the compiler's choice.
        state_shardings = jax.tree.map(lambda x: x.sharding, self.state)
        # check_args=(1,): only the batch can drift — re-checking the
        # whole TrainState per step would put O(param leaves) host work
        # back on the hot path
        return WarmStep(jax.jit(step, donate_argnums=(0,),
                                out_shardings=(state_shardings, None)),
                        label="train_step", check_args=(1,),
                        recorder=self.telemetry_recorder)

    def _make_eval_step(self, module: TpuModule, step_fn):
        def step(params, batch):
            metrics = step_fn(params, batch)
            logged = module.pop_logged()
            if metrics is None:
                metrics = {}
            if not isinstance(metrics, dict):
                metrics = {"val_loss": metrics}
            return {**metrics, **logged}

        # auto: the eval batch shape is unknown until validation runs, so
        # the AOT compile happens on the first eval batch (still recorded
        # as a first-class metric, val_compile_time_s)
        return WarmStep(jax.jit(step), label="eval_step",
                        auto=self.warm_start, check_args=(1,),
                        recorder=self.telemetry_recorder)

    def _warm_start_train_step(self, example_batch) -> None:
        """AOT lower().compile() the train step for the known shapes —
        the cold compile happens HERE, visible as compile_time_s, instead
        of hiding inside the first batch. With the persistent cache a
        restarted process deserializes instead of recompiling, so this
        reads ~zero on every warm start after the first."""
        _, device_batch = self._place_train_batch(example_batch)
        stats = self._train_step.warm(self.state, device_batch,
                                      self._base_rng)
        self.callback_metrics.update(stats.to_metrics())

    def _place_train_batch(self, batch):
        """Host batch -> (leading dim, device-resident batch); the
        prefetcher's producer stage (runs on its thread)."""
        batch = self._cast(batch)
        return _leading_dim(batch), self._shard_train_batch(batch)

    def _place_eval_batch(self, batch):
        batch = self._cast(batch)
        return _leading_dim(batch) or 1, self.strategy.shard_batch(batch)

    def _shard_train_batch(self, batch):
        accum = self.accumulate_grad_batches
        if accum > 1:
            def split(x):
                x = np.asarray(x)
                if x.shape[0] % accum != 0:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"accumulate_grad_batches={accum}"
                    )
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            batch = jax.tree.map(split, batch)
            import jax.sharding as js

            spec = self.strategy.batch_spec()
            micro_spec = js.PartitionSpec(None, *spec)
            sharding = js.NamedSharding(self.strategy.mesh, micro_spec)
            return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
        return self.strategy.shard_batch(batch)

    def _cast(self, batch):
        if self.precision != "bf16":
            return batch
        def cast(x):
            x = np.asarray(x)
            if np.issubdtype(x.dtype, np.floating):
                return x.astype(jnp.bfloat16)
            return x
        return jax.tree.map(cast, batch)

    def _peek(self, loader):
        """Grab batch 0 without losing it. One-shot iterators (generators)
        are re-stitched with itertools.chain; they support one epoch only."""
        import itertools

        it = iter(loader)
        try:
            first = next(it)
        except StopIteration:
            # an empty loader would otherwise surface as a raw
            # StopIteration; the usual cause is drop_last truncation —
            # a per-process shard smaller than one batch
            raise ValueError(
                "the dataloader yielded no batches. With drop_last=True "
                "(the static-shape default) this happens when a shard "
                "holds fewer rows than batch_size — e.g. a small dataset "
                "split over many processes. Lower batch_size or grow the "
                "dataset."
            ) from None
        if it is loader:
            if self.max_epochs > 1:
                log.warning(
                    "train data is a one-shot iterator; it will be exhausted "
                    "after one epoch — pass a re-iterable (e.g. DataLoader) "
                    "for multi-epoch training"
                )
            return first, itertools.chain([first], it)
        return first, loader

    def _hit_max_steps(self) -> bool:
        return self.max_steps > 0 and self.global_step >= self.max_steps

    def _invoke(self, hook: str, *args) -> None:
        for cb in self.callbacks:
            getattr(cb, hook)(self, self.module, *args)

    def _invoke_batch_start(self, batch, batch_idx: int):
        """on_train_batch_start with batch replacement: a callback that
        returns a non-None value substitutes the device batch (the
        fault injector's nan_loss/grad_blowup poisoning rides this).
        Host-side per-batch dispatch only — no device sync."""
        for cb in self.callbacks:
            out = cb.on_train_batch_start(self, self.module, batch,
                                          batch_idx)
            if out is not None:
                batch = out
        return batch

    # ------------------------------------------------------------ telemetry

    def _setup_telemetry(self) -> None:
        """Build the span recorder + profiler controller for this fit.
        Host bookkeeping only — nothing here reaches the jitted step, so
        telemetry=off vs on compile the byte-identical program."""
        self.telemetry = TelemetryConfig.coerce(self.telemetry)
        rank = jax.process_index()
        if self.telemetry is not None:
            self.telemetry_recorder = TelemetryRecorder(
                directory=self.telemetry.resolved_dir(
                    self.default_root_dir),
                rank=rank, ring_size=self.telemetry.ring_size)
            self._telemetry_flush_every = max(
                1, self.telemetry.flush_every_n_steps)
            self._launch_s = _launch_seconds()
        self.profile = ProfileConfig.coerce(self.profile)
        if self.profile is not None:
            self._profiler = ProfilerController(self.profile, rank=rank)

    def _write_telemetry_ledger(self, completed: bool) -> None:
        """Refresh this rank's goodput ledger (telemetry/goodput.py) —
        cadenced AND final, atomic replace, so a SIGKILLed attempt still
        leaves an almost-current account for the driver to assemble."""
        rec = self.telemetry_recorder
        if not rec.enabled or rec.directory is None \
                or self._fit_start_perf is None:
            return
        ledger = _goodput.worker_ledger(
            rec, time.perf_counter() - self._fit_start_perf,
            rank=rec.rank, start_step=self._fit_start_step,
            end_step=self.global_step, launch_s=self._launch_s,
            completed=completed)
        _goodput.write_ledger(rec.directory, ledger, uid=rec.uid)

    def _finalize_telemetry(self, completed: bool) -> None:
        rec = self.telemetry_recorder
        if not rec.enabled:
            return
        totals = rec.phase_totals()
        wall = (time.perf_counter() - self._fit_start_perf
                if self._fit_start_perf is not None else 0.0)
        stalls = sum(totals.get(p, 0.0) for p in
                     ("compile", "data_wait", "ckpt_stall", "eval",
                      "metrics_fetch"))
        self.callback_metrics.update({
            "telemetry_compile_s": totals.get("compile", 0.0),
            "telemetry_data_wait_s": totals.get("data_wait", 0.0),
            "telemetry_ckpt_stall_s": totals.get("ckpt_stall", 0.0),
            "telemetry_eval_s": totals.get("eval", 0.0),
            "telemetry_spans_dropped": float(rec.dropped),
            "goodput_fraction": (max(0.0, wall - stalls) / wall
                                 if wall > 0 else 0.0),
        })
        self._write_telemetry_ledger(completed=completed)
        rec.close()


def _launch_seconds() -> float:
    """Worker spawn -> fit start (imports, jax init, rendezvous) — the
    goodput launch bucket. Zero outside a runtime worker: a local fit
    has no spawn cost worth charging."""
    try:
        from ray_lightning_tpu.runtime import session

        s = session.get_session()
        started = getattr(s, "started_at", None) if s is not None else None
        if started:
            return max(0.0, time.time() - started)
    except Exception:  # noqa: BLE001 — accounting must never fail a fit
        pass
    return 0.0


def _gather_out(tree) -> Any:
    """Host copy of a possibly-multi-process prediction output: batch-axis-
    sharded arrays are not fully addressable on any one process, so gather
    globally first (every rank sees the full output; rank 0's is the
    conventional carrier through run_distributed)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tree = multihost_utils.process_allgather(tree, tiled=True)
        return jax.tree.map(np.asarray, tree)
    return _to_host(tree)


def _logged_counts(metrics, host) -> Dict[str, int]:
    """The metrics of a step that are integer scalars on the device (counts
    a module logged, such as rows routed to its experts), as host ints."""
    return {k: int(host[k]) for k, v in metrics.items()
            if getattr(v, "ndim", None) == 0
            and jnp.issubdtype(v.dtype, jnp.integer)}


def _to_host(tree) -> Any:
    fetched = jax.device_get(tree)
    if isinstance(fetched, dict):
        return {
            k: (np.asarray(v) if hasattr(v, "shape") and np.ndim(v) else float(v))
            for k, v in fetched.items()
        }
    return jax.tree.map(np.asarray, fetched)


def _leading_dim(batch) -> Optional[int]:
    leaves = jax.tree.leaves(batch)
    if not leaves:
        return None
    shape = getattr(leaves[0], "shape", None)
    return int(shape[0]) if shape else None
