"""supervise(): the restart loop between the driver API and the runtime.

One supervised attempt is one ordinary ``run_distributed`` call — a fresh
worker group, launched and torn down by runtime/launch.py exactly as an
unsupervised run would be. The supervisor adds, around it:

  driver side   classify every failure (policy.classify_failure), sleep
                the backoff, pick the latest VALID checkpoint
                (checkpoint.latest_checkpoint — torn/corrupt candidates
                are skipped), and re-launch with ``ckpt_path`` pointing
                at it; the trainer's existing mid-epoch resume
                bookkeeping (core/trainer.py ``_resume_skip_batches``)
                replays the REST of the interrupted epoch, no batch
                twice, none skipped. A HealthMonitor rides the queue
                channel (heartbeats) and the pump's watchdog hook.
  worker side   the shipped trainer factory is wrapped to attach the
                periodic step-cadence checkpoint feeding the resume
                loop, the heartbeat sender, the SIGTERM drain
                (preempt.PreemptionGuard), and — when configured — the
                deterministic fault injector.

FATAL failures (a real Python exception in user code) fail fast with the
classified cause; the underlying WorkerError — rank-tagged, log tail
attached (runtime/group.py) — stays chained underneath.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal as _signal
import time
from typing import Any, Callable, Dict, List, Optional

from ray_lightning_tpu.checkpoint import latest_checkpoint
from ray_lightning_tpu.resilience.health import HealthMonitor, HeartbeatCallback
from ray_lightning_tpu.resilience.policy import (
    FailureKind,
    RetryPolicy,
    classify_failure,
)
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)


@dataclasses.dataclass
class ResilienceConfig:
    """Everything supervise() needs beyond the job itself.

    ``checkpoint_dir`` is the supervisor's OWN durable state: periodic
    step-cadence saves, preemption emergency saves, and the resume
    source of truth all live there (keep it distinct from a
    user ModelCheckpoint's dirpath — the supervisor prunes it).
    """

    checkpoint_dir: str
    policy: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    save_every_n_steps: int = 50
    keep_checkpoints: int = 2       # >= 2: corrupt-latest still resumes
    heartbeat_interval_s: float = 5.0
    stall_timeout_s: float = 180.0  # <= 0 disables health monitoring
    startup_grace_s: float = 600.0
    preempt_grace_s: float = 30.0
    resume: str = "auto"            # "auto" | "never": pick up an earlier
    #                                 run's checkpoints on first launch
    faults: Optional[str] = None    # fault-plan spec (faults.parse_faults)
    fault_state_dir: Optional[str] = None  # fire-once markers across
    #                                 restarts (defaults beside ckpts)
    #: step-cadence checkpoints stream in the background instead of
    #: stalling every rank at each save (checkpoint/io.py block=False;
    #: docs/PERFORMANCE.md). Emergency preemption saves always block.
    async_save: bool = True
    #: persistent XLA compile cache shared across restarts, so attempt N
    #: deserializes the train step instead of recompiling it (the cold
    #: compile otherwise multiplies by the restart budget). Passed to the
    #: trainer; None leaves the pipeline/compile_cache.py resolver's
    #: choice (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    compile_cache_dir: Optional[str] = None
    #: trainguard (resilience/guard.py): a GuardConfig (or True for the
    #: defaults) compiles the in-step anomaly guard into every worker's
    #: train step and arms escalation + the SDC probe. A CORRUPTION
    #: escalation makes the supervisor ROLL BACK: resume from the last
    #: blessed checkpoint at/below the marker's last-good step, advance
    #: the data order past the poisoned window, and record any
    #: quarantined rank in <checkpoint_dir>/.quarantine.json.
    guard: Any = None
    #: telemetry (telemetry/, docs/OBSERVABILITY.md): True (default)
    #: arms the span recorder in every worker with the run's shared
    #: ``<checkpoint_dir>/telemetry`` dir, the driver records its own
    #: attempt/backoff spans there, and supervise() assembles the
    #: goodput classification into SupervisedResult.goodput +
    #: ``telemetry/goodput.json``. False disables end to end.
    telemetry: Any = True
    #: elastic supervision (elastic/budget.py, docs/ELASTIC.md): an
    #: ElasticBudget makes the world size a LADDER instead of a pin —
    #: when the retry policy refuses another same-size relaunch (k
    #: hosts gone for good), the supervisor reshards the latest valid
    #: checkpoint onto the largest legal survivor world and resumes
    #: smaller; when the budget's capacity oracle reports capacity
    #: back, it grows on the next relaunch. Every change is recorded
    #: in SupervisedResult.reshards with its honest batch plan. None
    #: (default): fixed world size, exactly the old behavior.
    elastic: Any = None
    #: SLO watch (telemetry/watch.py, docs/OBSERVABILITY.md "watch
    #: rules & incidents"): True (or a WatchConfig / rule tuple) arms
    #: the declarative rule engine over the run's persisted evidence —
    #: evaluated driver-side after every classified failure and at the
    #: terminal bookkeeping, pure tail-bounded reads, ZERO effect on
    #: the compiled program (same discipline as telemetry=off,
    #: test-pinned). Breaches land in <checkpoint_dir>/incidents.jsonl
    #: and in SupervisedResult.incidents. None (default): off.
    watch: Any = None

    def resolved_telemetry_dir(self) -> Optional[str]:
        if not self.telemetry:
            return None
        from ray_lightning_tpu.telemetry import TelemetryConfig

        cfg = TelemetryConfig.coerce(self.telemetry)
        return cfg.dir or os.path.join(self.checkpoint_dir, "telemetry")


@dataclasses.dataclass
class SupervisedResult:
    """The job's FitResult plus the supervision ledger."""

    result: Any                     # runtime.fit.FitResult
    restarts: int                   # retryable restarts performed
    preemptions: int                # preemption resumes performed
    failures: List[Dict[str, Any]]  # classified history, launch order
    rollbacks: int = 0              # trainguard corruption rollbacks
    quarantined: List[int] = dataclasses.field(default_factory=list)
    #                                 ranks the SDC probe attributed
    #: goodput classification of the TOTAL supervised wall time
    #: (telemetry/goodput.py buckets; None when telemetry is off) —
    #: also written to <checkpoint_dir>/telemetry/goodput.json
    goodput: Optional[Dict[str, Any]] = None
    #: elastic world-size changes, launch order (docs/ELASTIC.md): one
    #: entry per shrink/grow with from/to world, reason, and the honest
    #: batch plan (ElasticBudget.batch_plan). Also persisted append-only
    #: to <checkpoint_dir>/reshards.jsonl with a clock-alignment header
    #: (the timeline merger ingests it — docs/OBSERVABILITY.md)
    reshards: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)
    #: watch-rule breaches fired during supervision
    #: (ResilienceConfig.watch; the on-disk record is
    #: <checkpoint_dir>/incidents.jsonl)
    incidents: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)

    @property
    def final_world(self) -> Optional[int]:
        """World size of the attempt that finished (None = unchanged
        from launch). Only ACTUAL world changes count — the ledger
        also records ``grow_refused`` entries (capacity-oracle
        refusals, docs/AUTOSCALE.md) which carry no ``to_world``."""
        for entry in reversed(self.reshards):
            if entry.get("reason") in ("shrink", "grow"):
                return entry["to_world"]
        return None

    @property
    def total_attempts(self) -> int:
        return 1 + self.restarts + self.preemptions + self.rollbacks


class SupervisedFailure(RuntimeError):
    """A supervised run that will not be retried: FATAL classification.
    The original exception (WorkerError with rank + log tail) is chained
    as __cause__."""

    def __init__(self, classified, attempts: int):
        self.classified = classified
        self.attempts = attempts
        super().__init__(
            f"supervised run failed FATALLY after {attempts} attempt(s): "
            f"[{classified.kind}/{classified.cause}"
            + (f" rank {classified.rank}" if classified.rank is not None
               else "")
            + f"] {classified.detail} — restarts will not help; see the "
              "chained worker error for the rank-tagged traceback and "
              "log tail")


class RestartBudgetExceeded(SupervisedFailure):
    def __init__(self, classified, attempts: int, budget: int):
        RuntimeError.__init__(
            self,
            f"supervised run still failing after {attempts} attempt(s) "
            f"(restart budget {budget} exhausted): "
            f"[{classified.kind}/{classified.cause}] {classified.detail}")
        self.classified = classified
        self.attempts = attempts


#: on-disk reshard ledger beside the checkpoints — the elastic story's
#: evidence stream (previously only in-memory on SupervisedResult)
RESHARD_LEDGER = "reshards.jsonl"
RESHARD_LEDGER_VERSION = "rlt-reshards-v1"


def _append_reshard_ledger(directory: str, entry: Dict[str, Any]) -> None:
    """Append one reshard entry (shrink/grow/grow_refused) to
    ``<directory>/reshards.jsonl``, writing the clock-alignment header
    first when creating the file — the same ``t0_wall``/monotonic
    stamp every other ledger carries, so the timeline merger
    (telemetry/timeline.py) never guesses this stream's epoch. Entries
    additionally carry their own epoch ``at`` stamp. Best-effort: a
    failed bookkeeping write must never cost the run its relaunch."""
    try:
        with open(os.path.join(directory, RESHARD_LEDGER), "a") as f:
            if f.tell() == 0:
                f.write(json.dumps({
                    "version": RESHARD_LEDGER_VERSION,
                    "t0_wall": time.time(),
                    "t0_perf": time.perf_counter(),
                    "pid": os.getpid(),
                }) + "\n")
            f.write(json.dumps(entry, default=str) + "\n")
    except OSError:
        log.exception("could not append to the reshard ledger")


def _wrapped_trainer_factory(trainer_factory: Callable[[], Any],
                             cfg: ResilienceConfig):
    """Runs in EVERY worker process (shipped by value via cloudpickle):
    the user's trainer plus the supervision callbacks."""
    from ray_lightning_tpu.core.callbacks import ModelCheckpoint
    from ray_lightning_tpu.resilience.faults import (
        FaultInjector,
        faults_from_env,
        parse_faults,
    )
    from ray_lightning_tpu.resilience.preempt import (
        PreemptionGuard,
        reset_preemption,
    )

    trainer = trainer_factory()
    reset_preemption()  # fresh process; stale flags impossible but cheap
    if cfg.compile_cache_dir and not trainer.compile_cache_dir:
        trainer.compile_cache_dir = cfg.compile_cache_dir
    has_periodic = any(
        isinstance(c, ModelCheckpoint)
        and getattr(c, "dirpath", None) == cfg.checkpoint_dir
        for c in trainer.callbacks)
    # Async step-cadence saves only when this job is single-process: the
    # in-tree orbax finalizes multi-host writes with a sync_global_devices
    # barrier (an XLA psum) on its background commit thread, which could
    # interleave with the step's own collectives mid-epoch. Multi-process
    # jobs keep the blocking save until the barrier rides the
    # coordination service (docs/PERFORMANCE.md "async checkpointing").
    import jax

    async_ok = cfg.async_save and jax.process_count() == 1
    if not has_periodic:
        trainer.callbacks.append(ModelCheckpoint(
            dirpath=cfg.checkpoint_dir, monitor=None,
            every_n_train_steps=max(1, cfg.save_every_n_steps),
            save_top_k=max(2, cfg.keep_checkpoints),
            async_save=async_ok))
    if cfg.heartbeat_interval_s > 0:
        trainer.callbacks.append(
            HeartbeatCallback(cfg.heartbeat_interval_s))
    trainer.callbacks.append(PreemptionGuard(
        cfg.checkpoint_dir, grace_s=cfg.preempt_grace_s,
        signals=(_signal.SIGTERM,)))
    if cfg.guard:
        from ray_lightning_tpu.resilience.guard import (
            GuardCallback,
            GuardConfig,
            read_rollback_marker,
        )

        trainer.guard = GuardConfig.coerce(cfg.guard)
        if not any(isinstance(c, GuardCallback) for c in trainer.callbacks):
            trainer.callbacks.append(GuardCallback(
                trainer.guard, marker_dir=cfg.checkpoint_dir))
        marker = read_rollback_marker(cfg.checkpoint_dir)
        if marker:
            # after a corruption rollback: advance the data order past
            # the poisoned window (trainer._apply_rollback_skip; stale
            # markers from older incidents no-op there)
            trainer.resume_skip_past = marker
    tdir = cfg.resolved_telemetry_dir()
    if tdir and trainer.telemetry is None:
        # every supervised worker records spans + goodput ledgers into
        # the run's shared telemetry dir; an explicit Trainer(telemetry=)
        # wins — the user already chose a destination
        from ray_lightning_tpu.telemetry import TelemetryConfig

        trainer.telemetry = TelemetryConfig(dir=tdir)
    faults = parse_faults(cfg.faults) if cfg.faults else faults_from_env()
    if faults:
        state_dir = (cfg.fault_state_dir
                     or os.environ.get("RLT_FAULT_STATE_DIR")
                     or os.path.join(cfg.checkpoint_dir, ".fault_state"))
        trainer.callbacks.append(FaultInjector(faults, state_dir))
    return trainer


def supervise(
    kind: str,
    module_factory: Callable[[], Any],
    trainer_factory: Callable[[], Any],
    data_factory: Callable[[], Any],
    num_processes: int,
    *,
    resilience: ResilienceConfig,
    **kw: Any,
) -> SupervisedResult:
    """Run one distributed job under supervision; returns the job result
    plus the restart ledger. Accepts every ``run_distributed`` keyword."""
    from functools import partial

    from ray_lightning_tpu.runtime.fit import run_distributed

    cfg = resilience
    policy = cfg.policy
    os.makedirs(cfg.checkpoint_dir, exist_ok=True)

    original_ckpt = kw.pop("ckpt_path", None)
    ckpt_path = original_ckpt
    if kind == "fit" and cfg.resume == "auto":
        found = latest_checkpoint(cfg.checkpoint_dir)
        if found is not None:
            log.info("supervise: resuming from earlier run's %s", found)
            ckpt_path = found

    world = num_processes
    launch_world = num_processes

    def _make_monitor(n: int) -> Optional[HealthMonitor]:
        if (kind == "fit" and cfg.stall_timeout_s > 0
                and cfg.heartbeat_interval_s > 0):
            # fit only: HeartbeatCallback starts its sender in
            # on_fit_start, which the eval-family jobs never fire — a
            # monitor there would declare a healthy long validate()
            # hung at startup_grace_s
            return HealthMonitor(
                n, stall_timeout_s=cfg.stall_timeout_s,
                startup_grace_s=cfg.startup_grace_s)
        return None

    monitor: Optional[HealthMonitor] = _make_monitor(world)

    user_q = kw.pop("on_queue_item", None)
    user_watchdog = kw.pop("watchdog", None)

    def _watchdog() -> None:
        if monitor is not None:
            monitor.check()
        if user_watchdog is not None:
            user_watchdog()

    def _on_queue_item(rank: int, item: Any) -> None:
        if monitor is not None and monitor.consume(rank, item):
            return
        if user_q is not None:
            user_q(rank, item)
        elif callable(item):
            item()  # the pump trampoline the group would have applied
        else:
            log.debug("dropping non-callable queue item from rank %d", rank)

    wrapped_tf = partial(_wrapped_trainer_factory, trainer_factory, cfg)

    # driver-side telemetry: attempt/backoff spans into the run's shared
    # dir (rank -1 = the driver), plus the wall/backoff ledger the
    # goodput assembly closes its books against
    telemetry_dir = (cfg.resolved_telemetry_dir() if kind == "fit"
                     else None)
    driver_rec = None
    if telemetry_dir:
        from ray_lightning_tpu.telemetry.spans import (
            PH_ATTEMPT,
            PH_BACKOFF,
            TelemetryRecorder,
        )

        driver_rec = TelemetryRecorder(directory=telemetry_dir, rank=-1)
    wall_t0 = time.perf_counter()
    backoff_s = 0.0

    # SLO watch (telemetry/watch.py): driver-side rule evaluation over
    # the run's persisted evidence — polled after every classified
    # failure (restart-rate breaches fire mid-run, not post-mortem)
    # and at the terminal bookkeeping (goodput_fraction sees the
    # assembled report). Pure file reads: the workers' compiled
    # program is untouched (test-pinned).
    watch_engine = None
    if kind == "fit" and cfg.watch:
        from ray_lightning_tpu.telemetry.watch import (
            WatchConfig,
            WatchEngine,
        )

        # telemetry_dir threaded explicitly: a TelemetryConfig(dir=...)
        # run keeps its spans/goodput ledgers OUTSIDE
        # <checkpoint_dir>/telemetry, and the watch must read where
        # they actually are
        watch_engine = WatchEngine(cfg.checkpoint_dir,
                                   WatchConfig.coerce(cfg.watch),
                                   telemetry_dir=telemetry_dir)

    def _watch_poll() -> List[Dict[str, Any]]:
        if watch_engine is None:
            return []
        try:
            watch_engine.poll()
        except Exception:  # noqa: BLE001 — observability must never
            # cost the run its result
            log.exception("watch evaluation failed")
        return list(watch_engine.incidents)

    def _assemble(restarts, preemptions, rollbacks):
        if telemetry_dir is None:
            return None
        from ray_lightning_tpu.telemetry import goodput as _gp

        try:
            if driver_rec is not None:
                driver_rec.close()
            report = _gp.assemble_goodput(
                telemetry_dir, time.perf_counter() - wall_t0,
                backoff_s=backoff_s, restarts=restarts,
                preemptions=preemptions, rollbacks=rollbacks)
            _gp.write_goodput(telemetry_dir, report)
            return report
        except Exception:  # noqa: BLE001 — accounting must never cost
            # the run its result
            log.exception("goodput assembly failed")
            return None

    restarts = 0
    preemptions = 0
    rollbacks = 0
    quarantined: List[int] = []
    failures: List[Dict[str, Any]] = []
    reshards: List[Dict[str, Any]] = []
    while True:
        if monitor is not None:
            monitor.reset()
        attempts = 1 + restarts + preemptions + rollbacks
        try:
            attempt_ctx = (driver_rec.span(PH_ATTEMPT,
                                           meta={"attempt": attempts,
                                                 "world": world})
                           if driver_rec is not None
                           else contextlib.nullcontext())
            with attempt_ctx:
                result = run_distributed(
                    kind, module_factory, wrapped_tf, data_factory,
                    world,
                    ckpt_path=ckpt_path,
                    on_queue_item=_on_queue_item,
                    watchdog=(_watchdog if (monitor is not None
                                            or user_watchdog is not None)
                              else None),
                    **kw,
                )
            goodput = _assemble(restarts, preemptions, rollbacks)
            return SupervisedResult(result, restarts, preemptions,
                                    failures, rollbacks, quarantined,
                                    goodput=goodput,
                                    reshards=reshards,
                                    incidents=_watch_poll())
        except BaseException as exc:
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            fc = classify_failure(exc)
            failures.append({"attempt": attempts, **fc.to_dict(),
                             "at": time.time()})
            log.warning("supervised attempt %d failed: [%s/%s] %s",
                        attempts, fc.kind, fc.cause, fc.detail)
            # mid-run watch cadence: a restart-rate / guard-streak
            # breach fires NOW, while an operator can still act on it
            _watch_poll()
            if fc.kind == FailureKind.FATAL:
                # land the driver's attempt/backoff spans for the
                # post-mortem report before failing for good
                _assemble(restarts, preemptions, rollbacks)
                _watch_poll()
                raise SupervisedFailure(fc, attempts) from exc
            allowed = policy.allows(restarts, preemptions, fc, rollbacks)
            new_world = None
            if (kind == "fit" and cfg.elastic is not None
                    and fc.kind != FailureKind.CORRUPTION):
                # elastic supervision (docs/ELASTIC.md): a refused
                # same-size relaunch becomes a SHRINK onto the largest
                # legal survivor world; an allowed relaunch whose
                # capacity oracle reports a different size moves toward
                # it (growth back when capacity returns). Only ACTUAL
                # world changes spend max_reshards — refusal records in
                # the ledger are free.
                spent = sum(1 for e in reshards
                            if e.get("reason") in ("shrink", "grow"))
                new_world, grow_refusal = _elastic_decision(
                    cfg.elastic, world, launch_world, allowed, spent)
                if grow_refusal is not None:
                    # the oracle kept a shrunk run small: record its
                    # answer (worlds + source) in the reshard ledger —
                    # the capacity truth is auditable, never implicit
                    refusal_entry = {**grow_refusal,
                                     "attempt": attempts,
                                     "at": time.time()}
                    reshards.append(refusal_entry)
                    _append_reshard_ledger(cfg.checkpoint_dir,
                                           refusal_entry)
                    log.warning(
                        "supervise: grow %d -> %d refused — capacity "
                        "oracle (%s) reports %s schedulable world(s)",
                        world, grow_refusal["resolved_max"],
                        grow_refusal["capacity_source"],
                        grow_refusal["capacity"])
            if new_world is None and not allowed:
                _assemble(restarts, preemptions, rollbacks)
                _watch_poll()
                raise RestartBudgetExceeded(
                    fc, attempts,
                    policy.max_rollbacks
                    if fc.kind == FailureKind.CORRUPTION
                    else policy.max_restarts) from exc
            if fc.kind == FailureKind.PREEMPTION:
                preemptions += 1
            elif fc.kind == FailureKind.CORRUPTION:
                rollbacks += 1
            else:
                restarts += 1
            delay = policy.next_delay(restarts + preemptions + rollbacks)
            if fc.kind == FailureKind.CORRUPTION and kind == "fit":
                ckpt_path = _rollback_target(cfg, rollbacks, quarantined,
                                             original_ckpt)
            elif kind == "fit":
                found = latest_checkpoint(cfg.checkpoint_dir)
                ckpt_path = found if found is not None else original_ckpt
            if new_world is not None:
                from ray_lightning_tpu.elastic.reshard import ReshardError

                try:
                    entry = _begin_reshard(cfg, world, new_world,
                                           ckpt_path, attempts,
                                           driver_rec)
                except ReshardError as rexc:
                    if allowed:
                        # a refused resize (legacy resume source) must
                        # not cost an otherwise-allowed same-size
                        # relaunch — skip the resize, keep supervising
                        log.error("supervise: elastic resize %d -> %d "
                                  "refused (%s); relaunching same-size",
                                  world, new_world, rexc)
                    else:
                        # the fixed-size budget is spent AND the resize
                        # cannot proceed: terminal — land the goodput
                        # postmortem like every other terminal path and
                        # fail with the classified cause, the refusal
                        # chained underneath
                        _assemble(restarts, preemptions, rollbacks)
                        _watch_poll()
                        raise RestartBudgetExceeded(
                            fc, attempts, policy.max_restarts) from rexc
                else:
                    reshards.append(entry)
                    _append_reshard_ledger(cfg.checkpoint_dir, entry)
                    world = new_world
                    monitor = _make_monitor(world)
            log.warning(
                "supervise: restart %d (retryable %d, preemptions %d, "
                "rollbacks %d) in %.1fs at world %d, resuming from %s",
                restarts + preemptions + rollbacks, restarts,
                preemptions, rollbacks, delay, world,
                ckpt_path or "scratch")
            backoff_ctx = (driver_rec.span(PH_BACKOFF)
                           if driver_rec is not None
                           else contextlib.nullcontext())
            with backoff_ctx:
                time.sleep(delay)
            backoff_s += delay


def _elastic_target_world(budget, world: int, launch_world: int,
                          allowed: bool,
                          reshards_done: int) -> Optional[int]:
    """Back-compat wrapper over `_elastic_decision`: just the target
    world (tests and external callers keep their contract)."""
    return _elastic_decision(budget, world, launch_world, allowed,
                             reshards_done)[0]


def _elastic_decision(budget, world: int, launch_world: int,
                      allowed: bool, reshards_done: int):
    """The elastic supervision decision (docs/ELASTIC.md): given the
    current world, whether the retry policy still allows a SAME-SIZE
    relaunch, and how many topology changes were already spent, pick
    the next world size — or None for "no change" (the caller then
    relaunches same-size or, when !allowed, exhausts the budget).

      * !allowed — the fixed-size story is over (k hosts are not
        coming back within budget): shrink to the largest legal world
        STRICTLY below the current one, bounded by reported capacity.
      * allowed + the capacity oracle reports a different size: move
        toward it (this is how a shrunk run grows back — the next
        relaunch after capacity returns resumes at the bigger world).

    Returns ``(target, grow_refusal)``: ``target`` is None for "no
    change"; ``grow_refusal`` is a ledger-shaped dict when the run sits
    BELOW its resolved max and the capacity oracle's answer is what
    kept it there — the supervisor records the oracle's answer (worlds
    + source, docs/AUTOSCALE.md "capacity oracle") in the reshard
    ledger so a run that stayed small has its reason on the record.

    Never proposes the current world, never exceeds max_reshards, and
    only proposes rungs `ElasticBudget.legal` accepts (divisibility via
    the plan checker's own MeshSpec/dp_degree machinery)."""
    if budget is None or reshards_done >= budget.max_reshards:
        return None, None
    answer = budget.capacity_answer(launch_world)
    raw_cap = answer.worlds if answer.worlds is not None \
        else budget.resolved_max(launch_world)
    cap = min(raw_cap, budget.resolved_max(launch_world))
    if not allowed:
        return (budget.largest_legal(min(cap, world - 1), launch_world),
                None)
    if cap != world:
        target = budget.largest_legal(cap, launch_world)
        if target is not None and target != world:
            return target, None
    if world < budget.resolved_max(launch_world) and cap <= world:
        # a shrunk run could grow but the oracle says capacity has not
        # returned: refuse, and say WHO said so
        return None, {
            "reason": "grow_refused",
            "from_world": world,
            "resolved_max": budget.resolved_max(launch_world),
            "capacity": raw_cap,
            "capacity_source": answer.source,
            "capacity_detail": answer.detail,
        }
    return None, None


def _begin_reshard(cfg: ResilienceConfig, world: int, new_world: int,
                   ckpt_path: Optional[str], attempts: int,
                   driver_rec) -> Dict[str, Any]:
    """Validate + record one elastic world change. The resume source
    must carry sharding provenance (a legacy checkpoint can only be
    restored onto the identical sharding — resharding it would be a
    silent lie about what was trained); the actual cross-topology
    restore happens worker-side in the relaunched trainer
    (core/trainer.py `_reshard_move`), accounted as the `reshard`
    goodput bucket."""
    from ray_lightning_tpu.checkpoint.io import read_meta
    from ray_lightning_tpu.elastic.reshard import (
        ReshardError,
        validate_reshard,
    )

    move = None
    if ckpt_path is not None:
        meta = read_meta(ckpt_path)
        if "mesh_spec" not in meta:
            raise ReshardError(
                f"elastic resize {world} -> {new_world} refused: resume "
                f"source {ckpt_path} carries no sharding provenance "
                "(legacy checkpoint — its writing mesh is unknowable, "
                "so the move cannot be validated). Re-save it once on "
                "the current mesh, or start the elastic run from a "
                "provenance-stamped checkpoint")
        # mesh-level validation against the WRITER's provenance, with
        # the budget's REAL mesh template as the target (largest_legal
        # only proposed worlds the template resolves at); the worker
        # validates again against the mesh it actually builds
        target_sizes = cfg.elastic.spec_for(new_world).resolve(
            new_world).sizes()
        move = validate_reshard(meta, target_sizes)["from_mesh"]
    entry: Dict[str, Any] = {
        "from_world": world,
        "to_world": new_world,
        "reason": "shrink" if new_world < world else "grow",
        "attempt": attempts,
        "at": time.time(),
        "ckpt": ckpt_path,
        "from_mesh": move,
        "batch_plan": cfg.elastic.batch_plan(world, new_world),
    }
    log.warning(
        "supervise: elastic %s %d -> %d (resuming from %s); batch "
        "plan: %s", entry["reason"], world, new_world,
        ckpt_path or "scratch",
        entry["batch_plan"].get("note", "global batch preserved"))
    if driver_rec is not None:
        from ray_lightning_tpu.telemetry.spans import PH_RESHARD

        with driver_rec.span(PH_RESHARD, meta={
                k: entry[k] for k in ("from_world", "to_world",
                                      "reason", "attempt")}):
            pass
    return entry


def _rollback_target(cfg: ResilienceConfig, rollbacks: int,
                     quarantined: List[int],
                     original_ckpt: Optional[str]) -> Optional[str]:
    """Pick the resume source after a trainguard CORRUPTION escalation:
    the newest BLESSED checkpoint at/below the marker's last-good step
    (a blessed-but-newer one could already carry the silent corruption
    the probe only just caught). Also folds the marker's quarantine
    verdict into the ledger and the on-disk ``.quarantine.json`` the
    next scheduler/operator reads, and stamps the rollback count back
    into the marker so the relaunched workers can surface it as the
    ``guard_rollbacks`` metric."""
    import json

    from ray_lightning_tpu.resilience.guard import (
        QUARANTINE_FILE,
        read_rollback_marker,
        write_rollback_marker,
    )

    marker = read_rollback_marker(cfg.checkpoint_dir) or {}
    max_step = marker.get("last_good_step")
    for rank in marker.get("quarantine") or []:
        if rank not in quarantined:
            quarantined.append(rank)
    if quarantined:
        qpath = os.path.join(cfg.checkpoint_dir, QUARANTINE_FILE)
        tmp = qpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"excluded": sorted(quarantined),
                       "at": time.time()}, f)
        os.replace(tmp, qpath)
        log.error("supervise: quarantining rank(s) %s (divergent "
                  "parameter fingerprint) — recorded in %s",
                  sorted(quarantined), qpath)
    if marker:
        write_rollback_marker(cfg.checkpoint_dir,
                              {**marker, "rollbacks_performed": rollbacks})
    if max_step is not None:
        # Abandon the poisoned window FOR GOOD: every checkpoint newer
        # than the last known-good step moves into quarantined.ckpts/
        # (kept for forensics, out of every candidate set). Without
        # this, a later RETRYABLE/PREEMPTION restart — or a driver
        # relaunch with resume="auto" — would pick the newest
        # blessed-but-silently-poisoned checkpoint right back up. Safe
        # to move here: the worker group is already torn down.
        _quarantine_newer_checkpoints(cfg.checkpoint_dir, int(max_step))
    found = latest_checkpoint(
        cfg.checkpoint_dir, good_only=True,
        max_step=int(max_step) if max_step is not None else None)
    if found is None:
        log.warning("supervise: no blessed checkpoint at/below step %s — "
                    "rolling back to %s", max_step,
                    original_ckpt or "scratch")
    return found if found is not None else original_ckpt


def _quarantine_newer_checkpoints(directory: str, max_step: int) -> None:
    """Move checkpoint subdirs with a recorded global_step above the
    rollback horizon into ``<directory>/quarantined.ckpts/`` — one
    level down, so ``latest_checkpoint`` (which scans immediate
    subdirs) never sees them again."""
    import json

    dest_root = os.path.join(directory, "quarantined.ckpts")
    try:
        names = os.listdir(directory)
    except OSError:
        return
    for name in names:
        cand = os.path.join(directory, name)
        meta_path = os.path.join(cand, "meta.json")
        if not os.path.isdir(os.path.join(cand, "state")):
            continue
        try:
            with open(meta_path) as f:
                step = int(json.load(f).get("global_step", -1))
        except (OSError, ValueError, TypeError):
            continue  # unreadable: verify_checkpoint already rejects it
        if step <= max_step:
            continue
        os.makedirs(dest_root, exist_ok=True)
        try:
            os.rename(cand, os.path.join(
                dest_root, f"{name}.rb{int(time.time())}"))
            log.warning("supervise: quarantined poisoned checkpoint %s "
                        "(step %d > last good %d)", cand, step, max_step)
        except OSError:
            log.exception("could not quarantine checkpoint %s", cand)


def fit_supervised(
    module_factory: Callable[[], Any],
    trainer_factory: Callable[[], Any],
    data_factory: Callable[[], Any],
    num_processes: int,
    *,
    resilience: ResilienceConfig,
    **kw: Any,
) -> SupervisedResult:
    """Supervised ``fit_distributed``: every transient pod failure becomes
    a resumed run instead of a lost one. See supervise()."""
    return supervise("fit", module_factory, trainer_factory, data_factory,
                     num_processes, resilience=resilience, **kw)
