"""The sweep runner: S concurrent trials, each a driver of its own workers.

Rebuild of the reference's signature three-level topology (SURVEY §3.3):
Tune driver -> trial actors -> training-worker actors, where each trial
runs the ENTIRE distributed-fit stack inside itself (reference
examples/ray_ddp_example.py:101-113; tests/test_tune.py). Here:

  sweep driver (this module)
    -> trial processes       (one runtime worker process per trial,
                              process-isolated like a Ray trial actor)
      -> training workers    (the trial calls Trainer.fit directly, or
                              fit_distributed to launch its own SPMD
                              worker group — the nested case)

Differences by design:
  * resource accounting is integral-slice (resources.py), not the
    reference's extra_cpu oversubscription trick (SURVEY §7.4 #4);
  * the report channel is duplex — the scheduler's verdict returns on the
    same socket and a stopped trial unwinds cooperatively via
    TrialStopped (schedulers.py), instead of Tune killing the actor;
  * checkpoints never transit the channel — trials write them in place
    and report paths (SURVEY §2.4 scaling hazard, consciously fixed).
"""
from __future__ import annotations

import os
import secrets
import threading
import traceback
from collections import deque
from multiprocessing.connection import Listener
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_lightning_tpu.analysis.lockwatch import san_lock
from ray_lightning_tpu.runtime.group import WorkerGroup, WorkerError
from ray_lightning_tpu.sweep import session as trial_session
from ray_lightning_tpu.sweep.analysis import ExperimentAnalysis, Trial
from ray_lightning_tpu.sweep.resources import ResourcePool, TpuResources
from ray_lightning_tpu.sweep.schedulers import (
    CONTINUE,
    FIFOScheduler,
    TrialScheduler,
)
from ray_lightning_tpu.sweep.space import expand
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)


class SweepError(RuntimeError):
    pass


class _HostPool:
    """Free-list of cluster hosts for trial placement (the reference let
    Ray's scheduler put trial actors on any node; here placement is
    explicit: each process-executor trial borrows `resources.hosts` hosts
    for its lifetime and returns them)."""

    def __init__(self, hosts):
        self._free = list(hosts)
        self._lock = san_lock("sweep.tuner.hosts")

    def try_acquire(self, n: int):
        with self._lock:
            if len(self._free) < n:
                return None
            taken, self._free = self._free[:n], self._free[n:]
            return taken

    def release(self, hosts) -> None:
        with self._lock:
            self._free.extend(hosts)


def _probe_device_count(executor: str) -> int:
    """Default chip-pool size.

    With process-isolated trials the DRIVER must not initialize the
    accelerator backend (on TPU, libtpu is exclusively held by whichever
    process touches it first — the driver grabbing it would starve every
    trial's workers), so the topology is probed in a throwaway subprocess.
    Inline trials run in this process and will initialize jax anyway.
    """
    if executor == "inline":
        import jax

        return len(jax.devices())
    import subprocess
    import sys

    try:
        out = subprocess.run(
            [sys.executable, "-c", "import jax; print(len(jax.devices()))"],
            capture_output=True, text=True, timeout=120,
        )
        return int(out.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001 — fall back to a safe minimum
        log.warning("device-count probe failed; defaulting the pool to 1 "
                    "chip — pass total_chips explicitly")
        return 1


def _trial_main(trainable, config, trial_id, trial_dir, address, authkey_hex,
                resume_from=None):
    """Body of one trial — runs inside the trial's own worker process
    (the analog of the reference's trial-actor trainable,
    reference examples/ray_ddp_example.py:61-76)."""
    ctx = trial_session.RemoteTrialContext(
        trial_id, trial_dir, address, bytes.fromhex(authkey_hex),
        last_checkpoint=resume_from,
    )
    trial_session.init_trial_session(ctx)
    # Nested SPMD workers launched by this trial inherit the trial identity
    # through the environment (sweep/callbacks.py resolves trial_dir from it
    # when the trial session object itself isn't bound in the worker).
    os.environ["RLT_TRIAL_ID"] = trial_id
    os.environ["RLT_TRIAL_DIR"] = trial_dir
    if resume_from:
        os.environ["RLT_TRIAL_RESUME"] = resume_from
    try:
        result = trainable(config)
        return (Trial.DONE, result)
    except trial_session.TrialStopped:
        return (Trial.STOPPED, None)
    finally:
        ctx.close()
        trial_session.reset_trial_session()


class _ReportServer:
    """Driver-side end of the duplex report channel: accepts one socket
    per trial, answers every report with the scheduler's verdict."""

    def __init__(self, handle_report: Callable[[str, Dict, Optional[str]], str],
                 bind_all: bool = False):
        self._handle = handle_report
        self._authkey = secrets.token_bytes(32)
        # Remote trials must reach the channel: bind the cluster-facing
        # interface and advertise its address (cf. WorkerGroup.start —
        # binding the SPECIFIC interface, not 0.0.0.0, keeps the
        # authenticated-but-cleartext pickle channel off networks no
        # trial dials in on; trusted-network assumption documented in
        # runtime/transport.py SECURITY note).
        from ray_lightning_tpu.runtime.group import routable_ip

        self._advertise = routable_ip() if bind_all else "127.0.0.1"
        if bind_all and self._advertise == "127.0.0.1":
            raise RuntimeError(
                "cannot determine a routable address for host-placed "
                "trials (no default route). Set RLT_NODE_IP to this "
                "machine's cluster-facing IP."
            )
        try:
            self._listener = Listener((self._advertise, 0),
                                      authkey=self._authkey)
        except OSError:
            # advertise may be a NAT/forwarded address that is valid to
            # dial but not a local interface (cf. WorkerGroup.start's
            # identical fallback)
            log.warning(
                "report-channel advertise address %s is not a local "
                "interface; binding 0.0.0.0 (ensure the network path to "
                "trials is trusted)", self._advertise,
            )
            self._listener = Listener(("0.0.0.0", 0), authkey=self._authkey)
        self._closed = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True
        )
        self._accept_thread.start()

    @property
    def address(self) -> tuple:
        return (self._advertise, self._listener.address[1])

    @property
    def authkey_hex(self) -> str:
        return self._authkey.hex()

    def _accept_loop(self) -> None:
        # Split accept from authentication: with the listener possibly on
        # 0.0.0.0 for host-placed trials, a peer that stalls or resets
        # mid-auth-challenge must neither wedge nor kill the acceptor —
        # later trials still need to hand-shake. The socket-level accept
        # (internal but stable: SocketListener.accept returns the raw
        # Connection, no challenge) only ever blocks waiting for NEW
        # connections; the blocking challenge runs on the per-connection
        # thread, so a hostile peer wedges only its own thread.
        import time as _time

        while not self._closed:
            try:
                conn = self._listener._listener.accept()
            except Exception:  # noqa: BLE001 — keep serving
                if self._closed:
                    return  # listener closed by close()
                log.warning("report server: accept failed\n%s",
                            traceback.format_exc(limit=2))
                # bound a persistent failure (e.g. EMFILE) to a warm
                # trickle instead of a hot busy-loop flooding the log
                _time.sleep(0.2)
                continue
            threading.Thread(
                target=self._auth_and_serve, args=(conn,), daemon=True
            ).start()

    def _auth_and_serve(self, conn) -> None:
        from multiprocessing.connection import (
            answer_challenge,
            deliver_challenge,
        )

        try:
            deliver_challenge(conn, self._authkey)
            answer_challenge(conn, self._authkey)
        except Exception:  # noqa: BLE001 — scanner / wrong key / reset
            log.warning("report server: rejected connection\n%s",
                        traceback.format_exc(limit=2))
            try:
                conn.close()
            except OSError:
                pass
            return
        self._serve(conn)

    def _serve(self, conn) -> None:
        try:
            while True:
                msg = conn.recv()
                if msg[0] == "report":
                    _, trial_id, metrics, ckpt = msg
                    # A handler error must still produce a reply — the trial
                    # is blocked on recv() and would hang forever otherwise.
                    try:
                        verdict = self._handle(trial_id, metrics, ckpt)
                    except Exception:  # noqa: BLE001
                        log.error("report handler failed for %s:\n%s",
                                  trial_id, traceback.format_exc())
                        verdict = CONTINUE
                    conn.send(verdict)
                elif msg[0] in ("hello", "bye"):
                    if msg[0] == "bye":
                        return
                else:
                    log.warning("report server: unknown message %r", msg[0])
        except (EOFError, OSError):
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self) -> None:
        self._closed = True
        self._listener.close()


class TrialRunner:
    def __init__(
        self,
        trainable: Callable[[Dict[str, Any]], Any],
        configs: List[Dict[str, Any]],
        *,
        metric: Optional[str],
        mode: str,
        scheduler: TrialScheduler,
        resources_per_trial: TpuResources,
        pool: ResourcePool,
        max_concurrent: Optional[int],
        storage_dir: str,
        executor: str,
        trial_timeout: Optional[float],
        env: Optional[Dict[str, str]],
        hosts: Optional[List[str]] = None,
        transport=None,
        retry_policy=None,
    ):
        self.trainable = trainable
        self.metric = metric
        self.mode = mode
        self.scheduler = scheduler
        self.resources = resources_per_trial
        self.pool = pool
        self.storage_dir = storage_dir
        self.executor = executor
        self.trial_timeout = trial_timeout
        self.env = env
        self.transport = transport
        #: trial-level retry (resilience/policy.py RetryPolicy): an
        #: infra-classified trial failure re-enqueues the trial, resuming
        #: from its last registered checkpoint, instead of burying a
        #: whole config under one flaky host
        self.retry_policy = retry_policy
        self.host_pool: Optional[_HostPool] = None
        if hosts:
            if transport is None or not transport.is_remote:
                # fail here, not inside the trial threads — a per-thread
                # ValueError would strand `running` and deadlock the sweep
                raise SweepError(
                    "hosts= requires a remote transport (e.g. SSHTransport)"
                )
            if resources_per_trial.hosts > len(hosts):
                raise SweepError(
                    f"one trial needs {resources_per_trial.hosts} hosts but "
                    f"only {len(hosts)} were given"
                )
            self.host_pool = _HostPool(hosts)
        cap = pool.max_concurrent(resources_per_trial)
        if cap < 1:
            raise SweepError(
                f"one trial needs {resources_per_trial.chips} chips but the "
                f"pool has {pool.total_chips}"
            )
        self.max_concurrent = min(max_concurrent or cap, cap)
        self._lock = san_lock("sweep.tuner.runner")
        self._cond = threading.Condition(self._lock)
        self.trials: List[Trial] = []
        for i, cfg in enumerate(configs):
            tid = f"trial_{i:05d}"
            tdir = os.path.join(storage_dir, tid)
            os.makedirs(tdir, exist_ok=True)
            trial = Trial(tid, cfg, tdir, resources_per_trial)
            # Resume: a rerun over an existing storage_dir restores each
            # trial's recorded progress; interrupted/errored trials restart
            # from their last registered checkpoint (extends reference
            # tune.py:128-142 with the restore direction).
            self._load_trial_state(trial)
            self.trials.append(trial)
        self._by_id = {t.trial_id: t for t in self.trials}

    # --------------------------------------------------------- persistence
    def _state_path(self, trial: Trial) -> str:
        return os.path.join(trial.trial_dir, "trial_state.json")

    def _snapshot_trial_state(self, trial: Trial) -> Tuple[str, Dict]:
        """Copy the mutable trial record (cheap, in-memory) — safe to
        call under self._lock; the file write happens outside it."""
        import json

        state = {
            "status": trial.status,
            "history": list(trial.history),
            "checkpoints": list(trial.checkpoints),
            "error": trial.error,
        }
        try:
            json.dumps(trial.result)
            state["result"] = trial.result
        except (TypeError, ValueError):
            pass  # non-JSON trainable return: status/history still persist
        return self._state_path(trial), state

    def _write_trial_state(self, trial_id: str, path: str,
                           state: Dict) -> None:
        import json

        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(state, f)
            os.replace(tmp, path)
        except (OSError, TypeError, ValueError) as exc:
            log.warning("could not persist %s state: %s", trial_id, exc)

    def _save_trial_state(self, trial: Trial) -> None:
        """Durable per-trial record (atomic rename) so a later sweep.run
        over the same storage_dir can skip DONE trials and resume the rest.
        Never call this holding self._lock — snapshot under the lock and
        write outside (threadcheck RLT705: every report thread and the
        scheduler loop contend on that lock; disk latency must not
        serialize them)."""
        path, state = self._snapshot_trial_state(trial)
        self._write_trial_state(trial.trial_id, path, state)

    def _load_trial_state(self, trial: Trial) -> None:
        import json

        path = self._state_path(trial)
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                state = json.load(f)
        except (OSError, ValueError) as exc:
            log.warning("ignoring unreadable %s state: %s",
                        trial.trial_id, exc)
            return
        trial.history = list(state.get("history", []))
        if trial.history:
            trial.last_result = trial.history[-1]
        trial.checkpoints = list(state.get("checkpoints", []))
        trial.result = state.get("result")
        status = state.get("status")
        if status in (Trial.DONE, Trial.STOPPED):
            # terminal: DONE finished; STOPPED was the scheduler's
            # deliberate early-kill — resurrecting it would let an
            # intentionally-culled config back into the race
            trial.status = status
        # Anything else (error / a stale "running" from a crashed driver)
        # stays PENDING and will be re-scheduled, resuming from
        # trial.last_checkpoint if one was registered.

    # ------------------------------------------------------------- reports
    def _handle_report(self, trial_id: str, metrics: Dict[str, Any],
                       checkpoint: Optional[str]) -> str:
        with self._lock:
            trial = self._by_id.get(trial_id)
            if trial is None:
                log.warning("report from unknown trial %s", trial_id)
                return CONTINUE
            iteration = trial.iterations + 1
            record = dict(metrics)
            # Ray Tune parity: every report carries training_iteration
            # (asserted by the reference's tests, test_tune.py:44-45).
            record.setdefault("training_iteration", iteration)
            trial.history.append(record)
            trial.last_result = record
            if checkpoint:
                trial.checkpoints.append(checkpoint)
            key = self.scheduler.metric or self.metric
            value = record.get(key) if key else None
            try:
                value = float(value) if value is not None else None
            except (TypeError, ValueError):
                value = None  # non-numeric metric: scheduler sees no signal
            verdict = self.scheduler.on_result(trial_id, iteration, value)
            if verdict != CONTINUE:
                log.info("scheduler stopping %s at iteration %d", trial_id,
                         iteration)
            path, state = self._snapshot_trial_state(trial)
        # The state file write runs OUTSIDE self._lock: every report
        # thread and the scheduler loop contend on it, and a slow disk
        # must not serialize trial scheduling (RLT705 regression,
        # pinned by test_concurrency_lint.py).
        self._write_trial_state(trial_id, path, state)
        return verdict

    # --------------------------------------------------------------- retry
    def _retry_delay(self, trial: "Trial",
                     exc: BaseException) -> Optional[float]:
        """Backoff delay when this failure should be retried, else None.
        Reuses the resilience failure taxonomy: FATAL (a deterministic
        user exception) is never retried — replaying a bug N times would
        just burn the budget a flaky host needs."""
        if self.retry_policy is None:
            return None
        from ray_lightning_tpu.resilience.policy import classify_failure

        fc = classify_failure(exc)
        if not fc.restartable or trial.restarts >= self.retry_policy.max_restarts:
            return None
        trial.restarts += 1
        delay = self.retry_policy.next_delay(trial.restarts)
        log.warning(
            "trial %s: retry %d/%d in %.1fs after [%s/%s] %s "
            "(resuming from %s)", trial.trial_id, trial.restarts,
            self.retry_policy.max_restarts, delay, fc.kind, fc.cause,
            fc.detail, trial.last_checkpoint or "scratch")
        return delay

    # -------------------------------------------------------------- inline
    def _run_inline(self) -> None:
        for trial in self.trials:
            if trial.status in (Trial.DONE, Trial.STOPPED):
                log.info("skipping %s: already %s", trial.trial_id,
                         trial.status)
                self.scheduler.on_trial_complete(trial.trial_id)
                continue
            self._run_inline_trial(trial)
            self.scheduler.on_trial_complete(trial.trial_id)
            self._save_trial_state(trial)

    def _run_inline_trial(self, trial: "Trial") -> None:
        import time as _time

        while True:
            trial.status = Trial.RUNNING
            # rebuilt per attempt: a retry must resume from the LAST
            # registered checkpoint, not the one the first attempt saw
            ctx = trial_session.LocalTrialContext(
                trial.trial_id, trial.trial_dir, self._handle_report,
                last_checkpoint=trial.last_checkpoint,
            )
            trial_session.init_trial_session(ctx)
            saved_env = {k: os.environ.get(k)
                         for k in ("RLT_TRIAL_ID", "RLT_TRIAL_DIR",
                                   "RLT_TRIAL_RESUME")}
            os.environ["RLT_TRIAL_ID"] = trial.trial_id
            os.environ["RLT_TRIAL_DIR"] = trial.trial_dir
            if trial.last_checkpoint:
                os.environ["RLT_TRIAL_RESUME"] = trial.last_checkpoint
            retry_in: Optional[float] = None
            try:
                trial.result = self.trainable(trial.config)
                trial.status = Trial.DONE
            except trial_session.TrialStopped:
                trial.status = Trial.STOPPED
            except BaseException as exc:  # noqa: BLE001 — recorded per trial
                retry_in = self._retry_delay(trial, exc)
                if retry_in is None:
                    trial.status = Trial.ERROR
                    trial.error = traceback.format_exc()
                    log.error("trial %s failed: %s", trial.trial_id, exc)
            finally:
                trial_session.reset_trial_session()
                for k, v in saved_env.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            if retry_in is None:
                return
            _time.sleep(retry_in)

    # ------------------------------------------------------------- process
    def _run_process(self) -> None:
        server = _ReportServer(
            self._handle_report,
            # only trials actually placed off-machine need a routable
            # report channel; otherwise stay on loopback
            bind_all=self.host_pool is not None,
        )
        terminal = (Trial.DONE, Trial.STOPPED)
        for t in self.trials:
            if t.status in terminal:
                log.info("skipping %s: already %s", t.trial_id, t.status)
                self.scheduler.on_trial_complete(t.trial_id)
        pending = deque(t for t in self.trials if t.status not in terminal)
        running: set = set()
        try:
            with self._cond:
                while pending or running:
                    while pending and len(running) < self.max_concurrent:
                        if not self.pool.try_acquire(self.resources):
                            break
                        trial_hosts = None
                        if self.host_pool is not None:
                            trial_hosts = self.host_pool.try_acquire(
                                self.resources.hosts
                            )
                            if trial_hosts is None:
                                self.pool.release(self.resources)
                                break
                        trial = pending.popleft()
                        running.add(trial.trial_id)
                        trial.status = Trial.RUNNING
                        threading.Thread(
                            target=self._trial_thread,
                            args=(trial, server, running, trial_hosts,
                                  pending),
                            daemon=True,
                        ).start()
                    self._cond.wait(timeout=1.0)
        finally:
            server.close()

    def _trial_thread(self, trial: Trial, server: _ReportServer,
                      running: set, trial_hosts=None,
                      pending: Optional[deque] = None) -> None:
        group = None
        retry_in: Optional[float] = None
        try:
            env = {**(self.env or {}),
                   "RLT_TRIAL_ID": trial.trial_id,
                   "RLT_TRIAL_DIR": trial.trial_dir}
            if trial_hosts:
                # the FULL borrowed host set rides the env so the trial's
                # nested fit_distributed can span all of them
                # (sweep.get_trial_hosts())
                env["RLT_TRIAL_HOSTS"] = ",".join(trial_hosts)
            # cross-host trial placement: the trial-driver process runs on
            # its first borrowed host (reference: Ray scheduled trial
            # actors on any node); nested SPMD workers launch from there
            group = WorkerGroup(
                num_workers=1,
                env=env,
                log_dir=os.path.join(trial.trial_dir, "logs"),
                hosts=trial_hosts[:1] if trial_hosts else None,
                transport=self.transport if trial_hosts else None,
            )
            group.start()
            [out] = group.run(
                _trial_main,
                per_rank_args=[(self.trainable, trial.config, trial.trial_id,
                                trial.trial_dir, server.address,
                                server.authkey_hex, trial.last_checkpoint)],
                timeout=self.trial_timeout,
            )
            trial.status, trial.result = out
        except WorkerError as exc:
            retry_in = self._retry_delay(trial, exc)
            if retry_in is None:
                trial.status = Trial.ERROR
                trial.error = exc.traceback_str
                log.error("trial %s failed:\n%s", trial.trial_id,
                          exc.traceback_str)
        except BaseException as exc:  # noqa: BLE001 — recorded per trial
            retry_in = self._retry_delay(trial, exc)
            if retry_in is None:
                trial.status = Trial.ERROR
                trial.error = traceback.format_exc()
                log.error("trial %s infra failure:\n%s", trial.trial_id,
                          trial.error)
        finally:
            if group is not None:
                group.shutdown()
            self.pool.release(self.resources)
            if trial_hosts and self.host_pool is not None:
                self.host_pool.release(trial_hosts)
            if retry_in is None:
                # terminal outcome only — a retried trial is not complete
                self.scheduler.on_trial_complete(trial.trial_id)
            self._save_trial_state(trial)
            if retry_in is not None:
                # resources are released; the backoff costs only this
                # daemon thread and one concurrency slot
                import time as _time

                _time.sleep(retry_in)
            with self._cond:
                if retry_in is not None and pending is not None:
                    trial.status = Trial.PENDING
                    pending.append(trial)
                running.discard(trial.trial_id)
                self._cond.notify_all()

    # ----------------------------------------------------------------- run
    def run(self) -> List[Trial]:
        if self.executor == "inline":
            self._run_inline()
        elif self.executor == "process":
            self._run_process()
        else:
            raise ValueError(f"unknown executor {self.executor!r}")
        return self.trials


def run(
    trainable: Callable[[Dict[str, Any]], Any],
    config: Optional[Dict[str, Any]] = None,
    *,
    num_samples: int = 1,
    metric: Optional[str] = None,
    mode: str = "min",
    scheduler: Optional[TrialScheduler] = None,
    resources_per_trial: Optional[TpuResources] = None,
    total_chips: Optional[int] = None,
    total_cpus: Optional[int] = None,
    max_concurrent: Optional[int] = None,
    storage_dir: Optional[str] = None,
    name: str = "sweep",
    executor: str = "process",
    trial_timeout: Optional[float] = None,
    env: Optional[Dict[str, str]] = None,
    hosts: Optional[List[str]] = None,
    transport=None,
    seed: int = 0,
    raise_on_failed_trial: bool = True,
    retry_policy=None,
) -> ExperimentAnalysis:
    """``tune.run`` analog (reference examples/ray_ddp_example.py:101-113).

    ``trainable(config)`` runs once per trial; inside it, ``sweep.report``
    (directly or via the TuneReportCallback family) streams metrics back.
    ``executor="process"`` gives Ray-Tune-style per-trial process isolation
    (each trial may itself launch an SPMD worker group); ``"inline"`` runs
    trials sequentially in this process (debug / single-host).

    ``total_chips`` is the pool the reserve-don't-occupy accounting carves
    integral per-trial blocks out of; it defaults to the number of visible
    devices (one v5p slice on a pod, the virtual CPU mesh in tests).

    ``hosts`` + a remote ``transport`` (runtime/transport.py) place each
    process-executor trial on a borrowed cluster host for its lifetime —
    the reference's "Tune schedules trial actors anywhere" capability;
    concurrency is additionally bounded by ``len(hosts) //
    resources_per_trial.hosts``. Ignored by the inline executor.

    ``retry_policy`` (resilience.RetryPolicy) retries trials whose
    failure classifies as infrastructure (a killed worker process, a
    timeout, a backend loss) up to ``max_restarts`` times with capped
    exponential backoff, resuming from the trial's last registered
    checkpoint; FATAL user exceptions still fail the trial immediately.
    """
    if mode not in ("min", "max"):
        raise ValueError("mode must be 'min' or 'max'")
    configs = expand(config or {}, num_samples=num_samples, seed=seed)
    if not configs:
        raise ValueError("empty search space")
    scheduler = scheduler or FIFOScheduler()
    if scheduler.metric is None:
        scheduler.metric = metric
        scheduler.mode = mode
    resources_per_trial = resources_per_trial or TpuResources()
    if total_chips is None:
        total_chips = max(_probe_device_count(executor),
                          resources_per_trial.chips)
    if total_cpus is None and resources_per_trial.cpus > 0:
        # trials reserve CPUs -> account against this machine's cores
        # (reference analog: Tune's cluster CPU pool)
        total_cpus = max(os.cpu_count() or 1, resources_per_trial.cpus)
    pool = ResourcePool(total_chips, total_cpus)
    storage_dir = storage_dir or os.path.join(os.getcwd(), "rlt_sweeps", name)
    os.makedirs(storage_dir, exist_ok=True)

    runner = TrialRunner(
        trainable, configs,
        metric=metric, mode=mode, scheduler=scheduler,
        resources_per_trial=resources_per_trial, pool=pool,
        max_concurrent=max_concurrent, storage_dir=storage_dir,
        executor=executor, trial_timeout=trial_timeout, env=env,
        hosts=hosts, transport=transport, retry_policy=retry_policy,
    )
    log.info("sweep %s: %d trials, <=%d concurrent, %d chips/trial of %d",
             name, len(runner.trials), runner.max_concurrent,
             resources_per_trial.chips, total_chips)
    trials = runner.run()
    analysis = ExperimentAnalysis(trials, metric, mode)
    failed = analysis.errors()
    if failed and raise_on_failed_trial:
        detail = "\n".join(f"--- {k} ---\n{v}" for k, v in failed.items())
        raise SweepError(f"{len(failed)} trial(s) failed:\n{detail}")
    return analysis
