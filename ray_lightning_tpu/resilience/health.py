"""Per-worker heartbeats + a stall watchdog over the existing queue channel.

The runtime's failure detector (group._check_liveness) only sees a worker
that DIED. A worker that is alive-but-wedged — a deadlocked collective, a
device that stopped answering — looks identical to one spending 20 minutes in XLA
compilation, and the reference's answer (Ray actor health checks) is gone.
The distinction this module draws:

  live channel, step advancing     -> healthy
  live channel, step frozen        -> "compiling or slow step": logged
                                      once, NOT killed (big-model compiles
                                      legitimately take tens of minutes;
                                      killing them would re-pay the
                                      compile forever)
  silent channel past the budget   -> hung: StallError (RETRYABLE)

Worker side: ``HeartbeatCallback`` runs a daemon thread that ships a tiny
dict over ``session.put_queue`` — the same side channel tune reports ride,
so no new sockets, and items interleave with results in the driver pump.
Driver side: ``HealthMonitor.consume`` absorbs those items from the pump's
``on_queue_item`` and ``HealthMonitor.check`` runs inside the pump's idle
slices (WorkerGroup.wait's ``watchdog`` hook).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from ray_lightning_tpu.analysis.lockwatch import san_lock
from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu.resilience.policy import StallError
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)

#: queue items with this "kind" are heartbeats, consumed by the monitor
#: before user on_queue_item callbacks ever see them
HEARTBEAT_KIND = "rlt.heartbeat"


def make_heartbeat(rank: int, step: int, phase: str = "step",
                   span: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``phase`` is the worker's CURRENT telemetry span phase (what the
    main thread is inside right now); ``span`` is the last completed
    span's summary — together a silent-channel stall report can say
    "hung in ckpt_stall at step 812" instead of just "hung"."""
    hb = {"kind": HEARTBEAT_KIND, "rank": rank, "step": int(step),
          "phase": phase, "sent_at": time.time()}
    if span:
        hb["span"] = {"phase": span.get("phase"),
                      "dur": span.get("dur"), "step": span.get("step")}
    return hb


def is_heartbeat(item: Any) -> bool:
    return isinstance(item, dict) and item.get("kind") == HEARTBEAT_KIND


class HeartbeatCallback(Callback):
    """Worker-side sender. A plain daemon thread (not the training loop)
    so heartbeats keep flowing while the main thread sits inside a
    compile or a long collective — that is precisely the signal that
    distinguishes "compiling" from "hung"."""

    def __init__(self, interval_s: float = 5.0):
        self.interval_s = interval_s
        self._stop: Optional[threading.Event] = None
        self._trainer = None

    def on_fit_start(self, trainer, module) -> None:
        from ray_lightning_tpu.runtime import session

        if not session.is_session_enabled():
            return  # not inside a runtime worker (e.g. local Trainer.fit)
        self._trainer = trainer
        self._stop = threading.Event()
        rank = session.get_actor_rank()
        stop = self._stop

        def _beat():
            while not stop.wait(self.interval_s):
                try:
                    step = int(self._trainer.global_step)
                    # the telemetry recorder's live phase is the
                    # authoritative answer to "what is this worker
                    # doing"; without one, fall back to the step-counter
                    # heuristic this module used before telemetry existed
                    rec = getattr(self._trainer, "telemetry_recorder",
                                  None)
                    phase = rec.current_phase() if rec is not None \
                        and rec.enabled else ""
                    span = rec.last_span() if rec is not None \
                        and rec.enabled else None
                    if not phase:
                        phase = "step" if step > 0 else "setup"
                    session.put_queue(
                        make_heartbeat(rank, step, phase, span=span))
                except Exception:  # noqa: BLE001 — channel closing during
                    # teardown, or a send racing shutdown; never crash the
                    # worker over telemetry
                    return

        threading.Thread(target=_beat, daemon=True,
                         name=f"rlt-heartbeat-{rank}").start()

    def _shutdown(self) -> None:
        if self._stop is not None:
            self._stop.set()
            self._stop = None

    def on_fit_end(self, trainer, module) -> None:
        self._shutdown()

    def on_exception(self, trainer, module, exc) -> None:
        self._shutdown()


class HealthMonitor:
    """Driver-side staleness tracker.

    ``stall_timeout_s`` — silent-channel budget AFTER a rank's first
    heartbeat (before it, ``startup_grace_s`` applies: spawn + imports +
    jax.distributed rendezvous happen heartbeat-less).
    ``step_stall_note_s`` — live-channel-no-progress threshold for the
    advisory "compiling or slow step" log line.
    """

    def __init__(self, num_workers: int, stall_timeout_s: float = 180.0,
                 startup_grace_s: float = 600.0,
                 step_stall_note_s: float = 120.0):
        self.num_workers = num_workers
        self.stall_timeout_s = stall_timeout_s
        self.startup_grace_s = startup_grace_s
        self.step_stall_note_s = step_stall_note_s
        self._lock = san_lock("resilience.health.monitor")
        self.reset()

    def reset(self) -> None:
        with self._lock:
            now = time.monotonic()
            self._started = now
            self._last_seen: Dict[int, float] = {}
            self._last_step: Dict[int, int] = {}
            self._step_since: Dict[int, float] = {}
            self._last_phase: Dict[int, str] = {}
            self._noted_stall: set = set()

    def consume(self, rank: int, item: Any) -> bool:
        """Absorb ``item`` if it is a heartbeat; True when consumed."""
        if not is_heartbeat(item):
            return False
        now = time.monotonic()
        with self._lock:
            hb_rank = int(item.get("rank", rank))
            step = int(item.get("step", -1))
            self._last_seen[hb_rank] = now
            self._last_phase[hb_rank] = str(item.get("phase", ""))
            if self._last_step.get(hb_rank) != step:
                self._last_step[hb_rank] = step
                self._step_since[hb_rank] = now
                self._noted_stall.discard(hb_rank)
        return True

    def check(self, now: Optional[float] = None) -> None:
        """Raise StallError for a hung rank; log (once per stall episode)
        for a live-but-not-stepping rank. Called from the pump's idle
        slices — must stay cheap."""
        now = time.monotonic() if now is None else now
        with self._lock:
            for rank in range(self.num_workers):
                seen = self._last_seen.get(rank)
                if seen is None:
                    if now - self._started > self.startup_grace_s:
                        raise StallError(
                            rank, now - self._started,
                            "no heartbeat ever arrived (worker never "
                            "reached the fit loop)")
                    continue
                silent = now - seen
                if silent > self.stall_timeout_s:
                    raise StallError(
                        rank, silent,
                        phase=self._last_phase.get(rank, ""),
                        step=self._last_step.get(rank, -1))
                frozen = now - self._step_since.get(rank, now)
                if (frozen > self.step_stall_note_s
                        and rank not in self._noted_stall):
                    self._noted_stall.add(rank)
                    phase = self._last_phase.get(rank, "")
                    if phase == "compile":
                        # not an inference from a frozen counter: the
                        # worker's live compile span says so
                        log.warning(
                            "rank %d: inside an XLA compile for %.0fs "
                            "(telemetry span; heartbeats live, step %d) "
                            "— not killing; big-model compiles "
                            "legitimately take tens of minutes",
                            rank, frozen, self._last_step.get(rank, -1))
                    else:
                        log.warning(
                            "rank %d: heartbeats live but step %d "
                            "unchanged for %.0fs%s — a slow step or a "
                            "wedged phase (not killing; the "
                            "silent-channel budget is %.0fs)",
                            rank, self._last_step.get(rank, -1), frozen,
                            f" (phase {phase!r})" if phase else "",
                            self.stall_timeout_s)

    def snapshot(self) -> Dict[int, Dict[str, Any]]:
        """Telemetry view (tests + CLI): per-rank last-seen age / step /
        reported phase."""
        now = time.monotonic()
        with self._lock:
            return {
                r: {"silent_s": now - self._last_seen[r],
                    "step": self._last_step.get(r, -1),
                    "phase": self._last_phase.get(r, "")}
                for r in self._last_seen
            }
