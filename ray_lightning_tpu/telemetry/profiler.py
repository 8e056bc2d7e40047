"""On-demand ``jax.profiler`` capture, driven from the fit loop.

A production incident is never reproduced with a profiler armed from the
start — the capture has to be armable on a RUNNING job. Three
triggers, all host-side and cadence-guarded (a whole-fit trace is the
first with ``start_step=1``):

    step window   ``ProfileConfig(start_step=500, num_steps=5)`` —
                  deterministic capture of a known-bad region;
    marker file   touch ``<dir>/CAPTURE`` (or a configured path) on the
                  worker's filesystem; the loop polls it on the logging
                  cadence and captures the next ``num_steps`` steps;
    SIGUSR1       ``signal=True`` installs a handler that sets a flag
                  (async-signal-safe: no jax work in the handler); the
                  loop picks it up at the next batch boundary.

Rank-scoped (``ranks=(0,)`` by default): an 8-host capture of the same
SPMD program is 8x the bytes for no new information. CPU-safe: when
``jax.profiler`` cannot start on this backend the controller logs ONE
loud note and disarms — profiling must never be able to kill a fit.
"""
from __future__ import annotations

import dataclasses
import os
import signal as _signal
import threading
from typing import Any, Optional, Tuple

from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)

#: marker filename polled inside the profile dir when no explicit
#: marker_file is configured
DEFAULT_MARKER = "CAPTURE"


@dataclasses.dataclass
class ProfileConfig:
    """``Trainer(profile=ProfileConfig(...))`` — see module docstring."""

    dir: str = "rlt_profile"
    #: capture [start_step, start_step + num_steps) deterministically;
    #: None = no step-window trigger (marker/signal only)
    start_step: Optional[int] = None
    num_steps: int = 5
    #: path polled for the marker trigger; None derives <dir>/CAPTURE
    marker_file: Optional[str] = None
    #: install a SIGUSR1 handler as the third trigger
    signal: bool = False
    #: ranks that capture (the trace is identical SPMD work everywhere)
    ranks: Tuple[int, ...] = (0,)
    #: marker/signal polling cadence in steps (host stat() is cheap but
    #: the idiom is cadence-guarded like every other telemetry touch)
    poll_every_n_steps: int = 5

    @classmethod
    def coerce(cls, value: Any) -> Optional["ProfileConfig"]:
        if value is None or value is False:
            return None
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(dir=value)
        raise TypeError(
            f"profile= takes True, a directory string, or a "
            f"ProfileConfig; got {type(value).__name__}")


class ProfilerController:
    """Owns one capture lifecycle; the trainer calls ``on_step(step)``
    once per batch (host-side, no device touch)."""

    def __init__(self, config: ProfileConfig, rank: int = 0):
        self.config = config
        self.rank = rank
        self.active = rank in tuple(config.ranks)
        self.capturing = False
        self.captures = 0
        self.disabled_reason: Optional[str] = None
        self._stop_at: Optional[int] = None
        self._signal_flag = threading.Event()
        self._marker = config.marker_file or os.path.join(
            config.dir, DEFAULT_MARKER)
        if self.active and config.signal:
            try:
                _signal.signal(_signal.SIGUSR1,
                               lambda *_: self._signal_flag.set())
            except (ValueError, OSError):
                # non-main thread / platform without SIGUSR1: the other
                # triggers still work
                log.warning("profiler: could not install SIGUSR1 trigger; "
                            "step-window/marker triggers remain armed")

    # ---- trigger evaluation (host-side, cadence-guarded) -----------------

    def _should_start(self, step: int) -> bool:
        cfg = self.config
        if cfg.start_step is not None and step == cfg.start_step:
            return True
        if step % max(1, cfg.poll_every_n_steps) == 0:
            if self._signal_flag.is_set():
                self._signal_flag.clear()
                return True
            if os.path.exists(self._marker):
                try:
                    os.remove(self._marker)  # one marker = one capture
                except OSError:
                    pass
                return True
        return False

    def on_step(self, step: int) -> None:
        """Advance the capture state machine at one batch boundary."""
        if not self.active or self.disabled_reason:
            return
        if self.capturing:
            if self._stop_at is not None and step >= self._stop_at:
                self._stop(step)
            return
        if self._should_start(step):
            self._start(step)

    # ---- capture ---------------------------------------------------------

    def _start(self, step: int) -> None:
        import jax

        try:
            # makedirs inside the guard: an unwritable profile dir must
            # disarm the profiler, not abort the training run
            os.makedirs(self.config.dir, exist_ok=True)
            jax.profiler.start_trace(self.config.dir)
        except Exception as exc:  # noqa: BLE001 — never kill the fit
            self.disabled_reason = f"{type(exc).__name__}: {exc}"
            log.error(
                "profiler: jax.profiler.start_trace failed on this "
                "backend (%s) — capture DISABLED for this run; profiling "
                "is a no-op here, not an error in your job",
                self.disabled_reason)
            return
        self.capturing = True
        self._stop_at = step + max(1, self.config.num_steps)
        log.warning("profiler: capture armed at step %d for %d steps -> %s",
                    step, self.config.num_steps, self.config.dir)

    def _stop(self, step: int) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001
            self.disabled_reason = f"{type(exc).__name__}: {exc}"
            log.error("profiler: stop_trace failed (%s); capture disabled",
                      self.disabled_reason)
        else:
            self.captures += 1
            log.warning("profiler: capture complete at step %d (XPlane "
                        "trace under %s)", step, self.config.dir)
        self.capturing = False
        self._stop_at = None

    def close(self) -> None:
        """Fit teardown: a capture left open (fit ended mid-window) is
        closed so the trace file finalizes."""
        if self.capturing:
            self._stop(self._stop_at or 0)
