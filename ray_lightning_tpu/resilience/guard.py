"""trainguard: in-step numerics guard, SDC detection, rollback-to-good.

PR 3's supervisor recovers from *process* failures; this module covers
the failure mode that actually ruins long TPU runs — the process stays
alive while training goes bad. Three tiers (docs/RESILIENCE.md
"trainguard"):

  tier 1  in-jit detection and skip. The train step already computes
          ``loss`` and ``grad_norm`` (core/trainer.py); the guard adds a
          finiteness check plus a loss-spike test against an EMA carried
          in the TrainState, and on anomaly a tree-select discards the
          update — params/opt-state/step pass through UNCHANGED, so one
          poisoned batch costs one skipped update, not the run. All of
          it compiles into the existing step: the anomaly flag and the
          counters ride the step's metrics outputs, which the trainer
          already fetches lazily on the log cadence — ZERO new host
          transfers (the guarded step must lint clean under RLT304 and
          its jaxpr carries no new effects; tests/test_trainguard.py
          pins both).

  tier 2  escalation and rollback. ``GuardCallback`` watches the
          counters at the moments they are host-resident anyway (the
          trainer's metric-fetch cadence — reading them costs nothing)
          and, when K anomalous steps land inside the window, writes a
          rollback marker and raises ``TrainingAnomalyError``. The
          supervisor classifies it CORRUPTION, resumes from the last
          **blessed** checkpoint (``latest_checkpoint(good_only=True,
          max_step=last_good_step)`` — the trainer stamps an
          anomaly-free-window verdict into every checkpoint's meta) and
          advances the data order past the poisoned window instead of
          replaying it.

  tier 3  SDC probe. At a configurable cadence the guard computes a
          cheap per-device parameter fingerprint (bitcast-to-uint32
          wraparound sum — order-independent, exact) via shard_map, one
          scalar per device, gathered with a single small collective.
          Devices that hold identical parameter bytes by construction
          (replicas: same coordinates on every sharded mesh axis) must
          produce identical fingerprints; a minority digest identifies
          the divergent device, and its host rank is quarantined in the
          rollback marker. A silent bit-flip on one chip is caught
          within one probe cadence instead of corrupting every
          checkpoint thereafter.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)

#: rollback marker file, written beside the supervisor's checkpoints on
#: escalation; the supervisor reads it to pick the rollback target and
#: the relaunched worker reads it to advance the data order. Stale
#: markers are self-invalidating: they apply only when their
#: detected_step is ahead of the restored step.
ROLLBACK_MARKER = ".trainguard_rollback.json"

#: quarantine ledger the supervisor maintains next to the marker —
#: ranks whose hardware produced a divergent parameter fingerprint.
QUARANTINE_FILE = ".quarantine.json"


# --------------------------------------------------------------- config


@dataclasses.dataclass
class GuardConfig:
    """Knobs for all three tiers. The defaults are sized for "a NaN or a
    10x loss spike is an anomaly; a handful of them in quick succession
    is corruption"."""

    #: tier 1 master switch (the compiled-in checks)
    enabled: bool = True
    #: loss > spike_factor * EMA + spike_margin => anomaly (the margin
    #: keeps near-zero losses from flagging noise)
    spike_factor: float = 10.0
    spike_margin: float = 1.0
    ema_decay: float = 0.9
    #: anomaly-free steps the EMA observes before the spike test arms
    #: (finiteness checks are armed from step 0)
    warmup_steps: int = 5
    #: tier 2: escalate when >= escalate_after anomalies land within the
    #: trailing escalate_window steps. Detection latency is bounded by
    #: the trainer's metric-fetch cadence (log_every_n_steps) — the
    #: counters are only read when they are host-resident anyway.
    escalate_after: int = 4
    escalate_window: int = 16
    #: a checkpoint is stamped blessed iff no anomaly occurred within
    #: this many updates before the save (and no streak is active)
    bless_clean_steps: int = 4
    #: tier 3: run the SDC fingerprint probe every N steps (0 disables)
    sdc_every_n_steps: int = 0

    @classmethod
    def coerce(cls, value) -> "GuardConfig":
        if value is True:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, dict):
            return cls(**value)
        raise TypeError(f"cannot build GuardConfig from {value!r}")


# ----------------------------------------------------------- tier 1 jit


@flax.struct.dataclass
class GuardState:
    """The guard's slice of the TrainState — five replicated scalars, so
    carrying it costs nothing next to the params."""

    ema: jnp.ndarray           # f32: EMA of finite losses
    seen: jnp.ndarray          # i32: finite losses observed (EMA warmup)
    skipped: jnp.ndarray       # i32: total anomalous updates discarded
    streak: jnp.ndarray        # i32: consecutive anomalous steps
    last_anomaly: jnp.ndarray  # i32: update index of the last anomaly, -1


def init_guard_state() -> GuardState:
    return GuardState(
        ema=jnp.zeros((), jnp.float32),
        seen=jnp.zeros((), jnp.int32),
        skipped=jnp.zeros((), jnp.int32),
        streak=jnp.zeros((), jnp.int32),
        last_anomaly=jnp.full((), -1, jnp.int32),
    )


def abstract_guard_state() -> GuardState:
    """ShapeDtypeStruct twin of ``init_guard_state`` for jaxpr-level
    audits (bench.py's guard summary) — no backend is ever touched."""
    s = jax.ShapeDtypeStruct
    return GuardState(ema=s((), jnp.float32), seen=s((), jnp.int32),
                      skipped=s((), jnp.int32), streak=s((), jnp.int32),
                      last_anomaly=s((), jnp.int32))


def apply_guard(cfg: GuardConfig, guard: GuardState, step, loss, grad_norm,
                new_params, old_params, new_opt, old_opt):
    """The tier-1 core, called INSIDE the jitted train step.

    Returns ``(params, opt_state, new_step, new_guard, metrics)``: on an
    anomaly the candidate update is discarded by a tree-select (params /
    opt-state / step pass through unchanged — the step index not
    advancing keeps the per-step RNG fold and optimizer bias-correction
    schedule identical to a run that never saw the poisoned batch), and
    the flag/counters are returned as ordinary metric scalars so they
    ride the existing lazy metrics fetch. No cond branches with side
    effects, no callbacks, no transfers.
    """
    loss32 = jnp.asarray(loss).astype(jnp.float32)
    gn32 = jnp.asarray(grad_norm).astype(jnp.float32)
    finite = jnp.isfinite(loss32) & jnp.isfinite(gn32)
    warmed = guard.seen >= cfg.warmup_steps
    spike = warmed & (loss32 > cfg.spike_factor * guard.ema
                      + cfg.spike_margin)
    bad = (~finite) | spike
    badi = bad.astype(jnp.int32)
    first = guard.seen == 0
    ema = jnp.where(
        bad, guard.ema,
        jnp.where(first, loss32,
                  cfg.ema_decay * guard.ema
                  + (1.0 - cfg.ema_decay) * loss32))
    new_guard = GuardState(
        ema=ema,
        seen=guard.seen + 1 - badi,
        skipped=guard.skipped + badi,
        streak=jnp.where(bad, guard.streak + 1, 0),
        last_anomaly=jnp.where(bad, jnp.asarray(step, jnp.int32),
                               guard.last_anomaly),
    )
    keep = lambda new, old: jnp.where(bad, old, new)  # noqa: E731
    params = jax.tree.map(keep, new_params, old_params)
    opt_state = jax.tree.map(keep, new_opt, old_opt)
    new_step = jnp.where(bad, step, step + 1)
    metrics = {
        "guard_anomaly": badi,
        "guard_skipped_steps": new_guard.skipped,
        "guard_streak": new_guard.streak,
        "guard_last_anomaly": new_guard.last_anomaly,
        "guard_loss_ema": ema,
    }
    return params, opt_state, new_step, new_guard, metrics


def bless_verdict(cfg: GuardConfig, guard_host, update_step: int) -> bool:
    """Anomaly-free-window verdict stamped into checkpoint meta
    (``blessed``): no active streak and the last anomaly at least
    ``bless_clean_steps`` updates behind the save point."""
    streak = int(np.asarray(guard_host.streak))
    last = int(np.asarray(guard_host.last_anomaly))
    return streak == 0 and (last < 0
                            or update_step - last >= cfg.bless_clean_steps)


# ------------------------------------------------------------ exceptions


class TrainingAnomalyError(RuntimeError):
    """Tier-2 escalation: K anomalous steps inside the window. The NAME
    is part of the protocol — it travels to the driver inside the worker
    traceback and ``policy.classify_failure`` keys on it (CORRUPTION)."""

    def __init__(self, detected_step: int, count: int, window: int,
                 last_good_step: int):
        self.detected_step = detected_step
        self.last_good_step = last_good_step
        super().__init__(
            f"training anomaly escalation: {count} anomalous step(s) "
            f"within the last {window} steps (detected at step "
            f"{detected_step}; last known-good step {last_good_step}) — "
            "rolling back to the last blessed checkpoint")


class SDCDetectedError(TrainingAnomalyError):
    """Tier-3 verdict: parameter fingerprints diverged across replicas —
    silent data corruption on the named rank(s)."""

    def __init__(self, suspect_ranks: Sequence[int], detected_step: int,
                 last_good_step: int, digests: Sequence[int] = ()):
        self.suspect_ranks = list(suspect_ranks)
        self.detected_step = detected_step
        self.last_good_step = last_good_step
        self.digests = list(digests)
        who = (f"rank(s) {self.suspect_ranks}" if self.suspect_ranks
               else "an unattributable replica (no majority)")
        RuntimeError.__init__(
            self,
            f"silent data corruption detected at step {detected_step}: "
            f"parameter fingerprints diverged across replicas — {who}; "
            f"last probe-verified step {last_good_step}. Rolling back "
            "to the last blessed checkpoint and quarantining the host")


# -------------------------------------------------------- rollback marker


def write_rollback_marker(dirpath: str, payload: Dict[str, Any]) -> None:
    """Atomic (tmp + os.replace), rank-0 only — same discipline as
    checkpoint meta.json. The marker is the worker->driver side channel
    that survives the process teardown."""
    if jax.process_index() != 0:
        return
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, ROLLBACK_MARKER)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def read_rollback_marker(dirpath: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(dirpath, ROLLBACK_MARKER)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# ------------------------------------------------------------ tier 3 SDC


def _leaf_digest(x) -> jnp.ndarray:
    """Bitcast-to-uint32 wraparound sum of one leaf block. Exact and
    order-independent (unsigned addition is associative/commutative mod
    2^32), so any reduction schedule yields the same fingerprint and a
    single flipped bit always changes it — EVERY stored bit must reach
    the sum (a lossy cast would make low-bit corruption invisible, the
    exact thing the probe exists to catch), so each dtype width is
    bitcast at its own width and 64-bit words are folded as two 32-bit
    halves."""
    nbits = jnp.dtype(x.dtype).itemsize * 8
    if jnp.issubdtype(x.dtype, jnp.floating):
        uint = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32,
                64: jnp.uint64}[nbits]
        u = jax.lax.bitcast_convert_type(x, uint)
    elif x.dtype == jnp.bool_:
        u = x.astype(jnp.uint32)
    else:
        u = x.astype({8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32,
                      64: jnp.uint64}[nbits])
    if u.dtype == jnp.uint64:  # only reachable with x64 enabled
        lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        return (jnp.sum(lo, dtype=jnp.uint32)
                + jnp.sum(hi, dtype=jnp.uint32))
    return jnp.sum(u.astype(jnp.uint32), dtype=jnp.uint32)


def _tree_digest(tree) -> jnp.ndarray:
    total = jnp.zeros((), jnp.uint32)
    for i, leaf in enumerate(jax.tree.leaves(tree)):
        # fold the leaf index in so two leaves swapping contents changes
        # the fingerprint despite the commutative sum
        total = total + _leaf_digest(leaf) * jnp.uint32(2 * i + 1)
    return total


def _spec_of(leaf):
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = getattr(leaf, "sharding", None)
    if isinstance(s, NamedSharding):
        return s.spec
    return P()


def replica_groups(params, mesh) -> List[List[int]]:
    """Groups of flat device indices (``mesh.devices.reshape(-1)``
    order) that hold bit-identical parameter bytes by construction:
    devices whose coordinates agree on every axis any param is sharded
    over. Pure DP -> one group of all devices; pure FSDP -> singletons
    (no redundancy to cross-check; the probe degrades to recording)."""
    sharded_axes: set = set()
    for leaf in jax.tree.leaves(params):
        for dim in _spec_of(leaf):
            if dim is None:
                continue
            for name in (dim if isinstance(dim, tuple) else (dim,)):
                sharded_axes.add(name)
    axes = tuple(mesh.axis_names)
    sizes = [dict(mesh.shape)[a] for a in axes]
    n = int(np.prod(sizes)) if sizes else 1
    groups: Dict[Tuple, List[int]] = {}
    for i in range(n):
        coords = np.unravel_index(i, sizes) if sizes else ()
        key = tuple(int(c) for a, c in zip(axes, coords)
                    if a in sharded_axes)
        groups.setdefault(key, []).append(i)
    return [g for g in groups.values() if len(g) >= 2]


def diagnose_digests(digests: Sequence[int],
                     groups: Sequence[Sequence[int]]
                     ) -> Tuple[List[int], bool]:
    """Compare per-device fingerprints within each replica group.
    Returns ``(suspect_device_indices, comparable)``: majority vote
    flags the minority devices; a group with no strict majority flags
    every disagreeing member (attribution indeterminate — with only two
    replicas a mismatch cannot name the liar). ``comparable`` is False
    when no group had redundancy to check."""
    suspects: set = set()
    comparable = False
    for g in groups:
        vals = [int(digests[i]) for i in g]
        counts = Counter(vals)
        comparable = True
        if len(counts) == 1:
            continue
        top, topn = counts.most_common(1)[0]
        if 2 * topn > len(g):
            suspects |= {i for i in g if int(digests[i]) != top}
        else:
            suspects |= set(g)
    return sorted(suspects), comparable


def build_sdc_probe(params, mesh):
    """Compile the fingerprint probe for this param tree/mesh.

    Returns ``(fn, devices, groups)``: ``fn(params)`` is a jitted
    function producing one uint32 fingerprint per device (a shard_map —
    each device digests its OWN local bytes, which is the whole point:
    under plain jit, XLA assumes replicas are consistent and a psum
    would launder the corruption away), gathered to a replicated
    ``(n_devices,)`` vector so every process can fetch it — one small
    collective per probe, nothing per step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = list(mesh.devices.flat)
    groups = replica_groups(params, mesh)
    if len(devices) == 1:
        fn = jax.jit(lambda p: _tree_digest(p).reshape((1,)))
        return fn, devices, groups
    specs = jax.tree.map(_spec_of, params)
    axes = tuple(mesh.axis_names)

    def per_device(p):
        return _tree_digest(p).reshape((1,))

    mapped = jax.shard_map(per_device, mesh=mesh, in_specs=(specs,),
                           out_specs=P(axes), check_vma=False)
    fn = jax.jit(mapped, out_shardings=NamedSharding(mesh, P()))
    return fn, devices, groups


# -------------------------------------------------------- GuardCallback


class GuardCallback(Callback):
    """Tiers 2+3, host side. Reads the tier-1 counters only at the
    moments the trainer has already fetched them (the log cadence) —
    escalation costs zero additional host syncs; the SDC probe runs
    under its own ``step % N == 0`` cadence guard."""

    def __init__(self, cfg: GuardConfig, marker_dir: Optional[str] = None):
        self.cfg = GuardConfig.coerce(cfg)
        self.marker_dir = marker_dir
        self._hist: List[Tuple[int, float]] = []   # (global_step, skipped)
        self._base = 0.0           # skipped count that aged out of the window
        self._last_good = 0
        self._probe = None
        self._probe_devices: List = []
        self._probe_groups: List[List[int]] = []
        self._probes_run = 0
        self._probe_ok_step = 0
        self._rollbacks_prior = 0

    # -- lifecycle ---------------------------------------------------------

    def _dir(self, trainer) -> str:
        return self.marker_dir or trainer.default_root_dir

    def on_fit_start(self, trainer, module) -> None:
        self._hist = []
        self._base = 0.0
        self._last_good = trainer.global_step
        self._probe_ok_step = trainer.global_step
        if self.cfg.sdc_every_n_steps:
            # retention floor input (core/callbacks.py _prune): with the
            # probe armed, the rollback target must sit at/below the
            # last probe-VERIFIED step — newer checkpoints are blessed
            # yet possibly silently poisoned. The restore point itself
            # counts as verified (it passed its digest check on load).
            trainer._guard_probe_ok_step = trainer.global_step
        marker = read_rollback_marker(self._dir(trainer))
        self._rollbacks_prior = int((marker or {}).get(
            "rollbacks_performed", 0))
        trainer.callback_metrics["guard_rollbacks"] = float(
            self._rollbacks_prior)
        trainer.callback_metrics.setdefault("guard_sdc_probes", 0.0)

    # -- per batch ---------------------------------------------------------

    def on_train_batch_end(self, trainer, module, metrics, batch_idx) -> None:
        step = trainer.global_step
        skipped = metrics.get("guard_skipped_steps") if isinstance(
            metrics, dict) else None
        if skipped is not None and _is_host_value(skipped):
            streak = metrics.get("guard_streak")
            self._note(trainer, step, float(np.asarray(skipped)),
                       float(np.asarray(streak))
                       if streak is not None and _is_host_value(streak)
                       else 0.0)
        if (self.cfg.sdc_every_n_steps
                and step % self.cfg.sdc_every_n_steps == 0):
            self._run_probe(trainer)

    # -- tier 2: escalation ------------------------------------------------

    def _note(self, trainer, step: int, skipped: float,
              streak: float = 0.0) -> None:
        prev_step = self._hist[-1][0] if self._hist else None
        if self._hist and skipped <= self._hist[-1][1]:
            # no new anomalies since the previous observation: every
            # step up to here is known clean
            self._last_good = step
        elif not self._hist and skipped <= 0:
            self._last_good = step
        self._hist.append((step, skipped))
        horizon = step - self.cfg.escalate_window
        while self._hist and self._hist[0][0] < horizon:
            self._base = max(self._base, self._hist.pop(0)[1])
        # The windowed count honors the documented contract only when
        # observations are at least window-dense — with a fetch cadence
        # LONGER than the window, a skipped-count delta spans the whole
        # gap and K-spread-over-many-steps would spuriously escalate.
        # The in-jit streak counter covers that regime exactly: it is
        # per-step accurate regardless of when it is read, so K
        # CONSECUTIVE anomalies always escalate.
        dense = (prev_step is not None
                 and prev_step >= horizon)
        in_window = skipped - self._base
        if (dense and in_window >= self.cfg.escalate_after) \
                or streak >= self.cfg.escalate_after:
            self._escalate(trainer, step,
                           int(max(in_window, streak)))

    def _escalate(self, trainer, step: int, count: int) -> None:
        err = TrainingAnomalyError(step, count, self.cfg.escalate_window,
                                   self._last_good)
        write_rollback_marker(self._dir(trainer), {
            "kind": "anomaly-streak",
            "detected_step": step,
            "last_good_step": self._last_good,
            "epoch": trainer.current_epoch,
            "epoch_batch": trainer._epoch_batches_done,
            "anomalies_in_window": count,
            "quarantine": [],
            "rollbacks_performed": self._rollbacks_prior,
            "at": time.time(),
        })
        log.error("trainguard: %s", err)
        raise err

    # -- tier 3: SDC probe -------------------------------------------------

    def _run_probe(self, trainer) -> None:
        state = trainer.state
        mesh = trainer.strategy.mesh
        if state is None or mesh is None:
            return
        if self._probe is None:
            # the strategy owns the sharding policy, so it builds the
            # probe (Strategy.sdc_probe) — replica grouping must match
            # what it actually placed
            self._probe, self._probe_devices, self._probe_groups = \
                trainer.strategy.sdc_probe(state.params)
            if not self._probe_groups:
                log.info(
                    "trainguard: no replicated parameter bytes on this "
                    "mesh (every device holds a distinct shard) — the "
                    "SDC probe records fingerprints but cannot "
                    "cross-check them")
        digests = np.asarray(jax.device_get(self._probe(state.params)))
        self._probes_run += 1
        trainer.callback_metrics["guard_sdc_probes"] = float(
            self._probes_run)
        suspects, comparable = diagnose_digests(digests,
                                                self._probe_groups)
        if not comparable or not suspects:
            self._probe_ok_step = trainer.global_step
            trainer._guard_probe_ok_step = trainer.global_step
            return
        ranks = sorted({self._probe_devices[i].process_index
                        for i in suspects})
        if len(suspects) >= len(self._probe_devices):
            ranks = []  # every replica disagrees with every other:
            #             attribution impossible, still roll back
        err = SDCDetectedError(ranks, trainer.global_step,
                               self._probe_ok_step,
                               digests=[int(d) for d in digests])
        write_rollback_marker(self._dir(trainer), {
            "kind": "sdc",
            "detected_step": trainer.global_step,
            "last_good_step": self._probe_ok_step,
            "epoch": trainer.current_epoch,
            "epoch_batch": trainer._epoch_batches_done,
            "quarantine": ranks,
            "digests": [int(d) for d in digests],
            "rollbacks_performed": self._rollbacks_prior,
            "at": time.time(),
        })
        log.error("trainguard: %s", err)
        raise err


def _is_host_value(v) -> bool:
    """True when the metric value is already host-resident (the trainer
    fetched it on the log cadence) — reading it then costs nothing. A
    still-on-device jax.Array is left alone: forcing it would add the
    per-step sync this design exists to avoid."""
    return isinstance(v, (bool, int, float, np.generic, np.ndarray))
