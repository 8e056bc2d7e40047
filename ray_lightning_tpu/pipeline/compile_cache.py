"""AOT warm start + persistent compilation cache for the jitted steps.

Two costs hide in "the first step is slow":

  * the in-process trace+compile of the train/eval step — paid lazily on
    step 1 under plain ``jit``, which makes compile time invisible
    (it reads as a slow first batch) and unreportable;
  * the cross-process recompile on every restart — the resilience
    supervisor relaunches workers, and without a persistent cache each
    restart pays the full XLA compile again, multiplied by the restart
    budget.

`WarmStep` fixes the first: it wraps a jitted function and eagerly
``lower().compile()``s it for the known input shapes (static shapes are
the framework contract — loaders drop ragged tails), recording trace/
lower/compile wall time as `CompileStats` so compile time is a
first-class metric (``trainer.callback_metrics["compile_time_s"]``).
Calls with matching shapes dispatch the AOT executable directly; a
shape drift (a user loader yielding a ragged batch) falls back to the
jitted path permanently rather than erroring — AOT is an optimization,
never a new constraint.

`enable_persistent_cache` fixes the second: it turns on jax's
persistent compilation cache (``jax_compilation_cache_dir``) with the
entry thresholds dropped to zero so even fast-compiling steps are
cached. Restart N then recompiles nothing: the lowered program hashes
to the same key and the executable is deserialized from disk.

The directory comes from ONE resolver, `resolve_cache_dir`, so it can
be placed from outside and never moves underneath a run:
``JAX_COMPILATION_CACHE_DIR`` wins when set (nothing in the package
then points jax anywhere else); otherwise an explicit
``compile_cache_dir=`` argument; otherwise whatever this process
already uses; otherwise the fixed ``<checkout>/.jax_cache``. A cache
that moves (a temp dir, a per-plan hash) is a cache that never hits.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Optional, Tuple

import jax

from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)


@dataclasses.dataclass
class CompileStats:
    """Wall-clock breakdown of one AOT warm start."""

    lower_s: float = 0.0     # trace + lower to StableHLO
    compile_s: float = 0.0   # XLA compile (near-zero on a persistent-cache hit)
    total_s: float = 0.0
    aot: bool = False        # an AOT executable is installed
    cache_dir: Optional[str] = None  # persistent cache in effect, if any

    def to_metrics(self, prefix: str = "") -> dict:
        return {
            f"{prefix}compile_time_s": self.total_s,
            f"{prefix}compile_lower_s": self.lower_s,
            f"{prefix}compile_xla_s": self.compile_s,
        }


#: where the cache lives when nothing places it: a fixed path inside the
#: checkout (git-ignored), so every process of every run finds it again
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(explicit: Optional[str] = None) -> str:
    """The persistent cache directory, by precedence: the
    ``JAX_COMPILATION_CACHE_DIR`` variable, the caller's ``explicit``
    argument, the directory this process already caches in, and last
    `DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if explicit:
        return os.path.abspath(explicit)
    return jax.config.jax_compilation_cache_dir or DEFAULT_CACHE_DIR


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache in the directory
    `resolve_cache_dir` picks (created if needed) and drop the
    size/time thresholds so every step program is cached. Idempotent;
    returns the directory. Process-global."""
    from jax._src import compilation_cache as _cc

    cache_dir = resolve_cache_dir(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax binds the on-disk cache object to the directory on first
        # use; without a reset a dir change after any compile in this
        # process is silently ignored
        _cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    # cache everything: the step is THE program that matters here, and
    # on a restart even a 0.5 s compile is pure waste
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def active_cache_dir() -> Optional[str]:
    """The persistent cache directory currently in effect, or None."""
    return jax.config.jax_compilation_cache_dir or None


def _abstract(tree: Any) -> Any:
    """ShapeDtypeStructs (sharding-carrying when available) for lower()."""
    def one(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None))

    return jax.tree.map(one, tree)


def _shape_sig(tree: Any) -> Tuple:
    """Hashable (shape, dtype) signature used to gate the AOT fast path."""
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree.leaves(tree))


class WarmStep:
    """A jitted step with an eagerly-compiled AOT fast path.

    ``warm(*example_args)`` lowers and compiles for those exact shapes
    (donation and shardings come from the wrapped ``jax.jit``); calls
    whose leaf shapes/dtypes match then run the AOT executable, others
    fall back to the jitted function (which re-traces as jit always did).
    The fallback is permanent after the first mismatch — a loader that
    yields ragged batches gets classic jit semantics, not errors.
    """

    def __init__(self, jitted: Callable, label: str = "step",
                 auto: bool = False,
                 check_args: Optional[Tuple[int, ...]] = None,
                 recorder: Any = None):
        from ray_lightning_tpu.telemetry.spans import NULL_RECORDER

        self._jitted = jitted
        self._label = label
        #: telemetry recorder (telemetry/spans.py): warm() runs under a
        #: "compile" span, so heartbeats report the phase live (a
        #: 20-minute big-model compile names itself instead of reading
        #: as a frozen step counter) and the goodput compile bucket is
        #: measured, not inferred
        self._recorder = recorder or NULL_RECORDER
        self._compiled = None
        self._sig: Optional[Tuple] = None
        self._attempted = False
        #: which positional args' shapes are re-checked per call. The
        #: trainer passes (1,) — only the BATCH can drift (the state is
        #: trainer-managed and the rng key is fixed), so the per-step
        #: check stays O(batch leaves) instead of walking a possibly
        #: hundreds-of-leaves TrainState on the hot path this package
        #: exists to de-host. None = check everything (generic use).
        self._check_args = check_args
        #: auto=True AOT-compiles on the first call's shapes (the eval
        #: step, whose batch shape is unknown until validation runs);
        #: auto=False waits for an explicit warm() (the train step, warmed
        #: eagerly at fit start) and is a plain jit passthrough otherwise.
        self._auto = auto
        self.stats = CompileStats()

    def warm(self, *example_args: Any) -> CompileStats:
        """AOT-compile for ``example_args``' shapes. Failures degrade to
        the jitted path with a logged warning — warm start must never be
        able to fail a fit that plain jit would have survived."""
        from ray_lightning_tpu.telemetry.spans import PH_COMPILE

        with self._recorder.span(PH_COMPILE,
                                 meta={"label": self._label}):
            return self._warm_inner(*example_args)

    def _warm_inner(self, *example_args: Any) -> CompileStats:
        self._attempted = True
        t0 = time.perf_counter()
        try:
            abstract = tuple(_abstract(a) for a in example_args)
            lowered = self._jitted.lower(*abstract)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception:  # noqa: BLE001 — optimization, not a contract
            log.exception("AOT warm start failed for %s; falling back to "
                          "lazy jit compilation", self._label)
            self.stats = CompileStats(total_s=time.perf_counter() - t0)
            return self.stats
        self._compiled = compiled
        idx = (range(len(abstract)) if self._check_args is None
               else self._check_args)
        self._sig = (len(abstract),
                     tuple(_shape_sig(abstract[i]) for i in idx))
        self.stats = CompileStats(
            lower_s=t1 - t0, compile_s=t2 - t1, total_s=t2 - t0,
            aot=True, cache_dir=active_cache_dir())
        log.info("%s warm start: lower %.3fs + compile %.3fs (persistent "
                 "cache: %s)", self._label, self.stats.lower_s,
                 self.stats.compile_s, self.stats.cache_dir or "off")
        return self.stats

    def _sig_of(self, args: Tuple) -> Tuple:
        idx = (range(len(args)) if self._check_args is None
               else self._check_args)
        return (len(args), tuple(_shape_sig(args[i]) for i in idx))

    def __call__(self, *args: Any) -> Any:
        if self._auto and not self._attempted and self._compiled is None:
            self.warm(*args)
        if self._compiled is not None:
            if self._sig_of(args) == self._sig:
                return self._compiled(*args)
            # shape drift: AOT assumptions broken — classic jit from here
            log.warning("%s input shapes drifted from the warm-start "
                        "shapes; disabling the AOT fast path", self._label)
            self._compiled = None
        return self._jitted(*args)

    @property
    def aot_active(self) -> bool:
        return self._compiled is not None

    def compiled_text(self) -> Optional[str]:
        """The AOT executable's optimized HLO, or None before `warm`:
        what a smoke greps to see which kernels the step really runs
        (a Mosaic kernel is a ``tpu_custom_call``)."""
        return None if self._compiled is None else self._compiled.as_text()
