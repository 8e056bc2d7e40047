"""What every runner kind shares: the device gate and the peak table, the
compile cache's place, host spans, the in-window compile counter, device
memory, and the run record handed back to `run.py`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple


class BenchError(RuntimeError):
    """The run cannot produce a result line; `run.py` exits non-zero."""


def load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    """Import a benchmark file by its path: cells, adapters, runner kinds and
    metric readers are found by the names `BENCHMARK.json` gives, and a name
    may hold dots."""
    if not os.path.isfile(path):
        raise BenchError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def checkout_of(path: str) -> str:
    """The checkout a file of `benchmarks/<directory>/` lies in."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(path))))


#: the directories whose files are found by a configuration's `"model"`
MODEL_PARTS = ("models", "reference", "tables")


def load_model_file(root: str, part: str, model: str):
    """`<root>/benchmarks/<part>/<model>.py` of the architecture a
    configuration names: its adapter (`models`), its plain reference
    (`reference`) or its tables of leaves and counts (`tables`). No file of
    the harness names a model; a new one enters as these three files."""
    if part not in MODEL_PARTS:
        raise BenchError(f"a model has no part {part!r}; it has {MODEL_PARTS}")
    return load_module(
        os.path.join(root, "benchmarks", part, model + ".py"),
        f"benchmarks_{part}_{model}")


def say(tag: str, **fields: Any) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ---- the device -------------------------------------------------------------


def require_chips(root: str, chips: int) -> Tuple[list, dict]:
    """The cell's devices and their peaks, or a `BenchError`: there is no CPU
    mode, and a device kind outside the table is an error, not a default."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise BenchError(f"jax's default backend is {backend!r}, not 'tpu': "
                         "the benchmark has no CPU mode")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), jax found "
                         f"{len(devices)}")
    table = load_json(os.path.join(root, "benchmarks", "peaks.json"))
    kind = devices[0].device_kind
    if kind not in table["device_kinds"]:
        raise BenchError(f"device_kind {kind!r} is not in benchmarks/"
                         "peaks.json; add it with its source")
    return devices[:chips], table["device_kinds"][kind]


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed place: where
    `JAX_COMPILATION_CACHE_DIR` says, else `<checkout>/.jax_cache` (the
    program's own resolver then takes the directory already in effect)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of `devices`."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use",
                                   stats.get("bytes_in_use", 0))))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts XLA backend compilations through jax's monitoring events; the
    window's count must stay 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


# ---- host spans -------------------------------------------------------------


class Spans:
    """The benchmark's own host spans: (name, start, end) on
    `time.perf_counter`, kept in memory. While a profiler trace is being
    recorded each span is also a `TraceAnnotation` named `bench.<name>`, so
    the reduction finds it on the device trace's clock."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                t0 = time.perf_counter()
                yield
                self.rows.append((name, t0, time.perf_counter()))
        else:
            t0 = time.perf_counter()
            yield
            self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> List[float]:
        return [e - s for n, s, e in self.rows
                if n == name and s >= lo and e <= hi]


# ---- the run record ---------------------------------------------------------


@dataclasses.dataclass
class RunRecord:
    """What a runner hands back: everything the metric readers read."""

    kind: str
    cell: dict
    config: dict
    traffic: dict
    hp: dict
    seconds: float
    chips: int
    peaks: dict
    #: the checkout the run's files were found in (`run.py`'s `root`)
    root: str = ""
    setup_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    #: end-to-end values by metric name (the runner computes what its kind
    #: defines; `run.py` prints those the cell lists)
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: the runner's own stamps and counts, by name (see each runner)
    stamps: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: Optional[Spans] = None
    #: `trace.TraceSummary` of a traced run, else None
    trace: Any = None
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    reference_s: float = 0.0

    def model_tables(self):
        """`benchmarks/tables/<model>.py` of the run's configuration: the
        counts a reader of operations or bytes needs."""
        return load_model_file(self.root, "tables", self.config["model"])


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))
