"""Test harness: a virtual 8-device CPU mesh, no TPU required.

This is the analog of the reference's throwaway local Ray clusters
(`ray.init(num_cpus=2)` fixtures, reference tests/test_ddp.py:16-21):
`--xla_force_host_platform_device_count=8` gives true multi-device SPMD
semantics (real shardings, real collectives compiled by XLA's CPU backend)
on any box.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent compilation cache: repeat runs (and the many subprocess
# workers, which inherit this env) skip recompiles of identical programs —
# the dominant cost of the suite. Keyed per jax version automatically.
# The directory is the one the package's resolver falls back to
# (pipeline/compile_cache.py DEFAULT_CACHE_DIR, pinned equal by
# tests/test_chip_bringup.py); spelled out here because it must be in
# the environment before jax is imported.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")
# Arm the lock-order sanitizer (analysis/lockwatch.py) for the whole
# suite: every san_lock the package creates becomes order-watched, so
# tier-1 doubles as a concurrency drill. Must be set BEFORE any package
# module is imported — san_lock decides armed-ness at creation time and
# module-level locks are created at import. Subprocess workers inherit
# it and sanitize themselves too.
os.environ.setdefault("RLT_LOCKWATCH", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_seed_env(monkeypatch, tmp_path):
    monkeypatch.delenv("RLT_GLOBAL_SEED", raising=False)
    monkeypatch.chdir(tmp_path)
    yield


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def tiny_llama_f32():
    """The suite's canonical tiny-Llama build — `LlamaConfig.tiny(
    use_flash=False, dtype=float32)`, init key 1 — compiled and
    initialized ONCE per session. Several module fixtures used to
    re-derive this identical build (generate, serve, serve_driver);
    the jitted `model.init` is one of the suite's compile-heaviest
    shared steps, and init params depend only on the RNG key and the
    param shapes (not the example batch), so one build serves them
    all. Treat the params as read-only."""
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    model = Llama(cfg)
    tokens = np.asarray(
        jax.random.randint(jax.random.key(0), (2, 8), 0, cfg.vocab_size),
        dtype=np.int32,
    )
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
    return cfg, model, params, tokens


def pytest_sessionfinish(session, exitstatus):
    """The lockwatch verdict for the whole run: any lock-order cycle the
    suite's real execution exercised fails the session (held-too-long is
    report-only — wall-clock on shared CI is not a correctness signal)."""
    from ray_lightning_tpu.analysis.lockwatch import (
        lockwatch_armed, lockwatch_cycles, lockwatch_findings,
    )

    if not lockwatch_armed():
        return
    cycles = lockwatch_cycles()
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_line(
            f"lockwatch: {len(cycles)} lock-order cycle(s) observed "
            f"across the suite", bold=bool(cycles))
    if cycles:
        for f in lockwatch_findings():
            if f.rule == "RLT702" and tr is not None:
                tr.write_line(f.format())
        session.exitstatus = 1
