"""AOT warm start + persistent compilation cache
(pipeline/compile_cache.py): compile-time metrics, cache hits across
trainers and shape-drift fallback."""
import os

import jax
import numpy as np
import pytest

from ray_lightning_tpu import DataLoader, SingleDevice, Trainer
from ray_lightning_tpu.pipeline.compile_cache import (
    WarmStep,
    enable_persistent_cache,
)

from tests.utils import BoringModel, random_dataset


def _cache_files(d):
    return sorted(f for f in os.listdir(d) if f.endswith("-cache"))


def _fit(tmp_path, cache_dir, *, warm_start=True, data=None, seed=3):
    data = data if data is not None else random_dataset(n=128)
    trainer = Trainer(
        strategy=SingleDevice(), max_epochs=1,
        default_root_dir=str(tmp_path), enable_checkpointing=False,
        enable_progress_bar=False, seed=seed, warm_start=warm_start,
        compile_cache_dir=str(cache_dir) if cache_dir else None,
    )
    module = BoringModel()
    trainer.fit(module, DataLoader(data, batch_size=32),
                DataLoader(data, batch_size=32))
    return trainer, module


class TestWarmStep:
    def test_aot_path_used_and_stats_recorded(self, tmp_path):
        trainer, _ = _fit(tmp_path, None)
        assert isinstance(trainer._train_step, WarmStep)
        assert trainer._train_step.aot_active
        assert trainer.callback_metrics["compile_time_s"] > 0
        # eval step auto-warms on its first batch
        assert trainer.callback_metrics["val_compile_time_s"] > 0

    def test_bitwise_parity_warm_vs_lazy(self, tmp_path):
        data = random_dataset(n=128)
        _, m_warm = _fit(tmp_path / "a", None, warm_start=True, data=data)
        _, m_lazy = _fit(tmp_path / "b", None, warm_start=False, data=data)
        for a, b in zip(jax.tree.leaves(jax.device_get(m_warm.params)),
                        jax.tree.leaves(jax.device_get(m_lazy.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_warm_start_off_is_plain_jit(self, tmp_path):
        trainer, _ = _fit(tmp_path, None, warm_start=False)
        assert not trainer._train_step.aot_active
        assert "compile_time_s" not in trainer.callback_metrics

    def test_shape_drift_falls_back_to_jit(self, tmp_path):
        """A loader yielding ragged batches must get classic jit
        semantics (retrace per shape), never an AOT shape error."""
        rng = np.random.default_rng(0)

        def batches():
            for bs in (32, 32, 16, 32):  # drift at batch 3
                yield {"x": rng.standard_normal((bs, 32),
                                                dtype=np.float32),
                       "y": rng.integers(0, 2, bs).astype(np.int32)}

        trainer = Trainer(
            strategy=SingleDevice(), max_epochs=1,
            default_root_dir=str(tmp_path), enable_checkpointing=False,
            enable_progress_bar=False, warm_start=True,
        )
        trainer.fit(BoringModel(), batches())
        assert trainer.global_step == 4
        assert not trainer._train_step.aot_active  # drift disabled AOT

    def test_second_trainer_hits_persistent_cache(self, tmp_path,
                                                  monkeypatch):
        """Two trainers compiling the identical program against one
        persistent cache dir: the second must ADD no cache entries (its
        lowered program hashes to the first's key — a disk hit, which is
        what makes supervisor restart N recompile nothing). An explicit
        ``compile_cache_dir=`` is honoured only while
        JAX_COMPILATION_CACHE_DIR is unset."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cache = tmp_path / "cache"
        data = random_dataset(n=128)
        t1, _ = _fit(tmp_path / "a", cache, data=data)
        files_after_first = _cache_files(cache)
        assert files_after_first, "no persistent cache entries written"
        t2, _ = _fit(tmp_path / "b", cache, data=data)
        assert _cache_files(cache) == files_after_first
        # both report the metric; the second's XLA share is a disk hit
        assert t1.callback_metrics["compile_time_s"] > 0
        assert t2.callback_metrics["compile_time_s"] > 0
        # hand the process-global cache back to the suite's directory
        monkeypatch.undo()
        enable_persistent_cache()


class TestWarmStepUnit:
    def test_warm_failure_degrades_to_jit(self):
        """warm() on something that cannot lower must not break calls."""
        step = WarmStep(jax.jit(lambda x: x + 1), label="t")
        step.warm(object())  # not abstractable -> logged fallback
        assert not step.aot_active
        assert int(step(jax.numpy.ones(()))) == 2

    def test_matching_shapes_dispatch_compiled(self):
        calls = {"n": 0}
        jitted = jax.jit(lambda x: x * 2)
        step = WarmStep(jitted, label="t")
        x = jax.numpy.arange(8, dtype=jax.numpy.float32)
        step.warm(x)
        assert step.aot_active
        assert np.array_equal(np.asarray(step(x)), np.asarray(x) * 2)
        # drifted shape: falls back, stays functional
        y = jax.numpy.arange(4, dtype=jax.numpy.float32)
        assert np.array_equal(np.asarray(step(y)), np.asarray(y) * 2)
        assert not step.aot_active
        del calls


@pytest.mark.slow  # spawns a subprocess to prove the cross-process hit
def test_cross_process_cache_reuse(tmp_path):
    """The supervisor's restart story: a FRESH process pointed at the
    same cache dir must not add entries either."""
    import subprocess
    import sys

    cache = tmp_path / "cache"
    script = f"""
import os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tests.utils import BoringModel, random_dataset
from ray_lightning_tpu import DataLoader, SingleDevice, Trainer
data = random_dataset(n=128)
t = Trainer(strategy=SingleDevice(), max_epochs=1,
            default_root_dir={str(tmp_path / "run")!r},
            enable_checkpointing=False, enable_progress_bar=False,
            seed=3, compile_cache_dir={str(cache)!r})
t.fit(BoringModel(), DataLoader(data, batch_size=32))
print("COMPILE_S", t.callback_metrics["compile_time_s"])
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # honour the explicit dir
    out1 = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert out1.returncode == 0, out1.stderr[-2000:]
    files_first = _cache_files(cache)
    assert files_first
    out2 = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert _cache_files(cache) == files_first
