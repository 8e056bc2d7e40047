"""The contracts the chip bring-up added (ISSUE 21), on the CPU mesh:
Mosaic calls sit in a manual region on a multi-device mesh, the compile
cache resolves to one directory, no fallback hides the device, and
`chip_smoke.py` rehearses at `LlamaConfig.tiny` but refuses to run for
real without a TPU."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_lightning_tpu import DataParallel, FSDP, ShardedMesh
from ray_lightning_tpu.ops import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- flash attention in a manual region ------------------------------------

@pytest.mark.parametrize("make", [
    lambda: DataParallel(num_workers=8),
    lambda: FSDP(num_workers=4),
    lambda: ShardedMesh(fsdp=2, tensor=2, num_workers=4),
], ids=["dp8", "fsdp4", "fsdp2xtensor2"])
def test_flash_on_mesh_matches_unwrapped(devices8, make):
    """`flash_attention_on_mesh` wraps the kernel in a shard_map over
    the batch axes and `tensor`; forward and gradients equal the
    unwrapped kernel (interpret mode), and the region is really there."""
    from ray_lightning_tpu.ops.attention import (
        flash_attention, flash_attention_on_mesh,
    )
    from ray_lightning_tpu.parallel.mesh import dp_axis_names

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((8, 128, 4, 64), np.float32))
    k = jnp.asarray(rng.standard_normal((8, 128, 2, 64), np.float32))
    v = jnp.asarray(rng.standard_normal((8, 128, 2, 64), np.float32))
    strategy = make()
    mesh = strategy.setup()

    def on_mesh(q, k, v):
        return flash_attention_on_mesh(q, k, v, mesh)

    def sq(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    sh = NamedSharding(mesh, P(dp_axis_names(mesh), None, "tensor", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    with dispatch.force_pallas():
        want = flash_attention(q, k, v)
        g_want = jax.grad(sq(flash_attention), argnums=(0, 1, 2))(q, k, v)
        assert "shard_map" in str(jax.make_jaxpr(on_mesh)(qs, ks, vs))
        got = jax.jit(on_mesh)(qs, ks, vs)
        g_got = jax.jit(jax.grad(sq(on_mesh), argnums=(0, 1, 2)))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_on_mesh_passes_through_and_refuses(devices8):
    from ray_lightning_tpu.ops.attention import flash_attention_on_mesh

    mesh = ShardedMesh(tensor=4, num_workers=4).setup()
    q = jnp.zeros((2, 128, 4, 64))
    kv = jnp.zeros((2, 128, 2, 64))
    # the XLA reference path partitions on its own: no manual region
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention_on_mesh(
        q, k, v, mesh, use_pallas=False))(q, kv, kv)
    assert "shard_map" not in str(jaxpr)
    # 2 kv heads cannot split over tensor=4: a clear error, not a
    # silent fall-through to something XLA then fails to partition
    with dispatch.force_pallas(), pytest.raises(ValueError,
                                               match="n_kv_heads"):
        flash_attention_on_mesh(q, kv, kv, mesh)


def test_sharded_replica_reports_reference_lanes():
    """A replica over a tensor mesh cannot run the paged Mosaic kernels
    (no manual region yet): it takes the reference lanes and
    `attention_path`/`prefill_path` say so."""
    from ray_lightning_tpu.models.llama import Llama, LlamaConfig
    from ray_lightning_tpu.parallel.mesh import make_mesh
    from ray_lightning_tpu.serve.engine import DecodeEngine, EngineConfig

    cfg = LlamaConfig.tiny(n_heads=2, n_kv_heads=2, dim=128,
                           dtype=jnp.float32)      # head_dim 64: tiles
    model = Llama(cfg)
    params = jax.eval_shape(
        model.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), params)
    ecfg = EngineConfig(capacity=2, block_size=8, blocks_per_slot=4,
                        prefill_chunk=8)
    with dispatch.force_pallas():
        one = DecodeEngine(model, params, ecfg)
        mesh = make_mesh(tensor=2, devices=jax.devices()[:2])
        sharded = DecodeEngine(model, params, ecfg, mesh=mesh)
    assert one.attention_path == one.prefill_path == "paged-pallas"
    assert (sharded.attention_path == sharded.prefill_path
            == "reference-gather")


# ---- one compile cache directory -------------------------------------------

@pytest.fixture
def cache_config():
    """These tests move the process-global cache; put it back."""
    from jax._src import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_cache_dir_resolution(monkeypatch, tmp_path, cache_config):
    from ray_lightning_tpu.pipeline import compile_cache as cc

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # unset: a fixed path inside the checkout
    assert cc.resolve_cache_dir() == os.path.join(REPO, ".jax_cache")
    # an explicit argument is honoured while the variable is unset
    assert cc.resolve_cache_dir(str(tmp_path / "mine")) == \
        str(tmp_path / "mine")
    # the variable beats everything, explicit argument included
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert cc.resolve_cache_dir() == str(tmp_path / "env")
    assert cc.resolve_cache_dir(str(tmp_path / "mine")) == \
        str(tmp_path / "env")


def test_enable_never_moves_a_cache_placed_from_outside(
        monkeypatch, tmp_path, cache_config):
    from ray_lightning_tpu.pipeline import compile_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert cc.enable_persistent_cache(str(tmp_path / "mine")) == \
        str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "env")
    assert not (tmp_path / "mine").exists()
    # with nothing explicit, a later caller keeps the active dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_persistent_cache() == str(tmp_path / "env")


def test_one_cache_dir_update_in_the_package():
    """Acceptance grep: the package points jax at a cache directory in
    exactly one place, the resolver's module."""
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "ray_lightning_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if 'config.update("jax_compilation_cache_dir"' in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["ray_lightning_tpu/pipeline/compile_cache.py"]


# ---- nothing hides the device ----------------------------------------------

def test_unknown_device_kind_has_no_peak():
    from ray_lightning_tpu.utils.probe import device_peak_tflops

    assert device_peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="TPU v5 lite"):
        device_peak_tflops("cpu")


def test_dead_backend_is_not_read_as_not_a_tpu(monkeypatch):
    def dead():
        raise RuntimeError("UNAVAILABLE: TPU backend setup error")

    monkeypatch.setattr(jax, "default_backend", dead)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        dispatch.interpret_mode()


def test_process_replicas_refused_from_a_parent_holding_the_tpu(
        monkeypatch, tmp_path):
    from jax._src import xla_bridge

    from ray_lightning_tpu.models.llama import LlamaConfig
    from ray_lightning_tpu.serve.driver import (
        ReplicaGroupConfig, ServeDriver,
    )

    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    npz = str(tmp_path / "params.npz")
    drv = ServeDriver(LlamaConfig.tiny(), npz,
                      ReplicaGroupConfig(backend="process"))
    with pytest.raises(RuntimeError, match="holds the host's chips"):
        drv.start()
    with pytest.raises(RuntimeError, match="backend='inline'"):
        drv.run([])
    # replicas pinned to the CPU do not need the chip
    from ray_lightning_tpu.serve.driver import _require_chip_free_parent

    _require_chip_free_parent(ReplicaGroupConfig(
        backend="process", env={"JAX_PLATFORMS": "cpu"}))


# ---- chip_smoke.py ---------------------------------------------------------

def test_chip_smoke_legs_rehearse_at_tiny(devices8, capsys):
    """Every leg, the four-device ones included, through the real entry
    points at `LlamaConfig.tiny`: control flow only. Inline replicas
    land on distinct devices (serve-4 asserts it) and every leg line
    names the device it ran on."""
    import chip_smoke

    legs = chip_smoke.run(chip_smoke.SmokeSize.tiny(), n_devices=8)
    assert set(legs) == {"train-1", "kernels", "serve-1", "train-4/fsdp",
                         "train-4/fsdp2xtensor2", "serve-4"}
    assert legs["serve-4"]["pool_devices"] == 4
    assert legs["train-4/fsdp"]["param_devices"] == 4
    out = capsys.readouterr().out
    assert out.count("platform=cpu device_kind='cpu' devices=8") == 6


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""          # no leg line, no result line
    assert "no CPU mode" in proc.stderr
