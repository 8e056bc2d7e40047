"""The benchmark's own tests run on the CPU, four virtual devices:
    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
They are outside tier-1's `tests/`."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def lifted_gate(monkeypatch):
    """The device gate lifted by the test (never by an option of run.py):
    `require_chips` hands back CPU devices and a made-up peak table."""
    import jax

    from benchmarks.harness import common

    def fake(root, chips):
        return jax.devices()[:chips], {"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11,
                                       "hbm_bytes": 1e9}

    monkeypatch.setattr(common, "require_chips", fake)
