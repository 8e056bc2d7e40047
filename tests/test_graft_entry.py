"""Driver-gate tests: the hooks in __graft_entry__.py must work exactly as
the external driver invokes them (fresh process, no test-harness env).

These guard the two externally-checked signals — the single-chip compile
check and the multi-chip dryrun (reference capability: multi-worker
correctness, reference ray_lightning/ray_ddp.py:257-264).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    # The driver runs the hooks without our conftest's virtual-device flags;
    # dryrun_multichip must self-provision. Strip anything the harness set.
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    # Keep it CPU in CI regardless of what hardware the box exposes.
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_dryrun_multichip_self_provisions():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); print('OK')"],
        cwd=REPO, env=_clean_env(), capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


@pytest.mark.slow
def test_entry_compiles():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "import __graft_entry__ as g;"
         "fn, args = g.entry();"
         "out = jax.jit(fn)(*args);"
         "jax.block_until_ready(out); print('OK', out.shape)"],
        cwd=REPO, env=_clean_env(), capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
