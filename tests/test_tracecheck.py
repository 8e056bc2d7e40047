"""tracecheck unit tests: the cost model, the ppermute schedule checks,
and the jaxpr auditor's three finding classes (RLT301/302/303) on small
synthetic modules — all CPU-only, no devices beyond the trace."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.analysis.costmodel import (
    collective_cost, parse_topology, topology_for_kind,
)
from ray_lightning_tpu.analysis.tracecheck import (
    audit_step, check_permutation,
)
from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.models.mlp import MLPClassifier
from ray_lightning_tpu.ops.pipeline import pipeline_perm
from ray_lightning_tpu.ops.ring_attention import ring_perm
from ray_lightning_tpu.parallel.strategy import DataParallel, ShardedMesh


# ---- cost model ----------------------------------------------------------


def test_parse_topology():
    t = parse_topology("v5p-64")
    assert t.n_devices == 64
    assert t.device_kind == "TPU v5p"
    assert t.hbm_bytes == 95 * 1024**3
    assert t.ici_gbps > 0


def test_parse_topology_rejects_unknown_family():
    with pytest.raises(ValueError, match="v5p"):
        parse_topology("v99-8")
    with pytest.raises(ValueError, match="expected"):
        parse_topology("not a topology")


def test_topology_for_kind_unknown_has_no_assumed_peak():
    """An unknown kind keeps the HBM override and the cpu family's ICI
    figures, but its compute peak is an error, never a v5e-class guess;
    the "cpu" pseudo-family states its own pseudo-figure."""
    with pytest.raises(ValueError, match="no bf16 peak on record"):
        topology_for_kind("FPGA mystery", 4, hbm_bytes=2 * 1024**3)
    t = topology_for_kind("cpu", 4, hbm_bytes=2 * 1024**3)
    assert t.n_devices == 4
    assert t.hbm_bytes == 2 * 1024**3  # override honored
    assert t.peak_tflops == 1.0


def test_collective_cost_ring_algebra():
    topo = parse_topology("v5e-8")
    n, payload = 8, 1024**2
    psum = collective_cost("psum", payload, {"data": n}, topo)
    ag = collective_cost("all_gather", payload, {"data": n}, topo)
    rs = collective_cost("reduce_scatter", payload, {"data": n}, topo)
    pp = collective_cost("ppermute", payload, {"data": n}, topo)
    assert psum.wire_bytes == int(2 * payload * (n - 1) / n)
    assert ag.wire_bytes == rs.wire_bytes == int(payload * (n - 1) / n)
    assert pp.wire_bytes == payload
    # a single-member group moves nothing
    assert collective_cost("psum", payload, {"data": 1}, topo).wire_bytes == 0


# ---- ppermute schedule checks (RLT303) -----------------------------------


def test_canonical_schedules_are_clean():
    assert check_permutation(ring_perm(8), 8) == []
    assert check_permutation(pipeline_perm(4), 4) == []
    assert check_permutation([], 4) == []


def test_two_disjoint_cycles_flagged():
    f = check_permutation([(0, 1), (1, 0), (2, 3), (3, 2)], 4)
    assert [x.rule for x in f] == ["RLT303"]
    assert "2 disjoint cycles" in f[0].message


def test_duplicate_and_out_of_range_flagged():
    assert any("duplicate destination" in x.message
               for x in check_permutation([(0, 1), (2, 1)], 4))
    assert any("duplicate source" in x.message
               for x in check_permutation([(0, 1), (0, 2)], 4))
    assert any("outside the axis" in x.message
               for x in check_permutation([(0, 9)], 4))


# ---- auditor: collective schedule ---------------------------------------


def _mlp_batch(b=32):
    return {"x": np.zeros((b, 784), np.float32),
            "y": np.zeros((b,), np.int32)}


def test_dp_gradient_psums_detected():
    rep = audit_step(MLPClassifier(features=(128,), num_classes=10),
                     DataParallel(), _mlp_batch(),
                     topology="v5e-8", label="mlp")
    assert rep.findings == []
    psums = [e for e in rep.collectives if e.kind == "psum"]
    assert psums, "data-parallel gradient all-reduce not detected"
    assert all(e.axes == ("data",) for e in psums)
    # the [784, 128] f32 kernel grad is the dominant payload
    assert max(e.payload_bytes for e in psums) == 784 * 128 * 4
    assert rep.ici_bytes_per_step > 0
    assert rep.fits


def test_report_to_dict_roundtrips_json():
    import json

    rep = audit_step(MLPClassifier(features=(16,), num_classes=4),
                     DataParallel(), _mlp_batch(16),
                     topology="v5e-4", label="mlp")
    d = json.loads(json.dumps(rep.to_dict()))
    assert d["topology"]["name"] == "v5e-4"
    assert d["fits"] is True
    assert isinstance(d["collectives"], list)
    assert d["ici_bytes_per_step"] == rep.ici_bytes_per_step


def test_fsdp_weight_gathers_and_grad_reduce_scatters():
    rep = audit_step(MLPClassifier(features=(512,), num_classes=16),
                     ShardedMesh(fsdp=4), _mlp_batch(16),
                     topology="v5e-4", label="mlp-fsdp")
    assert not [f for f in rep.findings if f.rule == "RLT301"]
    kinds = {e.kind for e in rep.collectives}
    assert "all_gather" in kinds, "ZeRO weight gather not scheduled"


# ---- auditor: RESHARD-IMPLICIT (RLT301) ----------------------------------


class _TPModule(TpuModule):
    """Two-matmul Megatron-style module. ``drop_spec`` drops w2's
    tensor spec — the fsdp auto-placement then collides with the
    tensor-sharded activation: the ISSUE's mis-sharded variant."""

    def __init__(self, drop_spec=False):
        super().__init__()
        self.drop_spec = drop_spec

    def init_params(self, rng, batch):
        return {"w1": jnp.zeros((256, 512), jnp.float32),
                "w2": jnp.zeros((512, 256), jnp.float32)}

    def configure_model(self):
        return None

    def configure_optimizers(self):
        return optax.sgd(1e-2)

    def param_specs(self, params):
        specs = {"w1": P(None, "tensor")}
        if not self.drop_spec:
            specs["w2"] = P("tensor", None)
        return specs

    def training_step(self, params, batch, rng):
        h = jax.nn.relu(batch["x"] @ params["w1"])
        return ((h @ params["w2"]) ** 2).mean()


def _tp_batch():
    return {"x": np.zeros((32, 256), np.float32)}


def test_correct_tensor_plan_is_clean():
    rep = audit_step(_TPModule(False), ShardedMesh(fsdp=2, tensor=2),
                     _tp_batch(), topology="v5e-4", label="tp-ok")
    assert rep.findings == []
    # row-parallel second matmul: psum over tensor is the SCHEDULE,
    # not a finding
    assert any(e.kind == "psum" and "tensor" in e.axes
               for e in rep.collectives)


def test_dropped_output_spec_flags_reshard_implicit():
    rep = audit_step(_TPModule(True), ShardedMesh(fsdp=2, tensor=2),
                     _tp_batch(), topology="v5e-4", label="tp-bad")
    assert any(f.rule == "RLT301" for f in rep.findings), \
        "mis-sharded matmul not flagged RESHARD-IMPLICIT"


# ---- auditor: HBM-OVERCOMMIT (RLT302) ------------------------------------


def test_hbm_overcommit_flagged_on_tiny_budget():
    from ray_lightning_tpu.analysis.costmodel import parse_topology

    topo = parse_topology("v5e-4", hbm_bytes=1024**2)  # 1 MiB chips
    rep = audit_step(MLPClassifier(features=(512, 512), num_classes=10),
                     DataParallel(), _mlp_batch(),
                     topology=topo, label="mlp-tiny-hbm")
    assert any(f.rule == "RLT302" for f in rep.findings)
    assert not rep.fits


# ---- auditor: RING-DEADLOCK (RLT303) in a traced step --------------------


class _RingModule(TpuModule):
    def __init__(self, perm_kind="ring"):
        super().__init__()
        self.perm_kind = perm_kind

    def init_params(self, rng, batch):
        return {"w": jnp.zeros((64, 64), jnp.float32)}

    def configure_model(self):
        return None

    def configure_optimizers(self):
        return optax.sgd(1e-2)

    def training_step(self, params, batch, rng):
        x = batch["x"] @ params["w"]
        n = self.mesh.shape["seq"]
        perm = {"ring": ring_perm(n),
                "two_cycles": [(0, 1), (1, 0), (2, 3), (3, 2)]}[
                    self.perm_kind]

        def local(x):
            y = jax.lax.ppermute(x, "seq", perm)
            return jax.lax.psum(x * y, "seq")

        f = shard_map(local, mesh=self.mesh, in_specs=P(None, "seq"),
                      out_specs=P(None, "seq"), check_vma=False)
        return (f(x) ** 2).mean()


def _ring_batch():
    return {"x": np.zeros((8, 64), np.float32)}


def test_explicit_shard_map_collectives_scheduled():
    rep = audit_step(_RingModule("ring"), ShardedMesh(seq=4),
                     _ring_batch(), topology="v5e-4", label="ring")
    assert not [f for f in rep.findings if f.rule == "RLT303"]
    explicit = [e for e in rep.collectives if not e.implicit]
    assert {"ppermute", "psum"} <= {e.kind for e in explicit}


def test_broken_ring_flags_deadlock():
    rep = audit_step(_RingModule("two_cycles"), ShardedMesh(seq=4),
                     _ring_batch(), topology="v5e-4", label="ring-bad")
    assert any(f.rule == "RLT303" for f in rep.findings)


# ---- API wrappers --------------------------------------------------------


def test_strategy_and_module_audit_step_wrappers():
    rep = ShardedMesh(fsdp=2).audit_step(
        MLPClassifier(features=(64,), num_classes=4), _mlp_batch(16),
        topology="v5e-2")
    assert rep.label  # auto-label from types
    rep2 = MLPClassifier(features=(64,), num_classes=4).audit_step(
        DataParallel(), _mlp_batch(16), topology="v5e-2")
    assert rep2.mesh_axes == {"data": 2}
    assert "tracecheck" in rep2.summary()
