"""numcheck (analysis/numcheck.py): the RLT8xx precision layer.

Fire/sanction matrix per rule over real jaxprs, the RLT804 collective
check over fabricated event streams, precision-ledger byte identities
against the audit's own memory accounting, the shared dtype-width table
(no drift vs RLT105), repo-audits-clean pins for every bundled trace
target, and CLI smoke for `lint --numerics` / `trace --no-numerics`.

The matrix convention: each `fire_*` test must produce EXACTLY the
named finding(s) — an injected bug yields one finding, not a spray —
and each `sanction_*` test must be silent. That exactness is the
contract that keeps the format.sh gate (zero RLT801/805 across the
examples) meaningful.
"""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_lightning_tpu.analysis import costmodel
from ray_lightning_tpu.analysis.numcheck import (
    LOW_PRECISION_EXTENT,
    check_gradient_collectives,
    check_numerics_sources,
    numcheck_jaxpr,
    summarize,
)


def _audit(fn, *args, loss_index=None):
    closed = jax.make_jaxpr(fn)(*args)
    return numcheck_jaxpr(closed, loss_index=loss_index)


def _rules(fn, *args):
    findings, _ = _audit(fn, *args)
    return [f.rule for f in findings]


BF = jnp.ones((512, 512), jnp.bfloat16)
F32 = jnp.ones((512, 512), jnp.float32)
Q8 = jnp.ones((512, 512), jnp.int8)
SMALL = jnp.ones((64, 64), jnp.bfloat16)


# --------------------------------------------------------------------------
# RLT801 — low-precision accumulation
# --------------------------------------------------------------------------


class TestRLT801:
    def test_fire_bf16_dot(self):
        assert _rules(lambda a, b: a @ b, BF, BF) == ["RLT801"]

    def test_fire_raw_bf16_reduce_sum(self):
        # raw reduce_sum at bf16 (jnp.sum would auto-widen — see below)
        fn = lambda a: lax.reduce_sum_p.bind(a, axes=(0,))  # noqa: E731
        assert _rules(fn, BF) == ["RLT801"]

    def test_sanction_preferred_f32_round_once(self):
        # the rule's own prescription: f32 accumulator, one rounding
        def fn(a, b):
            out = lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return out.astype(jnp.bfloat16)
        assert _rules(fn, BF, BF) == []

    def test_sanction_jnp_sum_auto_widens(self):
        # jnp.sum(bf16) inserts convert->f32->reduce->convert itself
        assert _rules(lambda a: a.sum(axis=0), BF) == []

    def test_sanction_small_extent(self):
        # K <= LOW_PRECISION_EXTENT costs < 1 decimal digit — sanctioned
        assert SMALL.shape[0] <= LOW_PRECISION_EXTENT
        assert _rules(lambda a, b: a @ b, SMALL, SMALL) == []
        fn = lambda a: lax.reduce_sum_p.bind(a, axes=(0,))  # noqa: E731
        assert _rules(fn, SMALL) == []

    def test_injected_bug_exactly_one_finding(self):
        # acceptance: an injected bf16-accumulating dot produces ONE
        # finding, not a cascade from its downstream uses
        def fn(a, b):
            y = a @ b
            return (y + 1.0).sum()
        findings, _ = _audit(fn, BF, BF)
        assert [f.rule for f in findings] == ["RLT801"]


# --------------------------------------------------------------------------
# RLT802 — transcendental on low-precision operand
# --------------------------------------------------------------------------


class TestRLT802:
    @pytest.mark.parametrize("fn", [jnp.exp, jnp.log, lax.rsqrt],
                             ids=["exp", "log", "rsqrt"])
    def test_fire_bf16_transcendental(self, fn):
        assert _rules(lambda a: fn(a), BF) == ["RLT802"]

    def test_sanction_f32_operand(self):
        assert _rules(lambda a: jnp.exp(a), F32) == []

    def test_sanction_softmax_submax(self):
        # exp(x - max(x)) is the numerically-sanctioned shape
        assert _rules(lambda a: jax.nn.softmax(a, axis=-1), BF) == []


# --------------------------------------------------------------------------
# RLT803 — cast churn (f32 -> bf16 -> f32 with no compute between)
# --------------------------------------------------------------------------


class TestRLT803:
    def test_fire_inline_round_trip(self):
        fn = lambda a: (a + 1.0).astype(jnp.bfloat16).astype(jnp.float32) * 2.0  # noqa: E731,E501
        assert _rules(fn, F32) == ["RLT803"]

    def test_sanction_compute_between_casts(self):
        # real bf16 arithmetic between the casts: that is mixed
        # precision working as designed, not churn
        def fn(a):
            h = (a + 1.0).astype(jnp.bfloat16) * 2.0
            return h.astype(jnp.float32) + 1.0
        assert _rules(fn, F32) == []

    def test_fire_scan_carried_cast(self):
        # the downcast rides a scan carry; the re-widen after the loop
        # still closes the round trip (fixpoint carry merge)
        def fn(a):
            h = (a + 1.0).astype(jnp.bfloat16)

            def body(c, _):
                return c, ()

            c, _ = lax.scan(body, h, None, length=3)
            return c.astype(jnp.float32) * 2.0
        assert _rules(fn, F32) == ["RLT803"]

    def test_sanction_rounding_fresh_accumulator(self):
        # downcasting a dot's WIDE accumulator is RLT801's own
        # prescription — re-widening later must not read as churn
        def fn(a, b):
            y = lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.bfloat16)
            return y.astype(jnp.float32).sum()
        assert _rules(fn, BF, BF) == []

    def test_sanction_cross_file_seam(self):
        # downcast here, re-widen inside ops/norms.py: a module-
        # boundary contract (the callee computes at f32 by design),
        # not a churn bug in either file
        from ray_lightning_tpu.ops.norms import rms_norm

        w = jnp.ones((512,), jnp.float32)

        def fn(a, w):
            h = (a + 1.0).astype(jnp.bfloat16)
            return rms_norm(h, w)
        assert _rules(fn, F32, w) == []


# --------------------------------------------------------------------------
# RLT805 — quantized payload consumed without a dequant scale
# --------------------------------------------------------------------------


class TestRLT805:
    def test_fire_scale_free_consume_exactly_one(self):
        # acceptance: int8 pushed straight into float math — one RLT805
        # (plus the bf16 dot's own RLT801, a distinct defect)
        findings, _ = _audit(lambda a, b: a.astype(jnp.bfloat16) @ b,
                             Q8, BF)
        assert sorted(f.rule for f in findings) == ["RLT801", "RLT805"]
        assert sum(f.rule == "RLT805" for f in findings) == 1

    def test_sanction_f32_scale(self):
        def fn(a, b):
            deq = a.astype(jnp.float32) * jnp.float32(0.02)
            return (deq.astype(jnp.bfloat16) @ b).astype(jnp.float32)
        findings, _ = _audit(fn, Q8, BF)
        assert all(f.rule != "RLT805" for f in findings)

    def test_narrow_scale_fires_then_clears(self):
        # a bf16 scale IS a scale (quant flag clears, the dot does not
        # re-fire) but re-quantizes the payload — its own RLT805
        def fn(a, b):
            return (a.astype(jnp.bfloat16) * jnp.bfloat16(0.02)) @ b
        findings, _ = _audit(fn, Q8, BF)
        narrow = [f for f in findings if f.rule == "RLT805"]
        assert len(narrow) == 1
        assert "narrower than f32" in narrow[0].message

    def test_sanction_int8_dot_int32(self):
        # integer-domain contraction (the int8-KV plan's inner product)
        # never enters float math — nothing to scale yet
        def fn(a, b):
            return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        assert _rules(fn, Q8, Q8) == []


# --------------------------------------------------------------------------
# pallas: kernels audit like plain arrays; f32 scratch is the sanction
# --------------------------------------------------------------------------


class TestPallasSanction:
    def test_rmsnorm_pallas_bf16_clean(self):
        # the kernel reads bf16 tiles but squares/sums in an f32
        # scratch — numcheck recurses into pallas_call and must see
        # that, not flag the bf16 refs
        from ray_lightning_tpu.ops.pallas.rmsnorm import rms_norm_pallas

        x = jnp.ones((8, 512), jnp.bfloat16)
        w = jnp.ones((512,), jnp.float32)
        assert _rules(lambda x, w: rms_norm_pallas(x, w), x, w) == []


# --------------------------------------------------------------------------
# model pins — the satellite-1 fixes stay fixed
# --------------------------------------------------------------------------


class TestModelPins:
    # `findings == []` includes RLT310: nothing the walk could not enter
    def test_fused_ce_accumulates_f32(self):
        # the chunked loop must carry f32 partials and dot with
        # preferred_element_type=f32 even on bf16 hidden/weights
        from ray_lightning_tpu.ops.fused_ce import fused_cross_entropy

        h = jnp.ones((4, 128, 64), jnp.bfloat16)
        W = jnp.ones((64, 512), jnp.float32)
        t = jnp.zeros((4, 128), jnp.int32)

        def loss(h, W):
            return fused_cross_entropy(h, W, t, chunk_tokens=128).mean()

        closed = jax.make_jaxpr(
            lambda h, W: jax.value_and_grad(loss, argnums=(0, 1))(h, W)
        )(h, W)
        findings, info = numcheck_jaxpr(closed, loss_index=0)
        assert findings == []
        assert info["loss_widest_dtype"] == "float32"

    def test_moe_mlp_bf16_grad_clean(self):
        # router logits, dispatch/combine einsums and expert matmuls
        # all accumulate f32 (preferred_element_type) at dtype=bf16
        from ray_lightning_tpu.models.moe import MoEMLP

        m = MoEMLP(n_experts=4, hidden_dim=128, top_k=2,
                   dtype=jnp.bfloat16)
        x = jnp.ones((2, 64, 32), jnp.bfloat16)
        params = m.init(jax.random.PRNGKey(0), x)["params"]

        def loss(p, x):
            y, aux = m.apply({"params": p}, x)
            return (y.astype(jnp.float32) ** 2).mean() + aux.mean()

        closed = jax.make_jaxpr(
            lambda p, x: jax.value_and_grad(loss)(p, x))(params, x)
        findings, _ = numcheck_jaxpr(closed)
        assert findings == []


# --------------------------------------------------------------------------
# RLT804 — gradient collectives vs optimizer-state width
# --------------------------------------------------------------------------


def _ev(**kw):
    return types.SimpleNamespace(**kw)


_PARAMS = {"layers/w/kernel": types.SimpleNamespace(
    shape=(8, 8), dtype=np.dtype("float32"))}
_OPT = {"mu/layers/w/kernel": types.SimpleNamespace(
    shape=(8, 8), dtype=np.dtype("float32"))}


class TestRLT804:
    def test_fire_bf16_grad_reduce_scatter(self):
        events = [_ev(kind="reduce_scatter", dtype="bfloat16",
                      param_path="params/layers/w/kernel", axes=("data",),
                      source="reduce_scatter @ x.py:1")]
        findings = check_gradient_collectives(events, _PARAMS, _OPT)
        assert [f.rule for f in findings] == ["RLT804"]
        assert findings[0].symbol == "params/layers/w/kernel"
        assert "data" in findings[0].message

    def test_dedupe_by_site_and_path(self):
        ev = _ev(kind="reduce_scatter", dtype="bfloat16",
                 param_path="params/layers/w/kernel", axes=("data",),
                 source="reduce_scatter @ x.py:1")
        assert len(check_gradient_collectives([ev, ev], _PARAMS, _OPT)) == 1

    def test_silent_cases(self):
        events = [
            # f32 payload: already as wide as the opt state
            _ev(kind="psum", dtype="float32",
                param_path="params/layers/w/kernel", axes=("data",),
                source="psum @ x.py:2"),
            # all_gather is a weight fetch, not a gradient reduction
            _ev(kind="all_gather", dtype="bfloat16",
                param_path="params/layers/w/kernel", axes=("data",),
                source="ag @ x.py:3"),
            # non-param payload (a metric psum)
            _ev(kind="psum", dtype="bfloat16", param_path="loss",
                axes=("data",), source="psum @ x.py:4"),
        ]
        assert check_gradient_collectives(events, _PARAMS, _OPT) == []

    def test_silent_when_opt_state_is_not_wider(self):
        opt = {"mu/layers/w/kernel": types.SimpleNamespace(
            shape=(8, 8), dtype=np.dtype(jnp.bfloat16))}
        events = [_ev(kind="reduce_scatter", dtype="bfloat16",
                      param_path="params/layers/w/kernel", axes=("data",),
                      source="reduce_scatter @ x.py:1")]
        assert check_gradient_collectives(events, _PARAMS, opt) == []


# --------------------------------------------------------------------------
# shared width table — RLT105 and RLT804 must not drift
# --------------------------------------------------------------------------


class TestWidthTable:
    def test_numcheck_width_is_costmodel_width(self):
        from ray_lightning_tpu.analysis import numcheck

        for dt in ("float32", "bfloat16", "float16", "int8", "float64"):
            assert numcheck._width(dt) == costmodel.dtype_width(dt)
        assert numcheck._width("bfloat16") == 2.0
        assert numcheck._width("int8") == 1.0

    def test_rlt105_and_rlt804_single_source(self):
        # both passes import THE costmodel symbol — a width tweak in
        # one place moves both rules together (no copied tables)
        import inspect

        import ray_lightning_tpu.analysis.numcheck as numcheck
        import ray_lightning_tpu.analysis.plan_checker as plan_checker

        assert numcheck.dtype_width is costmodel.dtype_width
        imp = "from ray_lightning_tpu.analysis.costmodel import dtype_width"
        for mod in (numcheck, plan_checker):
            src = inspect.getsource(mod)
            assert imp in src
            # no privately copied width table
            assert "DTYPE_WIDTHS = {" not in src


# --------------------------------------------------------------------------
# summarize — bench JSON block shape
# --------------------------------------------------------------------------


def test_summarize_counts_by_rule():
    findings, _ = _audit(lambda a, b: a.astype(jnp.bfloat16) @ b, Q8, BF)
    s = summarize(findings)
    assert s == {"total": 2, "by_rule": {"RLT801": 1, "RLT805": 1}}
    assert summarize([]) == {"total": 0, "by_rule": {}}


# --------------------------------------------------------------------------
# precision ledger — byte identities against the audit's own accounting
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mnist_report():
    from ray_lightning_tpu.analysis.cli import resolve_trace_target
    from ray_lightning_tpu.analysis.costmodel import parse_topology
    from ray_lightning_tpu.analysis.tracecheck import audit_step

    topo = parse_topology("v5p-8")
    module, strategy, batch, label = resolve_trace_target(
        "mnist_dp_example.py", topo)
    return audit_step(module, strategy, batch, topology="v5p-8",
                      label=label)


class TestPrecisionLedger:
    def test_ledger_sums_match_plan_bytes(self, mnist_report):
        p = mnist_report.precision
        assert sum(p["params"].values()) == \
            mnist_report.params_bytes_per_device
        assert sum(p["opt_state"].values()) == \
            mnist_report.opt_bytes_per_device
        assert all(v > 0 for by in
                   (p["params"], p["opt_state"], p["activations"])
                   for v in by.values())

    def test_ledger_classes_and_loss_dtype(self, mnist_report):
        p = mnist_report.precision
        assert set(p) == {"params", "opt_state", "activations",
                          "kv_pool", "loss_widest_dtype"}
        assert p["kv_pool"] == {}  # training step holds no KV pool
        assert p["loss_widest_dtype"] == "float32"

    def test_ledger_in_to_dict(self, mnist_report):
        d = mnist_report.to_dict()
        assert d["precision"] == mnist_report.precision

    def test_numerics_off_means_no_ledger(self):
        from ray_lightning_tpu.analysis.cli import resolve_trace_target
        from ray_lightning_tpu.analysis.costmodel import parse_topology
        from ray_lightning_tpu.analysis.tracecheck import audit_step

        topo = parse_topology("v5p-8")
        module, strategy, batch, label = resolve_trace_target(
            "mnist_dp_example.py", topo)
        rep = audit_step(module, strategy, batch, topology="v5p-8",
                         label=label, numerics=False)
        assert rep.precision is None


# --------------------------------------------------------------------------
# repo audits clean — every bundled trace target is RLT8xx-free
# --------------------------------------------------------------------------

_RLT8XX = {"RLT801", "RLT802", "RLT803", "RLT804", "RLT805"}


def _trace_rules(target, topo_name):
    from ray_lightning_tpu.analysis.cli import resolve_trace_target
    from ray_lightning_tpu.analysis.costmodel import parse_topology
    from ray_lightning_tpu.analysis.tracecheck import audit_step

    topo = parse_topology(topo_name)
    module, strategy, batch, label = resolve_trace_target(target, topo)
    rep = audit_step(module, strategy, batch, topology=topo_name,
                     label=label)
    return rep, sorted({f.rule for f in rep.findings} & _RLT8XX)


@pytest.mark.parametrize("target", [
    "mnist_dp_example.py", "pod_launch_example.py",
    "cifar_resnet_example.py", "bert_finetune_example.py",
])
def test_bundled_targets_numerics_clean(target):
    rep, rules = _trace_rules(target, "v5p-8")
    assert rules == []
    # a clean verdict is not a blind one
    assert rep.unentered == [] and rep.lost_specs == {}


@pytest.mark.slow
def test_llama3_8b_flagship_numerics_clean():
    rep, rules = _trace_rules("llama3-8b", "v5p-64")
    assert rules == []
    assert rep.unentered == [] and rep.lost_specs == {}
    assert rep.precision["loss_widest_dtype"] == "float32"
    assert rep.precision["params"]  # the ledger is populated


# --------------------------------------------------------------------------
# AST mini-pass — `lint --numerics`
# --------------------------------------------------------------------------


class TestASTPass:
    def test_inline_bf16_astype_in_dot_fires(self):
        src = ("import jax.numpy as jnp\n"
               "def f(a, b):\n"
               "    return jnp.dot(a.astype(jnp.bfloat16), b)\n")
        findings = check_numerics_sources([("m.py", src)])
        assert [f.rule for f in findings] == ["RLT801"]
        assert findings[0].line == 3

    def test_preferred_element_type_sanctions(self):
        src = ("import jax.numpy as jnp\n"
               "def f(a, b):\n"
               "    return jnp.einsum('ij,jk->ik', a.astype(jnp.bfloat16),"
               " b, preferred_element_type=jnp.float32)\n")
        assert check_numerics_sources([("m.py", src)]) == []

    def test_inline_int8_astype_fires_805(self):
        src = ("import jax.numpy as jnp\n"
               "def f(a, b):\n"
               "    return jnp.matmul(a.astype(jnp.int8), b)\n")
        findings = check_numerics_sources([("m.py", src)])
        assert [f.rule for f in findings] == ["RLT805"]

    def test_disable_comment_suppresses(self):
        src = ("import jax.numpy as jnp\n"
               "def f(a, b):\n"
               "    return jnp.dot(a.astype(jnp.bfloat16), b)"
               "  # rlt: disable=RLT801\n")
        assert check_numerics_sources([("m.py", src)]) == []


# --------------------------------------------------------------------------
# CLI smoke
# --------------------------------------------------------------------------


#: the CLI runs from whatever directory the suite's fixtures left, so
#: the package's location is pinned (as tests/test_tracecheck_examples.py
#: does): a sandbox without the package on its path failed these three
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CLI_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", "")}


class TestCLISmoke:
    def test_lint_numerics_flag(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax.numpy as jnp\n"
                       "def f(a, b):\n"
                       "    return jnp.dot(a.astype(jnp.bfloat16), b)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "ray_lightning_tpu", "lint",
             "--numerics", str(bad)],
            capture_output=True, text=True, env=_CLI_ENV)
        assert proc.returncode == 1
        assert "RLT801" in proc.stdout
        proc = subprocess.run(
            [sys.executable, "-m", "ray_lightning_tpu", "lint",
             "--no-numerics", str(bad)],
            capture_output=True, text=True, env=_CLI_ENV)
        assert proc.returncode == 0
        assert "RLT801" not in proc.stdout

    def test_trace_no_numerics_json(self):
        proc = subprocess.run(
            [sys.executable, "-m", "ray_lightning_tpu", "trace",
             "mnist_dp_example.py", "--topo", "v5p-8", "--no-numerics",
             "--json"],
            capture_output=True, text=True, env=_CLI_ENV)
        assert proc.returncode == 0, proc.stderr
        d = json.loads(proc.stdout)
        assert d["precision"] is None

    def test_trace_numerics_json_has_ledger(self):
        proc = subprocess.run(
            [sys.executable, "-m", "ray_lightning_tpu", "trace",
             "mnist_dp_example.py", "--topo", "v5p-8", "--json"],
            capture_output=True, text=True, env=_CLI_ENV)
        assert proc.returncode == 0, proc.stderr
        d = json.loads(proc.stdout)
        assert d["precision"]["loss_widest_dtype"] == "float32"
        assert sum(d["precision"]["params"].values()) == \
            d["params_bytes_per_device"]
