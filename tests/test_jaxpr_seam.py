"""The seam between the jaxpr analyses and jax's internals
(`analysis/jaxpr.py`): every wrapper jax 0.9 puts around a sub-program
is entered by both walkers, findings carry this file's name and line,
and what a walk cannot model fails aloud (RLT310) instead of reading
"clean". A jax upgrade that renames a wrapper or moves the source-info
API fails these cases by name."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax, shard_map
from jax.extend.core import Primitive
from jax.sharding import AbstractMesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.analysis.jaxpr import call_body, sub_jaxprs
from ray_lightning_tpu.analysis.numcheck import numcheck_jaxpr
from ray_lightning_tpu.analysis.tracecheck import audit_step
from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.parallel.strategy import ShardedMesh

_HERE = os.path.basename(__file__)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _custom_jvp(inner):
    f = jax.custom_jvp(inner)
    f.defjvp(lambda primals, tangents: (inner(primals[0]), tangents[0]))
    return f


def _custom_vjp(inner):
    f = jax.custom_vjp(inner)
    f.defvjp(lambda x: (inner(x), None), lambda _, g: (g,))
    return f


def _shard_map(inner):
    mesh = AbstractMesh((1,), ("seam",))
    return shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P())


#: wrapper name -> (inner -> the same function run inside that wrapper)
WRAPPERS = {
    "jit": lambda inner: jax.jit(inner),
    "checkpoint": lambda inner: jax.checkpoint(inner),
    "custom_jvp": _custom_jvp,
    "custom_vjp_under_grad": _custom_vjp,
    # loops: the operand is a loop constant, so the sub-program runs on
    # it as given (a carry enters in its settled layout or dtype)
    "scan": lambda inner: lambda x: lax.scan(
        lambda c, _: (c + inner(x), None), jnp.zeros_like(x), None,
        length=2)[0],
    "cond": lambda inner: lambda x: lax.cond(
        x.sum() > 0, inner, inner, x),
    "while_loop": lambda inner: lambda x: lax.while_loop(
        lambda c: c[1] < 2, lambda c: (c[0] + inner(x), c[1] + 1),
        (jnp.zeros_like(x), 0))[0],
    "shard_map": _shard_map,
}


# ---- numcheck: a cast round trip inside the wrapper ----------------------


def _churn(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_CHURN_LINE = _churn.__code__.co_firstlineno + 1


@pytest.mark.parametrize("wrapper", sorted(WRAPPERS))
def test_numcheck_enters_wrapper(wrapper):
    f = WRAPPERS[wrapper](_churn)
    x = jnp.ones((8, 8), jnp.float32)
    if wrapper == "custom_vjp_under_grad":
        f = jax.grad(lambda v, f=f: f(v).sum())
    closed = jax.make_jaxpr(f)(x)
    findings, _ = numcheck_jaxpr(closed)
    assert "RLT310" not in {f.rule for f in findings}, findings
    churn = [f for f in findings if f.rule == "RLT803"]
    assert churn, f"the round trip inside {wrapper} was not seen"
    assert any(f.symbol == f"convert_element_type @ {_HERE}:{_CHURN_LINE}"
               for f in churn), [f.symbol for f in churn]


# ---- tracecheck: a weight gather inside the wrapper ----------------------


class _Gathers(TpuModule):
    """One fsdp-sharded weight; the step gathers it inside ``wrapper``
    (a constraint to replicated; under shard_map, where constraints may
    not name a manual axis, an explicit all_gather)."""

    def __init__(self, wrapper):
        super().__init__()
        self.wrapper = wrapper

    def init_params(self, rng, batch):
        return {"w": jnp.zeros((64, 64), jnp.float32)}

    def configure_model(self):
        return None

    def configure_optimizers(self):
        return optax.sgd(1e-2)

    def param_specs(self, params):
        return {"w": P("fsdp", None)}

    def training_step(self, params, batch, rng):
        if self.wrapper == "shard_map":
            f = shard_map(
                lambda w: lax.all_gather(w, "fsdp", tiled=True),
                mesh=self.mesh, in_specs=P("fsdp", None), out_specs=P(),
                check_vma=False)
        else:
            f = WRAPPERS[self.wrapper](
                lambda w: lax.with_sharding_constraint(
                    w, NamedSharding(self.mesh, P(None, None))))
        w = params["w"]
        if self.wrapper == "while_loop":  # not reverse-differentiable
            w = lax.stop_gradient(w)
        return (batch["x"] @ f(w)).mean()


_GATHER = {"shard_map": "all_gather"}


@pytest.mark.parametrize("wrapper", sorted(WRAPPERS))
def test_tracecheck_enters_wrapper(wrapper):
    rep = audit_step(_Gathers(wrapper), ShardedMesh(fsdp=4),
                     {"x": np.zeros((8, 64), np.float32)},
                     topology="v5e-4", label=wrapper)
    assert rep.unentered == [] and rep.lost_specs == {}, rep.summary()
    prim = _GATHER.get(wrapper, "sharding_constraint")
    gathers = [e for e in rep.collectives
               if e.kind == "all_gather" and e.axes == ("fsdp",)
               and e.source.startswith(f"{prim} @ {_HERE}:")]
    assert gathers, (f"the gather inside {wrapper} is not in the "
                     f"schedule:\n{rep.summary()}")
    assert gathers[0].payload_bytes == 64 * 64 * 4


# ---- what the walks cannot model fails aloud -----------------------------

#: a wrapper jax might add tomorrow: one sub-program, lined up with the
#: equation one for one
_opaque_p = Primitive("seam_opaque_call")
_opaque_p.def_abstract_eval(lambda x, *, program: x)

#: ... and one whose sub-program does not line up (two inputs for the
#: equation's one): no rule can enter it
_mystery_p = Primitive("seam_mystery_call")
_mystery_p.def_abstract_eval(lambda x, *, program: x)


def _opaque(x):
    return _opaque_p.bind(x, program=jax.make_jaxpr(_churn)(x))


def _mystery(x):
    return _mystery_p.bind(
        x, program=jax.make_jaxpr(lambda a, b: _churn(a) + b)(x, x))


def test_unknown_wrapper_that_lines_up_is_entered_as_a_call():
    closed = jax.make_jaxpr(_opaque)(jnp.ones((8, 8), jnp.float32))
    eqn = closed.jaxpr.eqns[0]
    assert [k for k, _ in sub_jaxprs(eqn)] == ["program"]
    assert call_body(eqn) is not None
    findings, _ = numcheck_jaxpr(closed)
    assert [f.rule for f in findings] == ["RLT803"]


def test_unentered_sub_program_is_an_error_in_both_walks():
    x = jnp.ones((8, 8), jnp.float32)
    closed = jax.make_jaxpr(_mystery)(x)
    assert call_body(closed.jaxpr.eqns[0]) is None
    findings, _ = numcheck_jaxpr(closed)
    assert [f.rule for f in findings] == ["RLT310"]
    assert findings[0].severity == "error"
    assert "seam_mystery_call" in findings[0].symbol
    assert "program" in findings[0].message

    class _Wrapped(_Gathers):
        def training_step(self, params, batch, rng):
            return (_mystery(batch["x"]) @ params["w"]).mean()

    rep = audit_step(_Wrapped("jit"), ShardedMesh(fsdp=4),
                     {"x": np.zeros((8, 64), np.float32)},
                     topology="v5e-4", numerics=False)
    assert len(rep.unentered) == 1, rep.summary()
    assert f"seam_mystery_call @ {_HERE}" in rep.unentered[0]
    assert rep.to_dict()["unentered"] == rep.unentered
    # the batch is sharded over fsdp and the walk has no rule for the
    # primitive: the spec it lost is on the record too
    assert rep.lost_specs == {"seam_mystery_call": 1}
    assert "seam_mystery_call x1" in rep.summary()


def test_handler_that_raises_is_recorded_not_swallowed(monkeypatch):
    """The per-equation catch keeps the audit's promise to finish; what
    it caught is an RLT310, not a quiet 'unknown'. A moved source-info
    API is the case that went unseen for eight PRs."""
    from ray_lightning_tpu.analysis import numcheck

    def moved(eqn):
        raise AttributeError("'SourceInfo' object has no attribute 'x'")

    monkeypatch.setattr(numcheck, "source_of", moved)
    closed = jax.make_jaxpr(_churn)(jnp.ones((4,), jnp.float32))
    findings, _ = numcheck_jaxpr(closed)
    assert {f.rule for f in findings} == {"RLT310"}
    assert any("AttributeError" in f.message for f in findings)


def test_trace_cli_fails_on_an_opaque_wrapper(tmp_path):
    (tmp_path / "seam_factory.py").write_text(textwrap.dedent("""
        import numpy as np
        from ray_lightning_tpu.parallel.strategy import ShardedMesh
        from tests.test_jaxpr_seam import _Gathers, _mystery


        class Wrapped(_Gathers):
            def training_step(self, params, batch, rng):
                return (_mystery(batch["x"]) @ params["w"]).mean()


        def build():
            return (Wrapped("jit"), ShardedMesh(fsdp=4),
                    {"x": np.zeros((8, 64), np.float32)})
    """))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [str(tmp_path), _REPO, os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run(
        [sys.executable, "-m", "ray_lightning_tpu", "trace",
         "seam_factory:build", "--topo", "v5e-4", "--json"],
        capture_output=True, text=True, timeout=300, cwd=_REPO, env=env)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["ok"] is False
    assert len(d["unentered"]) == 1  # both walks met it: reported once
    assert f"seam_mystery_call @ {_HERE}" in d["unentered"][0]
    assert d["lost_specs"] == {"seam_mystery_call": 1}
