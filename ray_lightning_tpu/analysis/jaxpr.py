"""What the jaxpr analyses take from jax's internals, in one place.

`tracecheck`, `numcheck` and `serve/audit.py` walk traced programs. What
jax calls a primitive, where an equation keeps its sub-program and how a
source location is read are jax's decisions and move between releases;
the walkers ask here and nowhere else. Nothing in this module catches an
exception: when jax moves an API the call raises, the walker that asked
records the equation as RLT310 (an error), and a test fails by name.
docs/STATIC_ANALYSIS.md "what the analyses take from jax's internals".
"""
from __future__ import annotations

import os
from typing import Any, Iterator, List, Optional, Tuple


def sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """Every sub-program an equation carries, as ``(param key, open
    jaxpr)``, found by structure: a parameter (or a member of a tuple or
    list parameter) that is a jaxpr or closes over one. No primitive
    name is consulted, so a wrapper jax renames or adds is still seen."""
    found = []
    for key, val in eqn.params.items():
        for x in (val if isinstance(val, (tuple, list)) else (val,)):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                found.append((key, inner))
    return found


def call_body(eqn) -> Optional[Any]:
    """The body of an equation that is a plain call, whatever jax names
    it (`jit`, `remat2`, `custom_jvp_call`, `custom_vjp_call`, ...): it
    carries exactly one sub-program, whose inputs and outputs are the
    equation's own, one for one in shape and dtype. Else None."""
    subs = sub_jaxprs(eqn)
    if len(subs) != 1:
        return None
    body = subs[0][1]

    def same(inner, outer) -> bool:
        return len(inner) == len(outer) and all(
            getattr(i.aval, "shape", None) == getattr(o.aval, "shape", None)
            and getattr(i.aval, "dtype", None)
            == getattr(o.aval, "dtype", None)
            for i, o in zip(inner, outer))

    if same(body.invars, eqn.invars) and same(body.outvars, eqn.outvars):
        return body
    return None


def walk_eqns(jaxpr, nested: bool = False) -> Iterator[Tuple[Any, bool]]:
    """``(equation, nested)`` for every equation of a program and of all
    its sub-programs, in program order; ``nested`` is False only at the
    top level."""
    for eqn in jaxpr.eqns:
        yield eqn, nested
        for _, sub in sub_jaxprs(eqn):
            yield from walk_eqns(sub, True)


def user_location(eqn) -> Optional[Tuple[str, int]]:
    """``(file, line)`` of the user frame that made an equation, None
    where jax recorded none."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    return None if frame is None else (frame.file_name, frame.start_line)


def source_of(eqn) -> str:
    """"prim @ file.py:line" for findings and events; the bare primitive
    name where jax recorded no user frame."""
    name = eqn.primitive.name
    where = user_location(eqn)
    if where is None:
        return name
    base = os.path.basename(where[0])
    if base == "tracecheck.py":
        # the synthetic step wrapper (grads -> tx.update ->
        # apply_updates): name the phase, not the auditor's file
        return f"{name} @ <train-step optimizer update>"
    return f"{name} @ {base}:{where[1]}"


def pallas_kernel_ident(eqn) -> str:
    """Name and source line of a `pallas_call`'s kernel
    ("rlt_paged_decode at .../paged_attention.py:76"), which jax keeps
    on the kernel jaxpr's debug info."""
    return str(eqn.params["jaxpr"].debug_info.func_src_info)


def dce(closed):
    """Dead-code-eliminate a traced program (inputs and outputs kept) so
    an audit walks what XLA compiles: jit runs the same pass before
    lowering. Without it the walk charges residuals AD leaves behind
    that nothing consumes."""
    from jax.extend.core import ClosedJaxpr
    from jax.interpreters import partial_eval

    jaxpr, _ = partial_eval.dce_jaxpr(
        closed.jaxpr, [True] * len(closed.jaxpr.outvars), instantiate=True)
    return ClosedJaxpr(jaxpr, closed.consts)
