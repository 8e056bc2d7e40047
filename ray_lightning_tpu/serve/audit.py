"""Static analysis of the serving engine: tracecheck the decode step,
price the paged cache in HBM — zero devices, CPU-host safe.

Two consumers:

  * ``plan --serve`` (the serve-aware plan leg): a serving replica's
    HBM story — params + paged pool + the attention path's gathered
    view (the reference lane's capacity-wide dense copy, or the fused
    kernel's surviving per-group prefill gather) + the carried logits
    buffer — against the chip budget, plus the jaxpr-level audit of
    the step itself;
  * the test/format.sh gates: the decode step must audit CLEAN on BOTH
    attention paths — the paged gather/kernel must never read as an
    implicit reshard (RLT301), the step contains no ring collectives to
    deadlock (RLT303), a step that still materializes the dense
    slot-gathered view on a shape the fused kernel supports is flagged
    **RLT307 dense-paged-gather** (fires on the reference-path
    flagship trace; absent on the fused path, where the view does not
    exist; sanctioned on shapes the kernel cannot tile), and a step
    whose cond-nested PREFILL lane still gathers its group-sized pool
    view on a shape the fused prefill kernel tiles is flagged
    **RLT308 dense-paged-prefill-gather** (same fire/sanction
    discipline — the historical blanket sanction of the prefill
    gather became shape-conditional once the kernel covered it).
"""
from __future__ import annotations

from typing import Optional

from ray_lightning_tpu.analysis.costmodel import (
    Topology, paged_decode_traffic_bytes, parse_topology,
)
from ray_lightning_tpu.analysis.jaxpr import (
    dce, pallas_kernel_ident, walk_eqns,
)
from ray_lightning_tpu.serve.engine import EngineConfig, build_step
from ray_lightning_tpu.serve.kv_cache import serve_kv_plan_bytes


def _shape_fused_available(model_cfg, engine_cfg: EngineConfig) -> bool:
    """Would the fused DECODE kernel take this (model, engine) shape on
    a TPU (its KV tile: several pool blocks, `decode_tile_tokens`; its
    steps: a slot's live tiles)? The PLANNER'S question — shape support
    only, independent of the host's backend (a CPU host planning a v5p
    deployment must price the kernel the TPU will run; the runtime
    dispatch adds the backend gate via
    `ops.attention.paged_attention_uses_pallas`)."""
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_shapes_supported,
    )

    spec = engine_cfg.pool_spec
    return paged_shapes_supported(
        (engine_cfg.capacity, model_cfg.n_heads, model_cfg.head_dim),
        (spec.n_blocks, spec.block_size, model_cfg.n_kv_heads,
         model_cfg.head_dim))


def _shape_fused_prefill_available(model_cfg,
                                   engine_cfg: EngineConfig) -> bool:
    """The prefill twin of `_shape_fused_available`: would the fused
    PREFILL kernel tile this (model, engine) shape on a TPU? The two
    kernels gate shapes independently (the prefill kernel additionally
    tiles the chunk width)."""
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_shapes_supported,
    )

    spec = engine_cfg.pool_spec
    return paged_prefill_shapes_supported(
        (engine_cfg.prefill_batch, engine_cfg.prefill_chunk,
         model_cfg.n_heads, model_cfg.head_dim),
        (spec.n_blocks, spec.block_size, model_cfg.n_kv_heads,
         model_cfg.head_dim))


def trace_decode_step(model_cfg, engine_cfg: EngineConfig,
                      fused: bool = False,
                      fused_prefill: Optional[bool] = None):
    """``(closed_jaxpr, meta)`` for the engine's continuous-batching
    step over abstract inputs — the exact program `DecodeEngine` jits,
    traced with `eval_shape`/`make_jaxpr` so no backend initializes.

    ``fused=True`` traces the fused-lane program — the paged-attention
    kernel is pinned by `build_step`'s baked dispatch decision
    (`PagedDecodeView.use_pallas`, the same static aux `DecodeEngine`
    compiles), so the audited program IS the one a fused replica runs
    regardless of the host's backend; ``fused=False`` traces the
    reference lane as dispatched on this host. ``fused_prefill``
    selects the prefill lane the same way; ``None`` (the default)
    follows ``fused`` GATED BY the prefill kernel's own shape support
    — the engine decides the two lanes independently
    (`DecodeEngine.fused_prefill`), so on a shape only the decode
    kernel tiles the default traces the mixed program the replica
    actually compiles, not a fused-prefill program that would silently
    fall back inside the trace. ``meta`` carries ``pallas_kernels``
    (kernel identities found anywhere in the trace),
    ``dense_paged_gathers`` (top-level capacity-wide gathers of the
    pool — the RLT307 evidence) and ``prefill_paged_gathers``
    (cond-nested group-sized gathers of the pool — the RLT308
    evidence)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama
    from ray_lightning_tpu.models.serving import require_llama

    require_llama(model_cfg, "serve/audit.py:trace_decode_step")
    if fused_prefill is None:
        fused_prefill = fused and _shape_fused_prefill_available(
            model_cfg, engine_cfg)
    model = Llama(model_cfg)
    step = build_step(model, engine_cfg, fused=fused,
                      fused_prefill=fused_prefill)
    spec = engine_cfg.pool_spec
    C, CH, B = engine_cfg.capacity, engine_cfg.prefill_chunk, \
        engine_cfg.prefill_batch
    s = jax.ShapeDtypeStruct
    a_tok = np.zeros((1, 2), np.int32)
    a_params = jax.eval_shape(
        lambda k: model.init(k, a_tok)["params"],
        jax.eval_shape(lambda: jax.random.key(0)))
    pool = s((model_cfg.n_layers, spec.n_blocks, spec.block_size,
              model_cfg.n_kv_heads, model_cfg.head_dim),
             jnp.dtype(model_cfg.dtype))
    args = (
        a_params, pool, pool,
        s((C, model_cfg.vocab_size), jnp.float32),       # last_logits
        s((C, spec.blocks_per_slot), jnp.int32),         # tables
        s((C,), jnp.int32), s((C,), jnp.bool_),          # pos, decoding
        s((C,), jnp.float32), s((C,), jnp.int32),        # temp, top_k
        s((C, 2), jnp.uint32),                           # rngs
    )
    if B == 1:
        args += (
            s((), jnp.int32), s((CH,), jnp.int32),       # pf slot/tokens
            s((), jnp.int32), s((), jnp.int32),          # pf pos/last_row
        )
    else:
        args += (
            s((C,), jnp.int32),                          # slot_pad
            s((B,), jnp.int32), s((B, CH), jnp.int32),   # pf slots/tokens
            s((), jnp.int32), s((), jnp.int32),          # pf pos/last_row
            s((B,), jnp.int32),                          # pf pads
        )
    closed = dce(jax.make_jaxpr(step)(*args))
    params_bytes = sum(
        int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(a_params))
    pool_shape = tuple(pool.shape)
    return closed, {
        "args": args,
        "params_bytes": params_bytes,
        "fused": fused,
        "fused_prefill": fused_prefill,
        "pallas_kernels": _pallas_kernel_names(closed.jaxpr),
        "dense_paged_gathers": _dense_paged_gathers(
            closed.jaxpr, pool_shape, C),
        "prefill_paged_gathers": _prefill_paged_gathers(
            closed.jaxpr, pool_shape, C,
            engine_cfg.pool_spec.blocks_per_slot),
    }


def _pallas_kernel_names(jaxpr) -> list:
    """Kernel identities anywhere in the trace (recursive) — the
    fingerprint that the fused path actually lowered the kernel. The
    identity string is `analysis.jaxpr.pallas_kernel_ident`, the same
    extraction the step auditor records into
    `TraceReport.pallas_kernels`."""
    return [pallas_kernel_ident(eqn) for eqn, _ in walk_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def _dense_paged_gathers(jaxpr, pool_shape, capacity: int) -> list:
    """TOP-LEVEL gathers of a pool-shaped invar whose output is the
    capacity-wide dense slot view ``[L, C, M, P, Hkv, hd]`` — the
    decode lane's materialized copy, and RLT307's evidence. Top level
    only by design: the prefill lane's per-group gather lives inside
    the step's `lax.cond` and is RLT308's domain
    (`_prefill_paged_gathers` — shape-conditional on the fused PREFILL
    kernel covering it, no longer a blanket sanction; the copy is
    group-sized, priced honestly by `serve_kv_plan_bytes`)."""
    pool_vars = [v for v in jaxpr.invars
                 if tuple(getattr(v.aval, "shape", ())) == pool_shape]
    hits = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "gather" or not eqn.invars:
            continue
        if eqn.invars[0] not in pool_vars:
            continue
        out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
        if (len(out_shape) == 6 and out_shape[0] == pool_shape[0]
                and out_shape[1] == capacity):
            hits.append(out_shape)
    return hits


def _prefill_paged_gathers(jaxpr, pool_shape, capacity: int,
                           blocks_per_slot: int) -> list:
    """Gathers of a pool-shaped operand at ANY nesting level whose
    output is a group-sized dense slot view — the prefill lane's
    materialized per-group copy (it lives inside the step's `lax.cond`)
    and RLT308's evidence. Two shapes qualify:

      * ``[L, B, M, P, Hkv, hd]`` with ``B <= capacity`` and
        ``M == blocks_per_slot`` — the batched lane's group view
        (the capacity-wide B == capacity decode view is RLT307's
        top-level evidence, but nested it is still a dense paged
        gather and counts here);
      * ``[L, M, P, Hkv, hd]`` with ``M == blocks_per_slot`` — the
        single-slot lane's per-row view.

    Matching is by aval shape (a cond/pjit branch's pool invar carries
    the pool's aval), the same discipline as `_dense_paged_gathers`."""
    L, _, P, HKV, HD = pool_shape
    hits = []

    def _match(out_shape) -> bool:
        if len(out_shape) == 6:
            return (out_shape[0] == L and out_shape[1] <= capacity
                    and out_shape[2] == blocks_per_slot
                    and out_shape[3:] == (P, HKV, HD))
        if len(out_shape) == 5:
            return (out_shape[0] == L
                    and out_shape[1] == blocks_per_slot
                    and out_shape[2:] == (P, HKV, HD))
        return False

    for eqn, nested in walk_eqns(jaxpr):
        if (nested and eqn.primitive.name == "gather" and eqn.invars
                and tuple(getattr(eqn.invars[0].aval, "shape", ()))
                == pool_shape):
            out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
            if _match(out_shape):
                hits.append(out_shape)
    return hits


def _tp_invar_seeds(auditor, model_cfg, meta, tp: int):
    """`_VarInfo` seeds for the step's invars under a ``tp``-way tensor
    mesh — the SAME layout `DecodeEngine` places, so the audited
    collectives are the served ones: params via
    `engine.serving_param_specs` (wqkv/gate_up column-split, wo/w_down
    row-split, embeddings vocab-split; `seed_state` also registers each
    shape for the vars the walk re-derives, scan-sliced per-layer
    weights chiefly), the two pool leaves KV-head sharded
    (`kv_cache.pool_partition_spec`), every host-fed input and the
    carried logits replicated (the scheduler is tp-oblivious)."""
    import dataclasses as _dc

    from ray_lightning_tpu.analysis.tracecheck import (
        _repl, _spec_of_partition_spec, _VarInfo,
    )
    from ray_lightning_tpu.models.llama import Llama
    from ray_lightning_tpu.parallel.mesh import MeshSpec
    from ray_lightning_tpu.serve.engine import serving_param_specs
    from ray_lightning_tpu.serve.kv_cache import (
        pool_partition_spec, validate_pool_tp,
    )
    from ray_lightning_tpu.utils.pytree import named_leaves

    validate_pool_tp(model_cfg, tp)
    axis_names = tuple(f.name for f in _dc.fields(MeshSpec))
    a_params = meta["args"][0]
    seeds = auditor.seed_state(
        dict(named_leaves(a_params)),
        [spec for _, spec in serving_param_specs(
            Llama(model_cfg), a_params, axis_names)], "params")
    pool_spec = auditor._canon(
        _spec_of_partition_spec(pool_partition_spec(tp), 5))
    for i, arg in enumerate(meta["args"][1:], start=1):
        ndim = len(getattr(arg, "shape", ()))
        if i in (1, 2):
            seeds.append(_VarInfo(
                pool_spec, param=True,
                path="pool_k" if i == 1 else "pool_v"))
        else:
            seeds.append(_VarInfo(_repl(ndim), param=True))
    return seeds


def audit_decode_step(model_cfg, engine_cfg: EngineConfig,
                      topology="v5p-8", reserve_fraction: float = 0.10,
                      label: str = "serve decode step",
                      fused: bool = False,
                      fused_prefill: Optional[bool] = None,
                      traced=None, numerics: bool = True,
                      tp: int = 1):
    """Full tracecheck walk of the decode step: collective schedule
    (none expected on a single-replica tp=1 step — each replica is one
    model copy), RLT301/303/307/308 findings, and the liveness HBM peak
    vs the chip budget. Returns a `tracecheck.TraceReport`.

    ``tp > 1`` audits ONE RANK of a tensor-parallel replica: the
    invars are seeded with the engine's served layout
    (`_tp_invar_seeds`) and the walk prices the decode step's implicit
    collectives — the per-tick attention/MLP psums over the ``tensor``
    axis — exactly the way training steps are priced (wire bytes on
    ICI; ``sum(ev.wire_bytes for ev in report.collectives)`` is the
    decode ICI bytes/tick the bench gate ratchets). The traced program
    is identical (SPMD comes from shardings at jit time), so ``traced``
    reuse stays valid across ``tp`` values.

    ``numerics`` additionally runs numcheck's RLT801-805 pass over the
    same jaxpr (the int8-KV campaign's audit surface: an unscaled int8
    pool read fires RLT805 right here) and fills the report's
    ``precision`` ledger — per-dtype params / KV-pool / activation
    bytes; the decode step has no loss output, so the widest-path entry
    stays None.

    RLT307 (dense-paged-gather) fires when the traced step materializes
    the capacity-wide dense KV view although the fused decode kernel
    tiles the shape — i.e. on the reference-path flagship trace. RLT308
    (dense-paged-prefill-gather) is the prefill twin: it fires when the
    cond-nested prefill lane still gathers its group-sized pool view
    although the fused PREFILL kernel tiles the shape (the historical
    blanket sanction of the prefill gather became shape-conditional
    once the kernel covered it). The fused trace has neither gather
    (the views never exist), and shapes the kernels cannot tile are
    sanctioned.

    ``traced`` takes a ``(closed, meta)`` pair from an earlier
    `trace_decode_step` call with the SAME config/lanes so a caller
    that already holds the trace (the smoke legs read meta's gather
    evidence directly) never pays a second full trace of the same
    step — the PR 11 one-trace discipline."""
    import math

    import jax
    import numpy as np

    from ray_lightning_tpu.analysis.findings import Finding
    from ray_lightning_tpu.analysis.tracecheck import (
        TraceReport, _repl, _StepAuditor, _VarInfo,
    )

    topo = (topology if isinstance(topology, Topology)
            else parse_topology(topology))
    closed, meta = (traced if traced is not None
                    else trace_decode_step(model_cfg, engine_cfg,
                                           fused=fused,
                                           fused_prefill=fused_prefill))
    auditor = _StepAuditor({"tensor": tp} if tp > 1 else {}, topo)
    seeds = (_tp_invar_seeds(auditor, model_cfg, meta, tp)
             if tp > 1 else None)
    jaxpr = closed.jaxpr
    env = {v: _VarInfo(_repl(len(getattr(v.aval, "shape", ()))),
                       param=True)
           for v in (*jaxpr.invars, *jaxpr.constvars)}
    if seeds is not None:
        env.update(zip(jaxpr.invars, seeds, strict=True))
    peak, peak_by = auditor.walk(jaxpr, env, 1, False)
    if tp > 1:
        # the engine's jit pins every non-pool output REPLICATED at the
        # boundary (DecodeEngine out_shardings): the column-split
        # lm_head leaves `last_logits` vocab-sharded, so GSPMD
        # all-gathers it over `tensor` at the step's edge — the
        # dominant decode collective by bytes, and invisible inside the
        # traced function (the constraint lives in jit metadata, not
        # the jaxpr). Priced here from the walked output specs: the
        # pools (outvars 0-1) keep their sharding, everything else
        # gathers whatever tensor axes survive to the boundary.
        for i, v in enumerate(jaxpr.outvars):
            if i < 2:
                continue
            spec = auditor._info(v, env).spec
            if not spec:
                continue
            lost = {ax for s in spec for ax in s}
            if lost:
                auditor.record(
                    "all_gather", auditor._aval_bytes(v.aval, None),
                    sorted(lost), 1, implicit=True,
                    source="jit boundary (replicated out_shardings)",
                    dtype=str(getattr(v.aval, "dtype", "")) or None)
    findings = auditor.findings
    budget = int(topo.hbm_bytes * (1 - reserve_fraction))
    gib = 1024**3
    if peak > budget:
        findings.append(Finding(
            "RLT302",
            f"estimated peak HBM {peak / gib:.2f} GiB/device exceeds "
            f"the {topo.device_kind} budget {budget / gib:.2f} GiB: the "
            "serving step will OOM on this chip — shrink capacity, "
            "blocks_per_slot, or the pool",
            symbol=label))

    def _view_gib(shape) -> float:
        # k + v gathers at the POOL's dtype (model_cfg.dtype — the
        # first step invar is a param leaf whose dtype can differ,
        # e.g. f32 params serving a bf16 cache)
        return (2 * math.prod(shape)
                * np.dtype(model_cfg.dtype).itemsize) / gib

    if meta["dense_paged_gathers"] and _shape_fused_available(
            model_cfg, engine_cfg):
        shape = meta["dense_paged_gathers"][0]
        findings.append(Finding(
            "RLT307",
            f"the decode lane gathers a dense {list(shape)} slot view "
            f"of the paged pool every tick (~{_view_gib(shape):.2f} "
            "GiB of HBM + a full copy of traffic) on a shape the fused "
            "paged-attention kernel tiles — the kernel consumes the "
            "pool through the block tables and retires the view "
            "(selected automatically on TPU; "
            "docs/SERVING.md 'paged-attention kernel')",
            symbol=label))
    if meta["prefill_paged_gathers"] and _shape_fused_prefill_available(
            model_cfg, engine_cfg):
        shape = meta["prefill_paged_gathers"][0]
        findings.append(Finding(
            "RLT308",
            f"the prefill lane gathers a dense {list(shape)} "
            "group-sized view of the paged pool every chunk "
            f"(~{_view_gib(shape):.2f} GiB of HBM + a per-chunk copy "
            "of traffic) on a shape the fused paged-prefill kernel "
            "tiles — the kernel attends causally through the block "
            "tables and retires the last dense gather (selected "
            "automatically on TPU; docs/SERVING.md 'paged prefill "
            "kernel')",
            symbol=label))
    precision = None
    if numerics:
        from ray_lightning_tpu.analysis import numcheck as _numcheck

        findings.extend(f for f in _numcheck.numcheck_jaxpr(closed)[0]
                        if f not in findings)
        # the serve ledger's classes: params, the paged KV pool (args
        # 1-2: the k/v pools — the bytes the int8-KV campaign will
        # shrink), and whatever else the liveness peak holds. tp > 1:
        # per-SHARD bytes via the seeded specs (same division the
        # liveness walk applied)
        p_leaves = jax.tree.leaves(meta["args"][0])
        params_by: dict = {}
        for i, leaf in enumerate(p_leaves):
            dt = str(leaf.dtype)
            b = (auditor._aval_bytes(leaf, seeds[i].spec)
                 if seeds is not None else
                 int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize)
            params_by[dt] = params_by.get(dt, 0) + b
        pool_by: dict = {}
        for pl in meta["args"][1:3]:
            dt = str(pl.dtype)
            pool_by[dt] = pool_by.get(dt, 0) + int(
                np.prod(pl.shape)) * pl.dtype.itemsize // tp
        act_by: dict = {}
        for dt, b in peak_by.items():
            rem = b - params_by.get(dt, 0) - pool_by.get(dt, 0)
            if rem > 0:
                act_by[dt] = rem
        precision = {
            "params": params_by,
            "opt_state": {},
            "activations": act_by,
            "kv_pool": pool_by,
            "loss_widest_dtype": None,
        }
    params_dev = meta["params_bytes"]
    if seeds is not None:
        params_dev = sum(
            auditor._aval_bytes(leaf, s.spec)
            for leaf, s in zip(jax.tree.leaves(meta["args"][0]), seeds))
    return TraceReport(
        topology=topo,
        mesh_axes={"tensor": tp} if tp > 1 else {},
        collectives=auditor.events,
        findings=findings,
        params_bytes_per_device=params_dev,
        opt_bytes_per_device=0,
        peak_hbm_bytes=peak,
        hbm_budget_bytes=budget,
        label=label,
        pallas_kernels=auditor.pallas_kernels,
        precision=precision,
        lost_specs=auditor.lost_specs,
    )


def serve_memory_summary(model_cfg, engine_cfg: EngineConfig,
                         device_kind: str = "TPU v5p",
                         hbm_bytes: Optional[int] = None,
                         fused: Optional[bool] = None,
                         fused_prefill: Optional[bool] = None,
                         tp: int = 1) -> dict:
    """The serve-aware plan leg: itemized replica HBM (no optimizer —
    serving holds weights, the paged pool, the attention paths'
    surviving gathered view, and the carried logits) with a fits
    verdict against the chip budget. Pure byte math + one eval_shape;
    no devices.

    ``fused=None`` / ``fused_prefill=None`` auto-select by SHAPE
    support (the planner prices the paths the TPU deployment will run
    — `_shape_fused_available` / `_shape_fused_prefill_available`);
    pass False/True to price a specific path (the before/after table
    in docs/SERVING.md is exactly these pairs).

    ``tp > 1`` prices ONE RANK of a tensor-parallel replica (the
    ``plan --serve --tp N`` leg): params divide by ``tp`` exactly where
    the engine's layout shards them (`engine.serving_param_specs` —
    replicated leaves like norm gains stay whole), the pool and every
    KV view carry the head axis and divide, and the carried logits
    stay replicated. The fits verdict is per-chip."""
    import dataclasses as _dc

    import jax
    import numpy as np

    from ray_lightning_tpu.analysis.costmodel import (
        paged_prefill_traffic_bytes,
    )
    from ray_lightning_tpu.models.llama import Llama
    from ray_lightning_tpu.models.serving import require_llama
    from ray_lightning_tpu.parallel.plan import hbm_bytes_for_kind
    from ray_lightning_tpu.serve.kv_cache import gathered_view_bytes

    require_llama(model_cfg, "serve/audit.py:serve_memory_summary")
    if fused is None:
        fused = _shape_fused_available(model_cfg, engine_cfg)
    if fused_prefill is None:
        fused_prefill = _shape_fused_prefill_available(model_cfg,
                                                       engine_cfg)
    model = Llama(model_cfg)
    a_params = jax.eval_shape(
        lambda k: model.init(k, np.zeros((1, 2), np.int32))["params"],
        jax.eval_shape(lambda: jax.random.key(0)))
    if tp > 1:
        from ray_lightning_tpu.parallel.mesh import MeshSpec
        from ray_lightning_tpu.serve.engine import serving_param_specs

        axis_names = tuple(f.name for f in _dc.fields(MeshSpec))
        params_bytes = 0
        for (_, pspec), leaf in zip(
                serving_param_specs(model, a_params, axis_names),
                jax.tree.leaves(a_params)):
            b = int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
            if any("tensor" in ((e,) if isinstance(e, str) else tuple(e))
                   for e in tuple(pspec) if e is not None):
                b //= tp
            params_bytes += b
    else:
        params_bytes = sum(
            int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(a_params))
    spec = engine_cfg.pool_spec
    kv = serve_kv_plan_bytes(model_cfg, spec, engine_cfg.capacity,
                             fused=fused,
                             prefill_batch=engine_cfg.prefill_batch,
                             fused_prefill=fused_prefill, tp=tp)
    budget = hbm_bytes if hbm_bytes is not None else \
        hbm_bytes_for_kind(device_kind)
    usable = int(budget * 0.90)
    # the retired term is REPORTING (what the kernels bought back) and
    # prefill_gather_bytes is an ITEMIZATION of the surviving view (a
    # slice of gathered_view_bytes, never an extra buffer) — neither
    # may inflate the fits verdict
    resident = {k: v for k, v in kv.items()
                if k not in ("gathered_view_retired_bytes",
                             "prefill_gather_bytes")}
    total = params_bytes + sum(resident.values())
    # per-chunk prefill traffic: the group's span (block reads) + the
    # chunk's new K/V write, with the reference lane's view write+read
    # on top (costmodel.paged_prefill_traffic_bytes)
    group_span = int(gathered_view_bytes(
        model_cfg, spec, min(engine_cfg.prefill_batch,
                             engine_cfg.capacity))) // tp
    itemsize = np.dtype(model_cfg.dtype).itemsize
    chunk_bytes = (2 * model_cfg.n_layers * engine_cfg.prefill_batch
                   * engine_cfg.prefill_chunk * model_cfg.n_kv_heads
                   * model_cfg.head_dim * itemsize) // tp
    return {
        "params_bytes": int(params_bytes),
        **kv,
        "tp": tp,
        "attention_path": ("paged-pallas" if fused
                           else "reference-gather"),
        "prefill_attention_path": ("paged-pallas" if fused_prefill
                                   else "reference-gather"),
        "decode_kv_traffic_bytes_per_tick": paged_decode_traffic_bytes(
            kv["pool_bytes"], serve_kv_plan_bytes(
                model_cfg, spec, engine_cfg.capacity,
                fused=False, tp=tp)["gathered_view_bytes"], fused),
        "prefill_kv_traffic_bytes_per_chunk":
            paged_prefill_traffic_bytes(group_span, chunk_bytes,
                                        fused_prefill),
        "capacity": engine_cfg.capacity,
        "block_size": spec.block_size,
        "n_blocks": spec.n_blocks,
        "max_slot_len": engine_cfg.max_slot_len,
        "per_device_bytes": int(total),
        "budget_bytes": usable,
        "fits": total <= usable,
    }


def _param_count(model_cfg) -> int:
    """Parameter count by eval_shape — no device, no init."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models.llama import Llama
    from ray_lightning_tpu.models.serving import require_llama

    require_llama(model_cfg, "serve/audit.py:_param_count")
    model = Llama(model_cfg)
    a_params = jax.eval_shape(
        lambda key: model.init(key, np.zeros((1, 2), np.int32))["params"],
        jax.eval_shape(lambda: jax.random.key(0)))
    return sum(int(np.prod(leaf.shape or (1,)))
               for leaf in jax.tree.leaves(a_params))


def speculative_plan(model_cfg, draft_cfg, engine_cfg: EngineConfig,
                     accept_rate: float = 0.6) -> dict:
    """Price speculative decoding at this (target, draft, engine)
    shape — pure byte/FLOP math, no devices (the ``plan --serve`` and
    bench static-pricing leg).

    The cost model: one speculative tick spends ONE k-wide verify pass
    of the target (k token-forwards of compute, but a SINGLE sweep of
    the weights + pool — the memory-bound decode's actual currency)
    plus ``k`` single-token draft trips, and emits ``1 +
    accept_rate * (k - 1)`` tokens in expectation. Against ``k`` plain
    decode ticks (k weight+pool sweeps for k tokens), the win is the
    HBM-traffic ratio ``memory_bound_speedup_x``; the FLOP overhead
    ``flops_overhead_x`` is the price (verify recomputes every
    proposal, and rejected tails are discarded work)."""
    import numpy as np

    from ray_lightning_tpu.serve.kv_cache import pool_bytes

    k = engine_cfg.draft.k if engine_cfg.draft is not None else 4
    if not 0.0 <= accept_rate <= 1.0:
        raise ValueError(f"accept_rate {accept_rate} not in [0, 1]")
    n_t, n_d = _param_count(model_cfg), _param_count(draft_cfg)
    spec = engine_cfg.pool_spec
    flops_per_token = 2 * n_t                 # one target token-forward
    verify_step_flops = k * flops_per_token   # one k-wide chunk
    draft_flops_per_tick = k * 2 * n_d        # k single-token trips
    expected = 1.0 + accept_rate * (k - 1)
    params_bytes = n_t * np.dtype(model_cfg.dtype).itemsize
    draft_params_bytes = n_d * np.dtype(draft_cfg.dtype).itemsize
    pool = pool_bytes(model_cfg, spec)
    draft_pool = pool_bytes(draft_cfg, spec)
    # HBM read traffic per tick: the base tick sweeps target weights +
    # pool once per token; the spec tick sweeps them once per k-token
    # verify, plus k draft sweeps
    base_reads = params_bytes + pool
    spec_reads = base_reads + k * (draft_params_bytes + draft_pool)
    return {
        "k": k,
        "accept_rate": accept_rate,
        "target_params": n_t,
        "draft_params": n_d,
        "draft_params_bytes": int(draft_params_bytes),
        "draft_pool_bytes": int(draft_pool),
        "verify_step_flops": int(verify_step_flops),
        "draft_flops_per_tick": int(draft_flops_per_tick),
        "base_decode_flops_per_token": int(flops_per_token),
        "expected_tokens_per_tick": expected,
        "flops_per_emitted_token": int(
            (verify_step_flops + draft_flops_per_tick) / expected),
        "flops_overhead_x": (verify_step_flops + draft_flops_per_tick)
        / (expected * flops_per_token),
        "hbm_read_bytes_per_tick_base": int(base_reads),
        "hbm_read_bytes_per_tick_spec": int(spec_reads),
        "memory_bound_speedup_x": expected * base_reads / spec_reads,
    }


def shared_prefix_plan(model_cfg, engine_cfg: EngineConfig,
                       n_streams: int = 8,
                       prefix_tokens: Optional[int] = None) -> dict:
    """Price prefix sharing for ``n_streams`` requests over a common
    ``prefix_tokens``-token prompt prefix (default: half the slot).
    Only FULL blocks share (K/V at a position depends on the whole
    prefix, so the chain caches per complete block); the savings are
    the pool bytes and prefill tokens the other ``n_streams - 1``
    requests never spend — the ``plan --serve`` / bench static-pricing
    twin of the scheduler's measured `shared_block_fraction`."""
    import numpy as np

    spec = engine_cfg.pool_spec
    P = spec.block_size
    if prefix_tokens is None:
        prefix_tokens = engine_cfg.max_slot_len // 2
    if n_streams < 1:
        raise ValueError(f"n_streams {n_streams} < 1")
    full = min(prefix_tokens, engine_cfg.max_slot_len) // P
    block_bytes = (2 * model_cfg.n_layers * P * model_cfg.n_kv_heads
                   * model_cfg.head_dim
                   * np.dtype(model_cfg.dtype).itemsize)
    return {
        "n_streams": n_streams,
        "prefix_tokens": int(prefix_tokens),
        "shared_full_blocks": int(full),
        "block_bytes": int(block_bytes),
        "pool_bytes_without_sharing": int(n_streams * full * block_bytes),
        "pool_bytes_with_sharing": int(full * block_bytes),
        "shared_pool_bytes_saved": int(
            (n_streams - 1) * full * block_bytes),
        "prefill_tokens_saved": int((n_streams - 1) * full * P),
    }


def format_serve_summary(s: dict) -> str:
    gib = 1024**3
    fused = s.get("attention_path") == "paged-pallas"
    fused_pf = s.get("prefill_attention_path") == "paged-pallas"
    if fused and fused_pf:
        view_line = (
            f"  gathered view    {s['gathered_view_bytes'] / gib:7.2f} "
            "GiB  (prefill gather itemized at "
            f"{s.get('prefill_gather_bytes', 0) / gib:.2f} GiB; the "
            f"{s['gathered_view_retired_bytes'] / gib:.2f} GiB dense "
            "views are RETIRED by the fused paged decode + prefill "
            "kernels — no dense gather remains)")
    elif fused:
        view_line = (
            f"  prefill gather   {s['gathered_view_bytes'] / gib:7.2f} "
            "GiB  (per-group prefill copy; the decode lane's "
            f"{s['gathered_view_retired_bytes'] / gib:.2f} GiB dense "
            "view is RETIRED by the fused paged-attention kernel, and "
            "the fused paged-prefill kernel retires this remainder)")
    else:
        view_line = (
            f"  gathered view    {s['gathered_view_bytes'] / gib:7.2f} "
            "GiB  (reference engine's dense copy; the fused paged "
            "decode + prefill kernels retire it)")
    traffic_tail = ")" if fused else " + dense-view write+read)"
    pf_traffic = s.get("prefill_kv_traffic_bytes_per_chunk")
    tp = s.get("tp", 1)
    tp_tag = (f", tp={tp} (per-shard bytes, one rank of the replica "
              "group)" if tp > 1 else "")
    lines = [
        f"serve plan: {s['capacity']} slots x {s['max_slot_len']} "
        f"tokens, pool {s['n_blocks']} x {s['block_size']}-token "
        f"blocks, attention path: {s.get('attention_path', '?')}, "
        f"prefill path: {s.get('prefill_attention_path', '?')}"
        + tp_tag,
        f"  params           {s['params_bytes'] / gib:7.2f} GiB",
        f"  kv pool          {s['pool_bytes'] / gib:7.2f} GiB",
        view_line,
        f"  carried logits   {s['last_logits_bytes'] / gib:7.2f} GiB",
        f"  decode KV traffic {s['decode_kv_traffic_bytes_per_tick'] / gib:6.2f}"
        " GiB/tick (cost model: pool read" + traffic_tail,
    ]
    if pf_traffic is not None:
        lines.append(
            f"  prefill KV traffic {pf_traffic / gib:5.2f} GiB/chunk "
            "(cost model: group-block reads + chunk write"
            + (")" if fused_pf else " + group-view write+read)"))
    lines.append(
        f"  total {s['per_device_bytes'] / gib:.2f} GiB vs budget "
        f"{s['budget_bytes'] / gib:.2f} GiB — "
        f"{'fits' if s['fits'] else 'DOES NOT FIT'}")
    return "\n".join(lines)
