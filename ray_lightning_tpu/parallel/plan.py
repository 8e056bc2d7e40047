"""Pre-flight sharding/memory planner: prove a training configuration
fits a target topology BEFORE touching hardware.

The reference could not need this — its models were MNIST-sized MLPs
(reference tests/utils.py:96-120) and memory planning was "it fits". At
the north-star scale (BASELINE.json config 4: Llama-3-8B FSDP on a
v5p-64) a mis-sized mesh surfaces as a compile-time OOM after minutes of
queueing, so the framework owns a planner:

  * params/optimizer-state/gradient bytes are computed EXACTLY — the
    model is built only as `jax.eval_shape` abstractions and sharded by
    the strategy's own composition logic over a `jax.sharding.AbstractMesh`
    (zero devices of any kind needed, so an 8-chip dev box can plan a
    4096-chip pod);
  * activations are an analytic, documented bound (they depend on the
    remat policy and loss path, not just shapes) — see
    `llama_activation_bytes` for the flagship model's formula.

Typical use (and the shape of tests/test_llama8b_plan.py)::

    plan = plan_train_memory(
        LlamaModule(LlamaConfig.llama3_8b()),
        ShardedMesh(fsdp=64),
        n_devices=64,
        example_batch={"tokens": np.zeros((64, 8193), np.int32)},
        device_kind="TPU v5p",
    )
    assert plan.fits, plan.summary()
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh

from ray_lightning_tpu.parallel.mesh import AXIS_ORDER, MeshSpec

#: usable HBM per jax device, by PJRT device_kind (public spec sheets).
#: v5p advertises 95 GiB per chip; v5e/v6e per-chip figures likewise.
HBM_BYTES_BY_KIND: Dict[str, int] = {
    "TPU v3": 16 * 1024**3,
    "TPU v4": 32 * 1024**3,
    "TPU v5 lite": 16 * 1024**3,
    "TPU v5e": 16 * 1024**3,
    "TPU v5": 95 * 1024**3,
    "TPU v5p": 95 * 1024**3,
    "TPU v6 lite": 32 * 1024**3,
    "TPU v6e": 32 * 1024**3,
}


def abstract_mesh(spec: MeshSpec) -> AbstractMesh:
    """An AbstractMesh with this spec's axis names/sizes — NamedSharding
    accepts it, `shard_shape` works, and no devices are required."""
    sizes = spec.sizes()
    return AbstractMesh(tuple(sizes[ax] for ax in AXIS_ORDER), AXIS_ORDER)


def hbm_bytes_for_kind(device_kind: str,
                       hbm_bytes: Optional[int] = None) -> int:
    """Usable HBM per device for ``device_kind`` — or the explicit
    ``hbm_bytes`` override for hardware the table doesn't know. An
    unknown kind without an override raises a ValueError LISTING the
    known kinds (never a bare KeyError): the planner's most common
    first-contact failure is a device_kind string that doesn't match the
    spec-sheet spelling."""
    if hbm_bytes is not None:
        if hbm_bytes <= 0:
            raise ValueError(f"hbm_bytes must be positive, got {hbm_bytes}")
        return int(hbm_bytes)
    if device_kind not in HBM_BYTES_BY_KIND:
        raise ValueError(
            f"unknown device_kind {device_kind!r} (known: "
            f"{sorted(HBM_BYTES_BY_KIND)}); pass hbm_bytes_per_device= "
            "(plan_train_memory) / hbm_bytes= (this helper; CLI "
            "--hbm-bytes) explicitly for other hardware"
        )
    return HBM_BYTES_BY_KIND[device_kind]


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    mesh_axes: Dict[str, int]
    n_devices: int
    hbm_bytes_per_device: int
    params_bytes_global: int
    opt_bytes_global: int
    params_bytes_per_device: int
    opt_bytes_per_device: int
    grads_bytes_per_device: int
    activation_bytes_per_device: int
    #: fraction of HBM the plan refuses to allocate (XLA workspace,
    #: fragmentation, infeed buffers)
    reserve_fraction: float = 0.10

    @property
    def per_device_total(self) -> int:
        return (self.params_bytes_per_device + self.opt_bytes_per_device
                + self.grads_bytes_per_device
                + self.activation_bytes_per_device)

    @property
    def budget(self) -> int:
        return int(self.hbm_bytes_per_device * (1 - self.reserve_fraction))

    @property
    def fits(self) -> bool:
        return self.per_device_total <= self.budget

    @property
    def headroom_bytes(self) -> int:
        return self.budget - self.per_device_total

    def summary(self) -> str:
        gib = 1024**3
        return (
            f"mesh {self.mesh_axes} x{self.n_devices} devices: "
            f"params {self.params_bytes_per_device / gib:.2f} + "
            f"opt {self.opt_bytes_per_device / gib:.2f} + "
            f"grads {self.grads_bytes_per_device / gib:.2f} + "
            f"acts {self.activation_bytes_per_device / gib:.2f} = "
            f"{self.per_device_total / gib:.2f} GiB/device vs budget "
            f"{self.budget / gib:.2f} GiB "
            f"({'FITS' if self.fits else 'DOES NOT FIT'}; global params "
            f"{self.params_bytes_global / gib:.2f} GiB, opt "
            f"{self.opt_bytes_global / gib:.2f} GiB)"
        )


def _tree_bytes(tree) -> int:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(tree)
    )


def _sharded_tree_bytes(tree, shardings) -> int:
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)):
        total += int(np.prod(sh.shard_shape(leaf.shape))) * leaf.dtype.itemsize
    return total


def _abstract(batch) -> Any:
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
        if not isinstance(x, jax.ShapeDtypeStruct) else x,
        batch,
    )


def plan_train_memory(
    module,
    strategy,
    n_devices: int,
    example_batch: Any,
    *,
    activation_bytes_per_device: int = 0,
    device_kind: str = "TPU v5p",
    hbm_bytes_per_device: Optional[int] = None,
    reserve_fraction: float = 0.10,
) -> MemoryPlan:
    """Exact per-device param/opt/grad bytes for ``module`` trained under
    ``strategy`` on ``n_devices``, plus the caller's activation estimate.

    Builds NOTHING on devices: the strategy's sharding composition
    (module `param_specs` overlay + fsdp auto-placement + opt-state
    inheritance — the same code the Trainer runs) is evaluated against an
    AbstractMesh, and the model exists only as `eval_shape` output. The
    ``strategy`` instance is consumed by the plan (its mesh becomes
    abstract) — pass a fresh one, not the instance a Trainer will use.
    """
    spec = strategy.build_spec(n_devices).resolve(n_devices)
    mesh = abstract_mesh(spec)
    strategy.spec = spec
    strategy.mesh = mesh
    strategy.bind_module(module)
    module.setup()

    # The planner must never initialize a jax backend — it may be run
    # precisely because the accelerator is unavailable. Two traps:
    #   * a concrete jax.random.key(0) would materialize on the default
    #     device → the rng key is eval_shape'd abstract instead;
    #   * the pallas dispatch decision (ops/dispatch.py on_tpu) queries
    #     jax.default_backend() at TRACE time → pin the XLA reference
    #     path via the context-scoped override (kernel choice cannot
    #     change shapes; a contextvar, unlike an env write, leaves
    #     concurrent traces in other threads untouched).
    from ray_lightning_tpu.ops.dispatch import force_xla

    a_key = jax.eval_shape(lambda: jax.random.key(0))
    with force_xla():
        a_params = jax.eval_shape(
            module.init_params, a_key, _abstract(example_batch)
        )
        p_shardings = strategy.param_shardings(a_params)
        tx = module.configure_optimizers()
        a_opt = jax.eval_shape(tx.init, a_params)
        o_shardings = strategy.opt_state_shardings(a_opt, a_params)

    hbm_bytes_per_device = hbm_bytes_for_kind(
        device_kind, hbm_bytes_per_device)
    params_dev = _sharded_tree_bytes(a_params, p_shardings)
    opt_dev = _sharded_tree_bytes(a_opt, o_shardings)
    return MemoryPlan(
        mesh_axes={k: v for k, v in spec.sizes().items() if v > 1},
        n_devices=n_devices,
        hbm_bytes_per_device=hbm_bytes_per_device,
        params_bytes_global=_tree_bytes(a_params),
        opt_bytes_global=_tree_bytes(a_opt),
        params_bytes_per_device=params_dev,
        opt_bytes_per_device=opt_dev,
        # grads materialize at param sharding/dtype during the step (the
        # donated update overlaps them with params briefly — count them
        # in full; this is the conservative peak)
        grads_bytes_per_device=params_dev,
        activation_bytes_per_device=activation_bytes_per_device,
        reserve_fraction=reserve_fraction,
    )


def llama_activation_bytes(cfg, local_batch: int, seq: int,
                           weight_shard_degree: int = 1) -> int:
    """Activation-footprint bound for the flagship train step —
    remat=True (policy "nothing") + scan_layers + fused CE, the only
    configuration class that holds at 8B (models/llama.py):

      * saved residuals: the per-layer checkpoint stores each block's
        input, L x [B, S, D] bf16 (policy "nothing" saves only inputs);
      * one layer's live recompute set during its backward: the block
        re-runs forward, materializing qkv [B,S,(H+2Hkv)hd], two norms /
        residual adds [B,S,D] each, and the SwiGLU pair [B,S,3F], with
        gradient buffers alongside — 2x (value + cotangent);
      * loss tail: embedding output + final hidden [B,S,D] (bf16 + f32
        copy) and the fused-CE live tile, chunk x V bf16 logits x2
        (recompute + grad);
      * ce_inline_bwd adds its residuals: dx [B·S, D] (hidden dtype) and
        the f32 dW accumulator [D, V] (ops/fused_ce.py _ce_inline) —
        live from the forward scan until the optimizer update. Under
        SPMD the accumulator inherits the lm_head grad's sharding, so
        pass ``weight_shard_degree`` (the fsdp×tensor product) to charge
        the per-device shard instead of the full [D, V] — a ~3 GB
        overcharge at 8B scale would otherwise flip the exact flagship
        FSDP config this path was built for to DOES-NOT-FIT;
      * 1.5x slack for allocator fragmentation and XLA temporaries.

    Deliberately an over-estimate: a plan that passes here compiles with
    room to spare; exactness lives in the params/opt terms.
    """
    bs = local_batch * seq
    hd = cfg.head_dim
    saved = cfg.n_layers * bs * cfg.dim * 2
    if (getattr(cfg, "remat", True)
            and getattr(cfg, "remat_policy", "nothing") == "attn_out"):
        # per-layer saved attention residuals (q, o: H·hd; k, v: Hkv·hd;
        # model dtype — charged at cfg.dtype's width, not a bf16
        # assumption) + the f32 logsumexp — models/llama.py
        # _attn_residuals_saveable. Gated on cfg.remat: with remat=False
        # the model documents the policy as ignored, so charging the
        # residuals would overestimate against the config contract.
        elem = int(np.dtype(cfg.dtype).itemsize) if getattr(
            cfg, "dtype", None) is not None else 2
        saved += cfg.n_layers * bs * (
            (2 * cfg.n_heads + 2 * cfg.n_kv_heads) * hd * elem
            + cfg.n_heads * 4)
    live = bs * (
        2 * cfg.dim
        + (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
        + 3 * cfg.hidden_dim
    ) * 2 * 2
    ce = (cfg.ce_chunk_tokens * cfg.vocab_size * 2 * 2
          + bs * cfg.dim * (2 + 4))
    if getattr(cfg, "ce_inline_bwd", False):
        # + the live-tile delta: the inline body holds the f32 logits AND
        # the bf16 dlogits (6 B/elem, ops/fused_ce.py _ce_inline_fwd)
        # where the remat path's charge above assumed two bf16 tiles
        ce += (cfg.ce_chunk_tokens * cfg.vocab_size * 2
               + bs * cfg.dim * 2
               + cfg.dim * cfg.vocab_size * 4 // max(1, weight_shard_degree))
    return int(1.5 * (saved + live + ce))


def find_max_local_batch(
    module,
    strategy,
    n_devices: int,
    example_batch: Any,
    activation_bytes_fn,
    *,
    device_kind: str = "TPU v5p",
    hbm_bytes_per_device: Optional[int] = None,
    reserve_fraction: float = 0.10,
    ceiling: int = 65536,
) -> tuple[int, MemoryPlan]:
    """Largest per-device batch that fits, found at plan time — the
    TPU-first analog of PTL's ``auto_scale_batch_size`` (which the
    reference inherited from its PTL base): instead of trial-and-error
    OOM probing on live hardware, the weight-side costs are planned once
    (params/opt/grads are batch-independent) and the analytic activation
    bound is binary-searched against the remaining HBM. Zero devices
    touched, zero failed compiles.

    ``activation_bytes_fn(local_batch) -> int`` must be monotone
    non-decreasing (e.g. ``lambda b: llama_activation_bytes(cfg, b, S)``).
    ``example_batch`` sizes only the init trace; its batch dim does not
    constrain the search.

    Returns ``(local_batch, plan)`` where ``plan`` charges the found
    batch's activations; ``(0, plan)`` with the activation-free plan when
    even ``local_batch=1`` does not fit (the caller's model/mesh choice is
    the problem, not the batch). The global batch is
    ``local_batch * dp_degree(spec)``.
    """
    base = plan_train_memory(
        module, strategy, n_devices, example_batch,
        activation_bytes_per_device=0, device_kind=device_kind,
        hbm_bytes_per_device=hbm_bytes_per_device,
        reserve_fraction=reserve_fraction,
    )
    avail = base.headroom_bytes

    def fits(b: int) -> bool:
        return activation_bytes_fn(b) <= avail

    if not fits(1):  # covers avail < 0: no non-negative bound fits
        return 0, base

    # exponential growth to bracket, then bisect. Invariant: fits(lo) is
    # verified; hi is an EXCLUSIVE upper bound (failed, or past ceiling).
    lo, hi = 1, 2
    while hi <= ceiling and fits(hi):
        lo, hi = hi, hi * 2
    hi = min(hi, ceiling + 1)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    best = dataclasses.replace(
        base, activation_bytes_per_device=int(activation_bytes_fn(lo)))
    return lo, best


# ---- multi-slice (DCN) layout queries -------------------------------------
#
# The mesh layer lays devices out slice-major with `data` outermost
# (mesh.order_devices_for_slices): on an S-slice deployment, slice k
# owns the k-th contiguous block of T/S linear device indices of the
# AXIS_ORDER-major mesh array. These helpers answer, from that layout
# contract alone (no devices), which communication groups cross the
# slice boundary — the seam tracecheck's DCN tier (RLT306) and the
# elastic planner both price against.


def group_dcn_span(axes: Sequence[str], mesh_sizes: Mapping[str, int],
                   n_slices: int) -> int:
    """Number of distinct DCN slices a collective group varying exactly
    ``axes`` touches (1 = the group lives inside one slice).

    Computed from the mixed-radix AXIS_ORDER-major layout with
    slice-major device order: enumerate the group's member coordinates
    (axes absent from ``mesh_sizes`` count as size 1) and count the
    distinct ``linear_index // devices_per_slice`` blocks. Exact for the
    base-0 representative group; the layout is regular, so every other
    group of the same axes has the same span."""
    sizes = {ax: int(mesh_sizes.get(ax, 1)) for ax in AXIS_ORDER}
    total = math.prod(sizes.values())
    if n_slices <= 1 or total % n_slices:
        return 1
    per_slice = total // n_slices
    strides: Dict[str, int] = {}
    st = 1
    for ax in reversed(AXIS_ORDER):
        strides[ax] = st
        st *= sizes[ax]
    group_axes = [ax for ax in AXIS_ORDER
                  if ax in tuple(axes) and sizes[ax] > 1]
    members = {0}
    for ax in group_axes:
        members = {
            base + k * strides[ax]
            for base in members for k in range(sizes[ax])
        }
    return len({idx // per_slice for idx in members})


def dcn_crossing_axes(mesh_sizes: Mapping[str, int],
                      n_slices: int) -> Dict[str, int]:
    """Per non-trivial mesh axis: how many slices a group varying only
    that axis spans (entries only for axes that DO cross, span > 1).
    On the canonical layout only `data` (the outermost axis) should
    appear here; any other axis crossing DCN is the performance cliff
    RLT306 flags."""
    out: Dict[str, int] = {}
    for ax in AXIS_ORDER:
        if int(mesh_sizes.get(ax, 1)) <= 1:
            continue
        span = group_dcn_span((ax,), mesh_sizes, n_slices)
        if span > 1:
            out[ax] = span
    return out


def dp_degree(spec: MeshSpec) -> int:
    """Batch divisor of a spec (mirrors mesh_lib.dp_axis_names for
    specs). Requires a RESOLVED spec — a -1 wildcard would silently
    contribute nothing and undercount the degree."""
    sizes = spec.sizes()
    if any(s == -1 for s in sizes.values()):
        raise ValueError(
            f"dp_degree needs a resolved spec (call spec.resolve(n) "
            f"first); got {sizes}"
        )
    return math.prod(sizes[ax] for ax in ("data", "fsdp", "expert"))
