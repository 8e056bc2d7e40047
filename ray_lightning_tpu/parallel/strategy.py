"""Distribution strategies: how params/optimizer-state/batches map onto a mesh.

This is the rebuild of the reference's plugin layer (RayPlugin,
ray_lightning/ray_ddp.py:42-307; HorovodRayPlugin, ray_horovod.py:29-196).
The reference had exactly one strategy — allreduce data-parallelism — in two
protocol flavors (torch DDP / Horovod). On TPU the "protocol" dimension
disappears (one collective fabric: XLA over ICI) and the strategy dimension
widens: a strategy here is a *sharding policy* over a `Mesh`; XLA emits the
collectives. No process group object exists and no explicit allreduce is
ever called.

Strategies keep the reference's constructor-object UX
(`Trainer(strategy=DataParallel(num_workers=8))`, mirroring
`Trainer(plugins=[RayPlugin(num_workers=8)])`, ray_ddp.py:89-94) including
`init_hook` (ray_ddp.py:66-67,118-119) and env-var injection
(ray_ddp.py:21-31,158-164).
"""
from __future__ import annotations

import math
import os
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.parallel import mesh as mesh_lib
from ray_lightning_tpu.utils import get_logger
from ray_lightning_tpu.utils.pytree import _path_str, named_leaves as _named_leaves

log = get_logger(__name__)


class Strategy:
    """Base sharding strategy.

    Lifecycle (driven by the Trainer, cf. reference setup/start_training/
    post_dispatch at ray_ddp.py:113,143,201):
        setup(module)        — build the mesh, run init_hook, inject env vars
        shard_params(params) — place the param pytree with this policy
        shard_batch(batch)   — place a host batch as a global device array
        teardown()           — release mesh-related state
    """

    #: mesh axes this strategy uses; subclasses override.
    spec: mesh_lib.MeshSpec

    def __init__(
        self,
        num_workers: Optional[int] = None,
        init_hook: Optional[Callable[[], None]] = None,
        env: Optional[dict[str, str]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        self.num_workers = num_workers
        self.init_hook = init_hook
        self.env = dict(env or {})
        self._devices = list(devices) if devices is not None else None
        self.mesh: Optional[Mesh] = None
        self._module = None

    # ---- lifecycle -------------------------------------------------------

    def _select_devices(self) -> list[jax.Device]:
        devices = self._devices if self._devices is not None else jax.devices()
        if self.num_workers is not None:
            if self.num_workers > len(devices):
                raise ValueError(
                    f"num_workers={self.num_workers} exceeds available "
                    f"devices ({len(devices)})"
                )
            devices = devices[: self.num_workers]
        return list(devices)

    def build_spec(self, n_devices: int) -> mesh_lib.MeshSpec:
        raise NotImplementedError

    def setup(self, module=None) -> Mesh:
        if self.env:
            os.environ.update(self.env)
        if self.init_hook is not None:
            self.init_hook()
        devices = self._select_devices()
        self.spec = self.build_spec(len(devices))
        self.mesh = self.spec.build(devices)
        self._module = module
        if module is not None:
            # bind before the module builds its model so seq/tensor manual
            # islands (e.g. ring attention) can close over the mesh.
            module.mesh = self.mesh
        log.info(
            "strategy=%s mesh=%s over %d %s device(s)",
            type(self).__name__,
            dict(self.mesh.shape),
            len(devices),
            devices[0].platform,
        )
        return self.mesh

    def bind_module(self, module) -> None:
        """Point an already-built mesh at a (new) module: its param_specs
        drive sharding and it sees the mesh before building its model."""
        self._module = module
        if module is not None:
            module.mesh = self.mesh

    def teardown(self) -> None:
        self.mesh = None
        self._module = None

    # ---- sharding policy -------------------------------------------------

    def param_spec(self, path: str, leaf) -> P:
        """PartitionSpec for one parameter leaf. Default: replicate."""
        return P()

    def param_shardings(self, params) -> Any:
        assert self.mesh is not None, "call setup() first"
        module_specs = {}
        if self._module is not None and hasattr(self._module, "param_specs"):
            module_specs = self._module.param_specs(params) or {}

        def one(path, leaf):
            spec = module_specs.get(path)
            if spec is None:
                spec = self.param_spec(path, leaf)
            else:
                # BEFORE adaptation: _adapt_spec silently drops axes the
                # mesh doesn't know, so a typo'd axis name would quietly
                # replicate the leaf — the OOM-at-scale failure the
                # shardcheck subsystem exists to catch (RLT101)
                self._require_known_axes(path, spec)
            spec = self._adapt_spec(spec, getattr(leaf, "shape", ()))
            self._require_well_formed(path, spec,
                                      getattr(leaf, "shape", ()))
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(
            lambda kp, leaf: one(_path_str(kp), leaf), params
        )

    def _require_known_axes(self, path: str, spec: P) -> None:
        """Raise when a module-provided spec names an axis the mesh does
        not have at all (distinct from a size-1 axis, which is legal and
        dropped by _adapt_spec)."""
        known = set(self.mesh.shape)
        unknown = sorted(_spec_names(spec) - known)
        if unknown:
            raise ValueError(
                f"param_specs for {path!r} names unknown mesh "
                f"axis(es) {unknown} (mesh axes: {sorted(known)}) — a "
                "typo here would silently replicate the leaf "
                "[shardcheck RLT101]"
            )

    def _require_well_formed(self, path: str, spec: P, shape) -> None:
        """Eager structural validation of the COMPOSED spec (shardcheck
        RLT102/103/104): fail at setup with the leaf's name instead of
        at compile time with an XLA sharding error."""
        from ray_lightning_tpu.analysis.plan_checker import spec_findings

        errors = [f for f in spec_findings(
            spec, shape, dict(self.mesh.shape), path=path)
            if f.severity == "error"]
        if errors:
            raise ValueError(
                "sharding plan is malformed:\n"
                + "\n".join(f.format() for f in errors)
            )

    def _adapt_spec(self, spec: P, shape) -> P:
        """Drop mesh axes the strategy's mesh doesn't materialize (size 1)."""
        assert self.mesh is not None
        out = []
        for dim in spec:
            if dim is None:
                out.append(None)
                continue
            names = dim if isinstance(dim, tuple) else (dim,)
            kept = tuple(n for n in names if self.mesh.shape.get(n, 1) > 1)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def opt_state_shardings(self, abstract_opt, params) -> Any:
        """Shardings for the optimizer state: param-shaped leaves (adam
        mu/nu, momentum, …) inherit their param's sharding — ZeRO
        semantics; scalars/schedules replicate.

        Without this, `jit(tx.init)` leaves the whole opt state on one
        device (the init is shape-only, so XLA drops the input dependency
        and with it the sharding propagation).

        Opt-state pytrees embed param subtrees (optax builds them with
        `tree_map(zeros_like, params)`), so each opt leaf is matched to
        the param whose full path is the longest suffix of the opt leaf's
        path and whose shape agrees.
        """
        assert self.mesh is not None, "call setup() first"
        param_shardings = self.param_shardings(params)
        by_path = {}
        for (path, leaf), sharding in zip(
            _named_leaves(params), jax.tree.leaves(param_shardings)
        ):
            by_path[path] = (getattr(leaf, "shape", ()), sharding)
        replicated = self.replicated()

        def one(path: str, leaf):
            parts = path.split("/")
            for i in range(len(parts)):
                cand = "/".join(parts[i:])
                hit = by_path.get(cand)
                if hit and hit[0] == getattr(leaf, "shape", ()):
                    return hit[1]
            return replicated

        return jax.tree_util.tree_map_with_path(
            lambda kp, leaf: one(_path_str(kp), leaf), abstract_opt
        )

    def batch_spec(self) -> P:
        assert self.mesh is not None
        return P(mesh_lib.dp_axis_names(self.mesh))

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # ---- auditing --------------------------------------------------------

    def audit_step(self, module, example_batch, *, topology="v5p-8",
                   n_devices: Optional[int] = None,
                   reserve_fraction: float = 0.10, label: str = ""):
        """tracecheck this strategy's REAL jitted train step for
        ``module`` on ``topology`` (a name like ``"v5p-64"`` or an
        `analysis.costmodel.Topology`) — zero hardware, CPU-host safe.

        Returns an `analysis.tracecheck.TraceReport`: the collective
        schedule with ICI bytes/latency estimates, implicit-resharding
        findings (RLT301), ring/pipeline schedule checks (RLT303), and
        the peak-HBM estimate vs the chip budget (RLT302). Like
        `plan_train_memory`/`check_plan`, the strategy instance is
        CONSUMED (its mesh becomes abstract) — pass a fresh one, not
        the instance a live Trainer holds."""
        from ray_lightning_tpu.analysis.tracecheck import audit_step

        return audit_step(
            module, self, example_batch, topology=topology,
            n_devices=n_devices, reserve_fraction=reserve_fraction,
            label=label or f"{type(module).__name__} x "
                           f"{type(self).__name__}")

    # ---- trainguard: SDC fingerprint probe -------------------------------

    def sdc_probe(self, params):
        """Build the trainguard silent-data-corruption probe for this
        strategy's mesh (resilience/guard.py): a jitted ``shard_map`` in
        which every device digests its OWN local parameter bytes
        (bitcast-uint32 wraparound sum), gathered to one fingerprint per
        device with a single small collective.

        Returns ``(fn, devices, groups)``: ``fn(params) -> (n_devices,)``
        uint32 fingerprints in ``mesh.devices.reshape(-1)`` order,
        ``groups`` the replica groups whose members hold bit-identical
        bytes by this strategy's sharding policy (pure DP: all devices;
        pure FSDP: none — no redundancy to cross-check). Usable directly
        for an ad-hoc fleet screen: run it twice around a suspect step
        and diff."""
        from ray_lightning_tpu.resilience.guard import build_sdc_probe

        assert self.mesh is not None, "call setup() first"
        return build_sdc_probe(params, self.mesh)

    # ---- placement -------------------------------------------------------

    def shard_params(self, params) -> Any:
        return jax.device_put(params, self.param_shardings(params))

    def shard_batch(self, batch) -> Any:
        """Place a host batch (pytree of numpy arrays) as global jax.Arrays.

        Single-process: a plain device_put against the batch sharding.
        Multi-process: each host holds its local shard of the global batch
        (the DistributedSampler analog; reference forces a sampler with
        num_replicas=num_workers, rank=global_rank at ray_ddp.py:293-303)
        and we assemble a global array from per-process shards.
        """
        sharding = self.batch_sharding()
        divisor = mesh_lib.batch_size_divisor(self.mesh)

        def place(x):
            x = np.asarray(x)
            if x.shape and x.shape[0] % divisor != 0:
                raise ValueError(
                    f"Global batch dim {x.shape[0]} not divisible by "
                    f"data-parallel degree {divisor} (mesh {dict(self.mesh.shape)})"
                )
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree.map(place, batch)

    # ---- introspection ---------------------------------------------------

    @property
    def world_size(self) -> int:
        return math.prod(self.mesh.shape.values()) if self.mesh else 1

    @property
    def dp_size(self) -> int:
        return mesh_lib.batch_size_divisor(self.mesh) if self.mesh else 1


class DataParallel(Strategy):
    """Pure data parallelism: params replicated, batch sharded on `data`.

    Parity target: `RayPlugin` (reference ray_ddp.py:42-307). The gradient
    all-reduce the reference got from NCCL/Gloo buckets is compiled by XLA
    from the sharding annotations (psum over the `data` axis) and rides ICI.
    """

    def build_spec(self, n_devices: int) -> mesh_lib.MeshSpec:
        return mesh_lib.MeshSpec(data=n_devices)


class FSDP(Strategy):
    """ZeRO-style fully-sharded data parallelism as sharding annotations.

    Params and optimizer state are sharded along the `fsdp` mesh axis (each
    leaf on its largest divisible dimension); activations stay data-parallel.
    XLA inserts the all-gather (forward/backward) and reduce-scatter (grad)
    that FSDP implementations hand-schedule. "Activations stay
    data-parallel" is the MODEL's to state, not this overlay's: the
    annotations here name weights only, and where a chip's rows x sequence
    exceed the widths GSPMD reshards the activations instead of gathering
    the weights unless the block pins them to the batch axes
    (`models/llama.py:_activation_pin`; docs/PERFORMANCE.md "Collective
    overlap" has the rule and how to list a step's collectives from the
    compiled HLO without a chip). Stands in the "second protocol"
    slot Horovod occupied in the reference (ray_horovod.py:29-196) and is
    the BASELINE.json Llama-8B strategy.
    """

    def __init__(self, *args, min_shard_size: int = 2**10, **kwargs):
        super().__init__(*args, **kwargs)
        self.min_shard_size = min_shard_size

    def build_spec(self, n_devices: int) -> mesh_lib.MeshSpec:
        return mesh_lib.MeshSpec(fsdp=n_devices)

    def param_spec(self, path: str, leaf) -> P:
        return fsdp_auto_spec(
            getattr(leaf, "shape", ()),
            self.mesh.shape.get("fsdp", 1),
            self.min_shard_size,
        )

    def _adapt_spec(self, spec: P, shape) -> P:
        return _fsdp_adapt_spec(self, spec, shape)


class ShardedMesh(Strategy):
    """Explicit N-D mesh strategy composing dp × fsdp × tensor × seq
    (× expert × pipe).

    The general form: `ShardedMesh(data=2, fsdp=2, tensor=2)`. Tensor-axis
    placement comes from the module's `param_specs` hook (Megatron-style
    column/row splits are module knowledge); fsdp placement is automatic;
    `pipe` feeds the GPipe building block (ops/pipeline.py).
    """

    def __init__(
        self,
        data: int = 1,
        fsdp: int = 1,
        expert: int = 1,
        seq: int = 1,
        tensor: int = 1,
        pipe: int = 1,
        min_shard_size: int = 2**10,
        **kwargs,
    ):
        super().__init__(**kwargs)
        self._spec = mesh_lib.MeshSpec(data, fsdp, expert, seq, tensor, pipe)
        self.min_shard_size = min_shard_size

    def build_spec(self, n_devices: int) -> mesh_lib.MeshSpec:
        return self._spec.resolve(n_devices)

    def param_spec(self, path: str, leaf) -> P:
        return fsdp_auto_spec(
            getattr(leaf, "shape", ()),
            self.mesh.shape.get("fsdp", 1),
            self.min_shard_size,
        )

    def _adapt_spec(self, spec: P, shape) -> P:
        return _fsdp_adapt_spec(self, spec, shape)


class SingleDevice(Strategy):
    """Trivial strategy: one device, no sharding (debug / laptop path)."""

    def __init__(self, **kwargs):
        kwargs.setdefault("num_workers", 1)
        super().__init__(**kwargs)

    def build_spec(self, n_devices: int) -> mesh_lib.MeshSpec:
        return mesh_lib.MeshSpec()


# Reference-familiar alias: `RayPlugin` → the TPU DP strategy; the north
# star names it RayXlaPlugin (BASELINE.json).
class RayXlaPlugin(DataParallel):
    """Drop-in ctor shape of the reference's RayPlugin (ray_ddp.py:89-94).

    ``num_cpus_per_worker`` is honored as the per-worker host-CPU budget:
    it is exported through the strategy's env injection and sizes the data
    pipeline's prefetch thread pool (core/data.py); pair it with
    ``TpuResources(cpus=...)`` for sweep-level packing. ``use_gpu`` has no
    TPU meaning and warns when set (the device set IS the TPU slice).
    """

    def __init__(self, num_workers: Optional[int] = None,
                 num_cpus_per_worker: Optional[int] = None,
                 use_gpu: bool = False, init_hook=None, **kwargs):
        if use_gpu:
            log.warning("RayXlaPlugin(use_gpu=True) ignored: this is the "
                        "TPU backend; devices come from the slice topology")
        env = dict(kwargs.pop("env", None) or {})
        if num_cpus_per_worker is not None:
            # only an EXPLICIT budget is exported — a default injection
            # would leak into os.environ and retune every DataLoader in
            # the process, not just this strategy's
            env.setdefault("RLT_NUM_CPUS_PER_WORKER",
                           str(max(1, num_cpus_per_worker)))
        self.num_cpus_per_worker = max(1, num_cpus_per_worker or 1)
        super().__init__(num_workers=num_workers, init_hook=init_hook,
                         env=env, **kwargs)


# ---- spec helpers --------------------------------------------------------


def _fsdp_adapt_spec(strategy: Strategy, spec: P, shape) -> P:
    """Shared FSDP/ShardedMesh adapt: drop trivial axes, then overlay
    `fsdp` on a free divisible dim of module-provided tensor specs."""
    spec = Strategy._adapt_spec(strategy, spec, shape)
    if (strategy.mesh.shape.get("fsdp", 1) > 1
            and "fsdp" not in _spec_names(spec)):
        spec = _augment_with_axis(
            spec, shape, "fsdp", strategy.mesh.shape["fsdp"],
            strategy.min_shard_size,
        )
    return spec


def _spec_names(spec: P) -> set:
    names = set()
    for dim in spec:
        if dim is None:
            continue
        for n in dim if isinstance(dim, tuple) else (dim,):
            names.add(n)
    return names


def _augment_with_axis(
    spec: P, shape, axis_name: str, axis_size: int, min_size: int
) -> P:
    """Add `axis_name` to the largest free, divisible dim of `spec`."""
    if not shape or int(np.prod(shape)) < min_size:
        return spec
    dims = list(spec) + [None] * (len(shape) - len(spec))
    candidates = sorted(
        range(len(shape)), key=lambda i: shape[i], reverse=True
    )
    for i in candidates:
        if dims[i] is None and shape[i] % axis_size == 0:
            dims[i] = axis_name
            return P(*dims)
    return spec


def fsdp_auto_spec(shape, fsdp_size: int, min_size: int) -> P:
    """Shard the largest divisible dim on `fsdp`; replicate small leaves."""
    if fsdp_size <= 1:
        return P()
    return _augment_with_axis(P(*([None] * len(shape))), shape, "fsdp",
                              fsdp_size, min_size)


