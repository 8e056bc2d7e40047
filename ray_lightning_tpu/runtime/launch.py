"""Single-controller launch of a multi-controller SPMD program.

SURVEY §7.4 hard part #1: Ray is single-driver/many-actors, while JAX on a
pod is one process per host all executing the same program. This module
reconciles the two: the driver (your single script, C1 of SURVEY §7.1)
ships ONE closure to H host-processes; each process initializes
``jax.distributed`` against a coordinator the driver picked (the analog of
the reference's MASTER_ADDR/PORT dance, ray_ddp.py:152-156 — but the
coordination service is JAX's, not a torch TCPStore), joins the global
device mesh, and jointly executes the SPMD program. The driver keeps the
Ray-like futures/queue view via WorkerGroup.

On a real TPU pod the same closure runs with per-host launch handled by
the pod runtime (one of these processes per host; ``coordinator_address``
a pod-internal IP); on a dev box / CI, ``platform="cpu"`` +
``num_cpu_devices_per_process`` gives REAL multi-process collectives over
gloo — the test story of SURVEY §7.1 C8.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence

from ray_lightning_tpu.runtime.group import WorkerGroup, find_free_port
from ray_lightning_tpu.runtime.transport import Transport
from ray_lightning_tpu.utils import get_logger

log = get_logger(__name__)


def _probe_coordinator_port():
    """Runs ON worker 0: find a port free on all interfaces of ITS host.

    Stdlib-only by design: cloudpickle pickles module-level functions by
    REFERENCE, so the remote side imports this module to resolve it —
    fine (the package is required on workers anyway, since user closures
    import it too), but the body must not assume anything about the
    worker's jax state. Reference analog: find_free_port executed on
    worker 0 for MASTER_PORT (ray_ddp.py:154-156).
    """
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _await_coordinator(coordinator: str, rank: int) -> None:
    """Bounded preflight from a non-zero rank: the jax coordinator (on
    worker 0) must become dialable within the window, else fail with the
    fix by name — a wrong coordinator address otherwise surfaces as a
    multi-minute opaque barrier hang inside jax.distributed.initialize
    (VERDICT r3 weak #4 / next #7).

    The window defaults to 60s and is raised via RLT_COORD_PREFLIGHT_S
    (a slow-but-healthy rank 0 — cold NFS jax import, fat job blob —
    must not be misdiagnosed as unroutable); <= 0 skips the preflight.
    """
    import os
    import socket
    import time

    try:
        timeout = float(os.environ.get("RLT_COORD_PREFLIGHT_S", "60"))
    except ValueError:
        timeout = 60.0
    if timeout <= 0:
        return
    host, port = coordinator.rsplit(":", 1)
    deadline = time.monotonic() + timeout
    last_err: Exception | None = None
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, int(port)), timeout=5):
                return
        except OSError as exc:  # not up yet, or unroutable
            last_err = exc
            time.sleep(0.5)
    raise RuntimeError(
        f"rank {rank}: jax coordinator {coordinator} was unreachable for "
        f"{timeout:.0f}s ({last_err}). In a multi-host job this address "
        "must be a fabric-routable IP of worker 0 — set RLT_NODE_IP in "
        "worker 0's environment (transport host_env) to pin the right "
        "interface, or pass coordinator_address= to launch(). If worker 0 "
        "is just slow to start (cold imports), raise RLT_COORD_PREFLIGHT_S."
    )


def _spmd_main(
    fn: Callable,
    args: tuple,
    kwargs: dict,
    num_processes: int,
    coordinator: str,
    platform: Optional[str],
    num_cpu_devices: Optional[int],
    rank: int,
    rank_args: tuple = (),
):
    """Body shipped to every worker — shared prefix (fat: the user job)
    first, per-rank suffix last, matching WorkerGroup.run's ship-once
    split. Order matters: jax config BEFORE any backend initialization,
    distributed init BEFORE user code touches devices."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if num_cpu_devices:
        jax.config.update("jax_num_cpu_devices", num_cpu_devices)
        # Cross-process CPU collectives ride gloo (the CI fabric; on TPU
        # the fabric is ICI and this knob is untouched). Only with > 1
        # process: gloo requires the distributed client, which a
        # single-process job never initializes — setting it there kills
        # backend creation with an opaque "distributed_client: NoneType".
        if num_processes > 1:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if num_processes > 1:
        if rank != 0:
            _await_coordinator(coordinator, rank)
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=rank,
        )
    result = fn(*args, *rank_args, **kwargs)
    # Success path ONLY: on an exception the peers may be mid-collective,
    # and tearing the coordination service out from under them turns one
    # rank's Python exception into cluster-wide gloo aborts (observed:
    # EnforceNotMet 'op.preamble.length 16 vs 4' -> SIGABRT on the
    # healthy rank) while THIS rank blocks in the shutdown barrier —
    # delaying the very error message the driver's fail-fast
    # classification needs. The failed group is torn down by the driver
    # (group.shutdown kills after the grace window), which is the
    # correct owner of cleanup on the error path.
    if num_processes > 1:
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
    return result


def launch(
    fn: Callable,
    num_processes: int,
    *,
    args: tuple = (),
    kwargs: Optional[dict] = None,
    platform: Optional[str] = None,
    num_cpu_devices_per_process: Optional[int] = None,
    env: Optional[Dict[str, str]] = None,
    init_hook: Optional[Callable[[], None]] = None,
    on_queue_item: Optional[Callable[[int, Any], None]] = None,
    per_rank_args: Optional[Sequence[tuple]] = None,
    log_dir: Optional[str] = None,
    timeout: Optional[float] = None,
    hosts: Optional[Sequence[str]] = None,
    transport: Optional[Transport] = None,
    coordinator_address: Optional[str] = None,
    watchdog: Optional[Callable[[], None]] = None,
) -> List[Any]:
    """Run ``fn`` on ``num_processes`` host-processes as one SPMD job.

    Returns the per-rank results in rank order (reference analog: the
    fan-out + process_results + unpack sequence, ray_ddp.py:178-193 — but
    every rank's return value is kept; rank 0's is the conventional
    carrier of results).

    ``fn`` runs AFTER jax.distributed.initialize, so inside it
    ``jax.devices()`` is the global device set and a ``Mesh`` built over it
    spans all processes.

    ``hosts`` + a remote ``transport`` (e.g. SSHTransport) place one
    process per cluster host — the cross-host path. The jax coordinator
    then binds on WORKER 0's host at its routable IP (the reference's
    MASTER_ADDR ← worker0 IP, MASTER_PORT ← free port dance,
    ray_ddp.py:152-156); locally it stays on loopback. Override with an
    explicit ``coordinator_address`` when pod metadata supplies one.
    """
    group = WorkerGroup(
        num_workers=num_processes,
        env=env,
        init_hook=init_hook,
        log_dir=log_dir,
        hosts=hosts,
        transport=transport,
    )
    group.start()
    try:
        if coordinator_address is not None:
            coordinator = coordinator_address
        elif group.is_remote and num_processes > 1:
            # rank 0 hosts the coordination service: its routable IP (from
            # the hello) + a port probed free on its own interfaces.
            host0 = group.executors[0].get_node_ip()
            port0 = group.run_single(0, _probe_coordinator_port, timeout=60)
            coordinator = f"{host0}:{port0}"
            log.info("jax coordinator at %s (worker 0)", coordinator)
        else:
            coordinator = f"127.0.0.1:{find_free_port()}"
        # Ship-once split (reference ray.put fan-out, ray_ddp.py:168-171):
        # the fat user job (fn + its args, typically module/data factories
        # with captured datasets) serializes ONCE in WorkerGroup.run; only
        # the rank id + per-rank extras are serialized per worker.
        shared = (fn, tuple(args), dict(kwargs or {}), num_processes,
                  coordinator, platform, num_cpu_devices_per_process)
        rank_extras = [
            (r, tuple(per_rank_args[r]) if per_rank_args else ())
            for r in range(num_processes)
        ]
        return group.run(
            _spmd_main,
            shared_args=shared,
            per_rank_args=rank_extras,
            on_queue_item=on_queue_item,
            timeout=timeout,
            watchdog=watchdog,
        )
    finally:
        group.shutdown()


def launch_cpu_spmd(
    fn: Callable,
    num_processes: int = 2,
    devices_per_process: int = 2,
    **kw,
) -> List[Any]:
    """CI/dev-box convenience: a real multi-process gloo-backed mesh with
    ``num_processes * devices_per_process`` CPU devices — the TPU-rebuild
    analog of the reference's throwaway local Ray clusters
    (``ray.init(num_cpus=2)``, reference tests/test_ddp.py:16-21)."""
    return launch(
        fn,
        num_processes,
        platform="cpu",
        num_cpu_devices_per_process=devices_per_process,
        **kw,
    )
