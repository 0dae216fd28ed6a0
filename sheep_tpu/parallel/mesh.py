"""Device mesh construction for the distributed build.

The reference's distribution unit is an MPI rank owning an edge-disjoint
partial graph (graph2tree.cpp:134-157).  Here the unit is a mesh axis
``'workers'``: edge records are sharded along it, the degree histogram is
psum-reduced across it (the MPI_Allreduce of lib/sequence.h:78), and the
partial forests merge with an all_gather + associative rebuild (the
MPI_Reduce custom op of lib/jnode.cpp:203-250).  Collectives ride ICI on a
real slice; multi-host meshes extend over DCN via ``jax.distributed`` with
the same code (XLA inserts the transport).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "workers"


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     connect_timeout_s: float | None = None) -> None:
    """Join a multi-host mesh (the reference's `mpiexec` across nodes).

    Wraps ``jax.distributed.initialize``: with no arguments it relies on the
    cluster environment (TPU pods auto-detect; elsewhere set
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID).  After
    this, ``jax.devices()`` spans every host and :func:`make_mesh` builds a
    global mesh whose collectives ride ICI within a slice and DCN across
    hosts — the same SPMD program, no code changes.

    ``connect_timeout_s`` bounds how long a worker waits for the
    coordinator (default: SHEEP_CONNECT_TIMEOUT env, else 300s — jax's
    own default).  An unreachable coordinator then raises a RuntimeError
    naming the address instead of hanging the process until some outer
    harness (pytest, SLURM) kills it — the failure a misconfigured
    launcher actually produces.
    """
    import os

    if connect_timeout_s is None:
        connect_timeout_s = float(os.environ.get("SHEEP_CONNECT_TIMEOUT",
                                                 "300"))
    if coordinator_address and process_id not in (None, 0):
        # Pre-probe the coordinator from worker processes: some jax
        # releases LOG(FATAL) (SIGABRT, no Python traceback) when the
        # coordination handshake times out, so an unreachable address
        # must be caught BEFORE handing control to the C++ client.
        # Process 0 hosts the service itself and is exempt.
        _probe_coordinator(coordinator_address, connect_timeout_s,
                           process_id, num_processes)
    kw = dict(coordinator_address=coordinator_address,
              num_processes=num_processes, process_id=process_id)
    try:
        jax.distributed.initialize(
            initialization_timeout=int(connect_timeout_s), **kw)
    except Exception as exc:
        addr = coordinator_address or \
            os.environ.get("JAX_COORDINATOR_ADDRESS", "<auto>")
        raise RuntimeError(
            f"could not join the jax.distributed coordinator at {addr} "
            f"(process {process_id}/{num_processes}, waited up to "
            f"{connect_timeout_s:.0f}s): {exc}") from exc


def _probe_coordinator(address: str, timeout_s: float,
                       process_id, num_processes) -> None:
    """Retry a plain TCP connect to ``address`` until it accepts or
    ``timeout_s`` elapses; raise a RuntimeError naming the address on
    failure.  The coordinator may legitimately come up AFTER its workers
    (launchers start all ranks at once), hence the retry loop rather than
    a single attempt."""
    import socket
    import time

    host, _, port_s = address.rpartition(":")
    try:
        port = int(port_s)
    except ValueError:
        raise RuntimeError(
            f"malformed coordinator address {address!r} "
            "(want host:port)") from None
    deadline = time.monotonic() + timeout_s
    last: Exception | None = None
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise RuntimeError(
                f"could not join the jax.distributed coordinator at "
                f"{address} (process {process_id}/{num_processes}, waited "
                f"up to {timeout_s:.0f}s): {last}") from last
        try:
            with socket.create_connection((host or "127.0.0.1", port),
                                          timeout=min(5.0, remaining)):
                return
        except OSError as exc:
            last = exc
            time.sleep(min(0.2, max(0.0, deadline - time.monotonic())))


def make_mesh(num_workers: int | None = None) -> Mesh:
    devices = jax.devices()
    if num_workers is None:
        num_workers = len(devices)
    if num_workers > len(devices):
        raise ValueError(
            f"requested {num_workers} workers but only {len(devices)} devices")
    return Mesh(devices[:num_workers], (AXIS,))


def edge_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
