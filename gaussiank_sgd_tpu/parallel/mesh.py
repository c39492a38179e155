"""Device mesh construction from the TPU slice topology.

Reference parity: rank discovery and process-group setup in the reference come
from MPI/Horovod environment variables (``hvd.init()``, ``MPI.COMM_WORLD`` —
SURVEY.md §2.1, §3.1). TPU-native, the slice topology *is* the communicator:
``jax.devices()`` enumerates every chip in the slice (after
``jax.distributed.initialize()`` on multi-host), and a
``jax.sharding.Mesh`` over them replaces ranks, comms groups, and host files.
XLA lowers collectives over the mesh onto ICI (intra-slice) / DCN
(inter-slice) links — the NCCL/OpenMPI role in the reference (SURVEY.md §5
"Distributed comm backend").

Axis convention:
  * ``dp``  — data parallelism (the reference's only strategy, SURVEY.md §2.2)
  * ``ici_dp`` x ``dcn_dp`` — optional 2D split of dp so the sparse allgather
    rides ICI within a slice with only the cross-slice hop on DCN.
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Env vars whose presence means "this process was launched as part of a
# multi-process job". jax.distributed.initialize() is called only then:
# called unconditionally it probes for a cluster first (on a TPU VM, the
# GCE metadata server — minutes of retries on a machine without network).
_MULTIHOST_ENV_VARS = (
    "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
    "JAX_NUM_PROCESSES", "JAX_PROCESS_ID",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


def _looks_multihost() -> bool:
    if any(os.environ.get(v) for v in _MULTIHOST_ENV_VARS):
        return True
    # TPU slice metadata: multi-host only when several workers are listed
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def maybe_initialize_distributed() -> None:
    """Initialize multi-host JAX iff launched as part of a multi-process
    job; a single-host run (one chip, one four-chip host, the CPU test
    mesh) never calls ``jax.distributed.initialize()``. This replaces the
    reference's ``hvd.init()`` / ``MPI_Init`` (SURVEY.md §3.1 step 1).

    A failure to initialize propagates: a multi-host job falling back to
    per-host independent training is the worst silent failure mode a
    data-parallel framework has.
    """
    if _looks_multihost():
        jax.distributed.initialize()


def data_parallel_mesh(num_devices: Optional[int] = None,
                       devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D data-parallel mesh over all (or the first ``num_devices``) chips.

    The reference's ``-np P`` / ``nworkers`` (SURVEY.md §2 C6) maps to the
    size of this mesh's ``dp`` axis.
    """
    devs = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devs)}")
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), ("dp",))

def hierarchical_dp_mesh(ici_size: int,
                         dcn_size: int,
                         devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D (dcn_dp, ici_dp) mesh for multi-slice data parallelism.

    Keeps the heavy sparse allgather on the fast ICI axis; only the final
    cross-slice reduction crosses DCN — the TPU analogue of the reference's
    hierarchical NCCL-within-node / MPI-across-nodes layout (``nwpernode``,
    SURVEY.md §2 C6).
    """
    devs = list(devices if devices is not None else jax.devices())
    want = ici_size * dcn_size
    if want > len(devs):
        raise ValueError(
            f"requested {ici_size}x{dcn_size}={want} devices, have {len(devs)}")
    devs = devs[:want]
    # On real multi-slice TPU, rows of the mesh MUST be slice-contiguous or
    # the "ici" axis collectives silently cross DCN — use the topology-aware
    # builder, which groups by slice_index and orders within-slice devices
    # along the ICI torus. A naive reshape is only acceptable on the virtual
    # CPU test platform, where there is no topology at all.
    try:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(ici_size,), dcn_mesh_shape=(dcn_size,), devices=devs)
        arr = arr.reshape(dcn_size, ici_size)
    except Exception:
        if devs and devs[0].platform != "cpu":
            raise  # never fall back to a topology-blind layout on hardware
        arr = np.asarray(devs).reshape(dcn_size, ici_size)
    return Mesh(arr, ("dcn_dp", "ici_dp"))


def dp_sp_mesh(dp_size: int, sp_size: int,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D (dp, sp) mesh: data parallelism x ring-attention sequence
    parallelism (parallel/ring_attention.py — long-context path, beyond
    the reference). The sp axis is LAST so the sparse gradient exchange
    (trainstep gather axis) and the K/V ring both ride the fastest links.
    """
    devs = list(devices if devices is not None else jax.devices())
    want = dp_size * sp_size
    if want > len(devs):
        raise ValueError(
            f"requested {dp_size}x{sp_size}={want} devices, have {len(devs)}")
    devs = devs[:want]
    # same topology discipline as hierarchical_dp_mesh: the sp rows must be
    # ICI-neighbor-contiguous or every K/V ring hop silently crosses slow
    # links; never fall back to a blind reshape on real hardware
    try:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_device_mesh((dp_size, sp_size), devices=devs)
    except Exception:
        if devs and devs[0].platform != "cpu":
            raise
        arr = np.asarray(devs).reshape(dp_size, sp_size)
    return Mesh(arr, ("dp", "sp"))


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for model/optimizer state: replicated across dp."""
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh,
                  axes: str | Sequence[str] | None = None) -> NamedSharding:
    """Sharding for a batch: leading dim split across the data-parallel axes.

    Defaults to *all* mesh axes, which is correct for both the 1-D ``('dp',)``
    mesh and the hierarchical ``('dcn_dp', 'ici_dp')`` mesh — every axis of
    both is data parallelism.
    """
    axes = tuple(mesh.axis_names) if axes is None else axes
    return NamedSharding(mesh, P(axes))


def shard_batch(mesh: Mesh, batch: Any, spec: Optional[P] = None) -> Any:
    """Place a host batch onto the mesh; leading dim sharded over dp by
    default, or per ``spec`` (e.g. ``P('dp', 'sp')`` for sequence-parallel
    batches whose dim 1 shards over the sp axis)."""
    sharding = (NamedSharding(mesh, spec) if spec is not None
                else batch_sharded(mesh))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
