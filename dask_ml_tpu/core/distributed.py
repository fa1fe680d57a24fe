"""Multi-host execution plane: process-group bootstrap + global meshes.

The reference scales past one machine through ``distributed.Client`` — a
scheduler process, worker processes over TCP, and task graphs shipped
between them (SURVEY.md §2.3).  The TPU-native control plane is radically
smaller: ``jax.distributed.initialize`` forms the process group (one
process per host / TPU slice), every process runs the SAME program
(multi-controller SPMD), and the data plane is XLA collectives — ICI
within a slice, DCN between slices — inserted by the compiler from
sharding annotations.  There is no scheduler to build: placement is the
mesh.

Two mesh shapes are offered:

* :func:`global_mesh` (default) — the existing ``('data', 'model')`` axes
  spanning ALL global devices, host-major, so every single-host SPMD
  program in this framework (solvers, Lloyd, packed search) runs unchanged
  on a pod or multi-slice fleet; the segment of each ``psum`` that crosses
  hosts rides DCN automatically.
* :func:`global_mesh(hierarchical=True)` — an explicit outer ``'dcn'``
  axis (slices/hosts) × inner ``('data', 'model')``, for algorithms that
  want different strategies per level (slice-local reduce then cross-slice
  combine, the scaling-book recipe).

Data ingest across hosts uses :func:`shard_rows_global`: every process
contributes its LOCAL row block and the result is one global
``ShardedRows`` whose row axis is sharded over all hosts' devices — the
analogue of ``client.scatter`` without a scheduler hop.

CPU processes (tests, the driver's multi-host dryrun) get cross-process
collectives via jaxlib's Gloo transport, the direct analogue of the
reference's ``distributed.utils_test.gen_cluster`` fake-cluster harness:
a REAL protocol stack over localhost.

Multi-controller ordering contract: every process must issue the SAME
device computations in the SAME order, or collectives deadlock.  The
packed adaptive search satisfies this (one lockstep cohort per round —
see ``model_selection/_incremental.py :: train_cohort``), and is the
supported cross-host search plane.  ``HyperbandSearchCV``'s concurrent
brackets interleave dispatches nondeterministically across threads and
must therefore stay on a single controller — pass
``HyperbandSearchCV(..., sequential_brackets=True)`` to run one lockstep
bracket at a time, the multi-controller-legal form (exercised
cross-process in ``core/_multihost_worker.py``).
"""

from __future__ import annotations

import os

import numpy as np

import jax

from .mesh import DATA_AXIS, MODEL_AXIS, Mesh

DCN_AXIS = "dcn"

__all__ = [
    "DCN_AXIS",
    "initialize",
    "is_initialized",
    "process_count",
    "process_index",
    "global_mesh",
    "shard_rows_global",
]


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_count: int | None = None) -> None:
    """Join (or form) the multi-host process group.

    On TPU pods the arguments are discovered from the environment
    (``jax.distributed.initialize()`` with no args); on CPU the Gloo
    collectives transport is selected so cross-process psums work — the
    test-harness path mirroring the reference's ``gen_cluster``.
    """
    if is_initialized():
        return
    backend_is_cpu = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    if backend_is_cpu:
        jax.config.update("jax_platforms", "cpu")
        if local_device_count:
            # raises once the CPU backend exists (initialize() must run
            # before any device use): joining the group with a
            # silently-wrong device count would desync the fleet's mesh
            # and hang its first collective
            jax.config.update("jax_num_cpu_devices", int(local_device_count))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_initialized() -> bool:
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None
    except Exception:  # pragma: no cover
        return False


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def _host_major_devices():
    return sorted(jax.devices(), key=lambda d: (d.process_index, d.id))


def global_mesh(model_axis: int = 1, *, hierarchical: bool = False) -> Mesh:
    """A mesh over ALL global devices (every process of the group).

    ``hierarchical=False``: axes ``('data', 'model')`` — drop-in for
    ``core.mesh.set_mesh`` so every existing SPMD program spans the fleet.
    ``hierarchical=True``: axes ``('dcn', 'data', 'model')`` with the
    process/slice boundary explicit on the outer axis.
    """
    devices = _host_major_devices()
    n = len(devices)
    if n % model_axis:
        raise ValueError(f"{n} devices not divisible by model_axis={model_axis}")
    if not hierarchical:
        grid = np.array(devices).reshape(n // model_axis, model_axis)
        return Mesh(grid, (DATA_AXIS, MODEL_AXIS))
    nproc = jax.process_count()
    per = n // nproc
    if per % model_axis:
        raise ValueError(
            f"{per} per-process devices not divisible by model_axis={model_axis}"
        )
    grid = np.array(devices).reshape(nproc, per // model_axis, model_axis)
    return Mesh(grid, (DCN_AXIS, DATA_AXIS, MODEL_AXIS))


def row_spec(mesh: Mesh, ndim: int):
    """PartitionSpec sharding rows over every data-carrying mesh axis."""
    from jax.sharding import PartitionSpec as P

    axes = (
        (DCN_AXIS, DATA_AXIS) if DCN_AXIS in mesh.axis_names else DATA_AXIS
    )
    return P(axes, *([None] * (ndim - 1)))


def shard_rows_global(local_rows, mesh: Mesh | None = None, *, dtype=None):
    """Every process contributes its local row block; returns one global
    ``ShardedRows`` row-sharded over the whole fleet.

    The scatter analogue (`client.scatter` in the reference) — except no
    bytes move through a scheduler: each host places its own rows on its
    own devices and the array is only *logically* global.

    Local blocks are padded to the per-process shard multiple; the global
    ``n_samples`` is the collective sum of real rows (computed with one
    tiny psum on the mask).  Every process must contribute the same padded
    row count (pad ragged per-host blocks yourself — the mask keeps the
    math exact); feature dimensions must agree everywhere.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import get_mesh
    from .sharded import ShardedRows, pad_rows

    mesh = mesh or get_mesh()
    x = np.asarray(local_rows)
    if dtype is not None:
        x = x.astype(dtype)
    # rows per process must fill this process's addressable shards equally
    row_axes = (
        mesh.shape[DCN_AXIS] * mesh.shape[DATA_AXIS]
        if DCN_AXIS in mesh.axis_names
        else mesh.shape[DATA_AXIS]
    )
    nproc = jax.process_count()
    if row_axes < nproc or row_axes % nproc:
        raise ValueError(
            f"mesh row axes span {row_axes} shards, which cannot be split "
            f"evenly over {nproc} processes — give every process at least "
            "one data shard (reduce model_axis or use more data devices)"
        )
    local_shards = row_axes // nproc
    padded, n_local = pad_rows(x, local_shards)
    mask_local = np.zeros(padded.shape[0], dtype=np.float32)
    mask_local[:n_local] = 1.0

    spec = row_spec(mesh, padded.ndim)
    sharding = NamedSharding(mesh, spec)
    global_rows = padded.shape[0] * jax.process_count()
    data = jax.make_array_from_process_local_data(
        sharding, padded, global_shape=(global_rows,) + padded.shape[1:]
    )
    mask = jax.make_array_from_process_local_data(
        NamedSharding(mesh, row_spec(mesh, 1)), mask_local,
        global_shape=(global_rows,),
    )
    # global real-row count: one scalar collective (every process computes
    # the same value from the same global mask)
    n_global = int(jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(mask))
    return ShardedRows(data=data, mask=mask, n_samples=n_global)
