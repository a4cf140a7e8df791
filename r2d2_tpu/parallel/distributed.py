"""Multi-host distributed runtime (SURVEY.md §5.8).

The reference's "distributed backend" is single-host
``torch.multiprocessing`` queues + shared memory (train.py:23-26); it has no
multi-node story at all.  The TPU-native equivalent splits cleanly:

- **Within the learner step**: nothing here — gradient/metric collectives
  are GSPMD-inserted ``psum``s over the mesh (parallel/mesh.py) and ride
  ICI within a slice and DCN across slices automatically.
- **Process bring-up**: :func:`init_distributed` wraps
  ``jax.distributed.initialize`` so N host processes (one per TPU host)
  form a single JAX runtime whose ``jax.devices()`` is the global device
  set.  After it returns, ``make_mesh`` over ``jax.devices()`` is a global
  mesh and the table-driven ``parallel/sharding.pjit_train_step``
  compiles unchanged.
- **Host-side data plane**: replay stays host-local (each host's actor
  fleet feeds its own buffer — the analogue of the reference's per-actor
  queues staying on one box).  ``cfg.batch_size`` remains the **global**
  batch: each host samples only :func:`host_batch_size` rows (its share of
  the dp axis) and :func:`host_local_batch` assembles them into one
  globally sharded device batch via
  ``jax.make_array_from_process_local_data`` — no batch data ever crosses
  DCN.  The step's dp-sharded priority output comes back through
  :func:`local_rows`, which reads only this host's addressable shards, so
  each host's priority feedback aligns with the indexes it sampled.

Single-process (tests, the one-chip benchmark cells) is the degenerate case:
every helper reduces to the identity / a sharded ``device_put``, which is how
the whole path is unit tested on the 8-device CPU mesh — the single-process
code path IS the multi-host code path.

Topology assumption (asserted): each host's devices cover whole dp groups,
contiguously — true for standard pod slices where the mesh is built from
``jax.devices()`` in order (make_mesh).
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from r2d2_tpu.config import Config
from r2d2_tpu.parallel.sharding import DEVICE_BATCH_KEYS, ShardingTable


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto: bool = False) -> Dict[str, int]:
    """Join (or create) the multi-host JAX runtime.

    Must run before any other JAX call in the process (XLA backend
    initialisation pins the runtime) — the CLI's ``--distributed`` flag
    calls it first thing.  Arguments default to the standard env vars
    (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``,
    ``JAX_PROCESS_ID``).  With ``auto=True`` (the CLI's behaviour) and no
    coordinator configured, ``jax.distributed.initialize()`` is called
    bare so TPU pods autodetect all three from the metadata server — an
    explicit distributed request never silently degrades to N independent
    single-host runs.  With ``auto=False`` (library default) and no
    coordinator, it is a no-op so single-process use needs no guards.

    Returns ``{"process_id": ..., "process_count": ...}``.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])

    # NOTE: nothing before initialize() may touch the backend
    # (jax.devices(), jax.process_count(), ...) or it would raise
    if not jax.distributed.is_initialized():
        if coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        elif auto:
            try:
                jax.distributed.initialize()  # TPU-pod autodetection
            except Exception as e:
                raise RuntimeError(
                    "distributed bring-up requested but no coordinator is "
                    "configured and autodetection failed; set "
                    "JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / "
                    "JAX_PROCESS_ID") from e
    return dict(process_id=jax.process_index(),
                process_count=jax.process_count())


def owned_dp_groups(mesh: Mesh) -> slice:
    """The contiguous range of dp groups whose devices this process owns.

    Raises (real errors, not asserts — this alignment is load-bearing for
    priority/index pairing and must survive ``python -O``) when a dp group
    is split across processes or this process's groups are
    non-contiguous: the topology assumption from the module docstring.
    """
    axis = mesh.axis_names.index("dp")
    dp = mesh.shape["dp"]
    groups = np.moveaxis(mesh.devices, axis, 0).reshape(dp, -1)
    local_ids = {d.id for d in jax.local_devices()}
    owned = []
    for i in range(dp):
        n_local = sum(d.id in local_ids for d in groups[i])
        if n_local not in (0, groups.shape[1]):
            raise RuntimeError(
                f"dp group {i} is split across processes; re-order mesh "
                f"axes so dp groups are host-aligned")
        if n_local:
            owned.append(i)
    if not owned:
        return slice(0, 0)
    if owned != list(range(owned[0], owned[-1] + 1)):
        raise RuntimeError(
            f"process owns non-contiguous dp groups {owned}; re-order mesh "
            f"axes so each host's dp rows are contiguous")
    return slice(owned[0], owned[-1] + 1)


def dp_rows_for_process(mesh: Mesh, global_batch: int) -> slice:
    """The contiguous slice of the global batch this process's devices own.

    Rows are sharded over the ``dp`` axis wherever it sits in the mesh; a
    dp group's row-shard is replicated over the remaining axes.
    """
    owned = owned_dp_groups(mesh)
    per = global_batch // mesh.shape["dp"]
    return slice(owned.start * per, owned.stop * per)


def local_mesh(mesh: Mesh) -> Mesh:
    """This process's whole-dp-group submesh of ``mesh`` — the same axis
    names and order, the dp extent reduced to the groups this process
    owns.  Collectives/jits over it are process-local (no cross-host
    lockstep needed), which is what lets each host run its own device-side
    replay plane (gather/write) independently while the global train step
    stays SPMD over the full mesh."""
    owned = owned_dp_groups(mesh)
    axis = mesh.axis_names.index("dp")
    sub = np.moveaxis(np.moveaxis(mesh.devices, axis, 0)[owned], 0, axis)
    return Mesh(sub, mesh.axis_names)


def assemble_global(shardings: Dict[str, Any],
                    local_arrays: Dict[str, jax.Array],
                    global_leading: int) -> Dict[str, jax.Array]:
    """Stitch per-process device-resident shards into global jax Arrays.

    ``local_arrays[k]`` is this process's slab, laid out over
    :func:`local_mesh` such that each local device already holds exactly
    the rows the global sharding assigns it (same physical device, same
    bytes — only the leading-axis coordinates differ by the process
    offset).  ``jax.make_array_from_single_device_arrays`` then assembles
    the global view with **zero data movement**: every process contributes
    its addressable shards.  Single-process this is a relabeling no-op.
    """
    out = {}
    for k, la in local_arrays.items():
        gshape = (global_leading, *la.shape[1:])
        out[k] = jax.make_array_from_single_device_arrays(
            gshape, shardings[k], [s.data for s in la.addressable_shards])
    return out


def host_batch_size(cfg: Config, mesh: Mesh) -> int:
    """How many rows of the global ``cfg.batch_size`` this host samples
    from its local replay buffer.  Single-process: ``cfg.batch_size``."""
    rows = dp_rows_for_process(mesh, cfg.batch_size)
    return rows.stop - rows.start


def host_local_batch(mesh: Mesh, local_batch: Dict[str, np.ndarray],
                     shardings: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """Build the globally dp-sharded device batch from per-process data.

    ``local_batch`` holds only this process's rows (``host_batch_size`` of
    them).  Single-process, the local rows are the whole batch and the
    result equals a sharded ``jax.device_put``.  Pass cached ``shardings``
    (``ShardingTable.batch_shardings()``) from hot paths to avoid
    rebuilding them per step.
    """
    if shardings is None:
        shardings = ShardingTable(mesh).batch_shardings()
    return {
        k: jax.make_array_from_process_local_data(shardings[k],
                                                  local_batch[k])
        for k in DEVICE_BATCH_KEYS
    }


def local_rows(arr: jax.Array, axis: int = 0) -> np.ndarray:
    """This process's rows of an ``axis``-sharded global array.

    Reads only addressable shards (a multi-host ``device_get`` of the full
    array would fail), ordered by global row index and deduplicated (a
    shard replicated over non-dp axes appears once per replica).
    Single-process this equals ``device_get`` of the whole array.
    """
    rows: Dict[int, np.ndarray] = {}
    for shard in arr.addressable_shards:
        start = shard.index[axis].start or 0
        if start not in rows:
            rows[start] = np.asarray(shard.data)
    return np.concatenate([rows[s] for s in sorted(rows)], axis=axis)


def global_from_local_rows(sharding: Any, local_data: np.ndarray,
                           global_shape: tuple, axis: int,
                           offset: int) -> jax.Array:
    """Host data → globally sharded device array, when this process's
    ``local_data`` covers global indices [offset, offset + local) of
    ``axis`` (replicated over every other mesh axis).

    The per-device H2D puts follow the sharding's own index map, so this
    works for any axis position (``make_array_from_process_local_data``
    only tiles the leading axis).  Used for the (k, B, 6) index bundles of
    the multi-host device-replay plane, which shard axis 1.
    """
    idx_map = sharding.addressable_devices_indices_map(global_shape)
    arrs = []
    for dev, idx in idx_map.items():
        sl = list(idx)
        s = sl[axis]
        start = (s.start or 0) - offset
        stop = (global_shape[axis] if s.stop is None else s.stop) - offset
        sl[axis] = slice(start, stop)
        arrs.append(jax.device_put(local_data[tuple(sl)], dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, arrs)


def sync_counter(value: int, reduce: str = "max") -> int:
    """All-process reduction of a host counter (e.g. env_steps, buffer
    size) — a device-mediated allgather so hosts agree on progress without
    a side channel.  Single-process it is the identity."""
    if jax.process_count() == 1:
        return int(value)
    from jax.experimental import multihost_utils

    vals = np.asarray(multihost_utils.process_allgather(
        np.asarray(value, np.int64)))
    if reduce == "max":
        return int(vals.max())
    if reduce == "min":
        return int(vals.min())
    return int(vals.sum())


def sync_min_array(values: np.ndarray) -> np.ndarray:
    """Element-wise min of a small float array across processes (the
    cross-host IS-weight normalisation for the multi-host device replay
    plane).  Single-process identity."""
    values = np.asarray(values, np.float64)
    if jax.process_count() == 1:
        return values
    from jax.experimental import multihost_utils

    return np.asarray(
        multihost_utils.process_allgather(values)).min(axis=0)
