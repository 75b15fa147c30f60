"""Mesh and multi-process helpers — every mesh, shard_map and
``jax.distributed`` call in the repo goes through here.

* ``make_mesh`` builds meshes with Auto axis types (the k-core shard_map
  programs place their own collectives), ``shard_map`` turns replication
  checking off.
* The multi-process (multi-host) helpers let the fused sharded runtime span
  processes — ``init_multiprocess`` brings a rank into the coordination
  service, ``global_mesh`` builds a mesh over every global device, and
  ``stage_to_mesh`` / ``fetch_replicated`` move host arrays across the
  single-vs-multi-process boundary (``jnp.asarray`` and ``np.asarray`` are
  process-local and fail on cross-process global arrays).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax._src.distributed import global_state as _distributed_state


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------------ #
# Multi-process (jax.distributed) topology
# ------------------------------------------------------------------ #

def init_multiprocess(coordinator_address: str, num_processes: int,
                      process_id: int) -> None:
    """Join this process into a ``jax.distributed`` service.

    Every rank of a multi-host run calls this before touching any device;
    afterwards ``jax.devices()`` is the GLOBAL device list and
    ``global_mesh`` spans it. Idempotent per process (jax forbids double
    initialization; a repeat call is a no-op). Deliberately avoids
    ``jax.process_count()`` here — merely asking would initialize the
    backend, after which jax refuses to join a coordination service.
    """
    if _distributed_state.client is not None:
        return
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def global_mesh(axis_name: str = "shard") -> jax.sharding.Mesh:
    """1-D mesh over EVERY global device (all processes' devices)."""
    return make_mesh((len(jax.devices()),), (axis_name,))


def is_multiprocess_mesh(mesh: jax.sharding.Mesh) -> bool:
    """True when ``mesh`` spans devices owned by more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def stage_to_mesh(arr: np.ndarray, mesh: jax.sharding.Mesh,
                  spec) -> jax.Array:
    """Build a global device array from a host copy every process holds.

    ``jnp.asarray`` commits to a process-local device and cannot feed a
    cross-process jit; ``jax.make_array_from_callback`` assembles the global
    array from per-shard slices instead — each process serves only the
    shards its own devices own. Works identically on a single-process mesh,
    where it degenerates to a plain device_put with ``spec``.
    """
    arr = np.asarray(arr)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def fetch_replicated(x, mesh: jax.sharding.Mesh) -> np.ndarray:
    """Host copy of a global array, valid on every process.

    Non-fully-addressable arrays (outputs sharded across processes) are
    first replicated with a collective identity jit — afterwards each
    process holds the complete value and the numpy conversion is local.
    """
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    from jax.sharding import PartitionSpec as P

    rep = jax.jit(
        lambda a: a,
        out_shardings=jax.sharding.NamedSharding(mesh, P()))(x)
    return np.asarray(rep.addressable_data(0))
