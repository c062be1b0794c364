"""Device mesh management and chunk sharding.

Reference behavior: plan fragments get N instances across BEs with scan ranges
assigned by locality (fe qe/CoordinatorPreprocessor.java:70, BackendSelector).
The TPU re-design: one SPMD program over a jax.sharding.Mesh; a table shard on
device i plays the role of fragment-instance i's scan range. Exchange between
fragments becomes XLA collectives over ICI (see exchange.py).
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..column.column import Chunk, pad_capacity

DATA_AXIS = "d"

def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """jax.shard_map with the replication/VMA check off (the engine always
    disables it: overflow-check outputs are deliberately per-shard).
    Single import point for engine + tests (tools/src_lint.py R1)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(n_devices: int | None = None, axis: str = DATA_AXIS) -> Mesh:
    """Mesh over the first n global devices. Under jax.distributed,
    jax.devices() spans every process (4 local CPU devices x 2 processes =
    8 global), so the same call builds the multi-process DCN mesh — the
    caller only ever sees one axis of n shards."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis,))


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh's devices live in more than one process — host
    transfers must then go through make_array_from_callback (each process
    materializes only its addressable shards) instead of device_put."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_global(x, sharding):
    """Place a host array onto a (possibly multi-process) sharding.

    Single-process: plain device_put. Multi-process: the callback path —
    jax invokes it once per LOCAL device with that shard's global index
    range, so each process materializes only its slice of the table (the
    per-process TabletStore slice; remote shards are never built here).
    """
    arr = np.asarray(x)
    if not mesh_spans_processes(sharding.mesh):
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx, a=arr: a[idx])


def shard_host_table(table, mesh: Mesh, axis: str = DATA_AXIS) -> Chunk:
    """Build a row-sharded global Chunk from a HostTable.

    Global capacity is padded so every shard has equal rows (XLA needs equal
    splits); the selection mask marks the real rows.
    """
    n = mesh.shape[axis]
    rows = table.num_rows
    local_cap = pad_capacity((rows + n - 1) // n)
    chunk = table.to_chunk(capacity=local_cap * n)
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)

    def put(x):
        return jax.device_put(x, sharding)

    data = tuple(put(d) for d in chunk.data)
    valid = tuple(None if v is None else put(v) for v in chunk.valid)
    sel = put(chunk.sel_mask())
    return Chunk(chunk.schema, data, valid, sel)


def chunk_pspec(chunk: Chunk, axis: str = DATA_AXIS):
    """PartitionSpec pytree matching a chunk's structure (row-sharded)."""
    spec = P(axis)
    return jax.tree_util.tree_map(lambda _: spec, chunk)


def replicated_pspec(tree):
    return jax.tree_util.tree_map(lambda _: P(), tree)
