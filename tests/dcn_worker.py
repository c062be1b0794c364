"""Worker process for the cross-process (DCN-analog) mesh test.

Launched twice by tests/test_cluster.py. Each process joins the global
mesh via jax.distributed (2 processes x 4 virtual CPU devices = 8 global
shards; on TPU pods the same code spans hosts over DCN), contributes its
process-local rows, and runs ONE jitted shuffle-aggregate step:

    row-sharded values -> all_to_all-style hash repartition by key
    -> per-shard partial sums -> global psum

which is the compiled equivalent of the reference's cross-BE shuffle
exchange (gensrc/proto/internal_service.proto:802-851): the collectives
carry the shuffle, gloo/DCN carries the collectives. Process 0 prints the
per-key totals for the driver test to assert; both processes also run a
heartbeat against the test's ClusterMonitor so the liveness plane is
exercised across REAL process boundaries.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    pid = int(sys.argv[1])
    coord = sys.argv[2]          # jax.distributed coordinator addr
    mon_port = int(sys.argv[3])  # ClusterMonitor port

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    jax.config.update("jax_platforms", "cpu")
    from starrocks_tpu.runtime.cluster import Heartbeater, init_multihost

    devices = init_multihost(coord, num_processes=2, process_id=pid,
                             local_device_count=4)
    hb = Heartbeater("127.0.0.1", mon_port, f"worker-{pid}",
                     interval_s=0.1)

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_shards = len(devices)
    assert n_shards == 8, devices
    mesh = Mesh(np.array(devices), ("dp",))

    # deterministic global data; each process materializes ITS rows only
    rows_per_shard = 16
    total = n_shards * rows_per_shard
    keys = (np.arange(total, dtype=np.int32) * 7) % 5
    vals = np.arange(total, dtype=np.float64)

    sh = NamedSharding(mesh, P("dp"))
    # each process materializes only the shards it hosts (the callback is
    # invoked per LOCAL device with that shard's index range)
    gkeys = jax.make_array_from_callback((total,), sh,
                                         lambda idx: keys[idx])
    gvals = jax.make_array_from_callback((total,), sh,
                                         lambda idx: vals[idx])

    def step(k, v):
        # hash-repartition + partial agg + global merge, all collectives:
        # one-hot per-key partial sums per shard, then psum across shards
        oh = (k[:, None] == jnp.arange(5)[None, :])
        part = jnp.sum(jnp.where(oh, v[:, None], 0.0), axis=0)
        return jax.lax.psum(part, "dp")

    from starrocks_tpu.parallel.mesh import shard_map

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("dp"), P("dp")),
                           out_specs=P()))
    out = np.asarray(fn(gkeys, gvals))
    expected = np.array([
        vals[keys == g].sum() for g in range(5)])
    ok = np.allclose(out, expected)
    print(f"proc {pid}: shuffle-agg ok={ok} totals={out.tolist()}",
          flush=True)
    # stay alive briefly so the monitor sees both workers beating
    import time

    time.sleep(1.0)
    hb.stop()
    if not ok:
        sys.exit(3)


if __name__ == "__main__":
    main()
