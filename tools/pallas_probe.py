#!/usr/bin/env python
"""Mosaic probe: does each kernel of ops/pallas_kernels.py compile on a TPU?

    python tools/pallas_probe.py            (one chip command; needs a TPU)

The five kernels sit behind non-default strategies and tier-1 only ever runs
them in interpret mode, so nothing else in the repo asks Mosaic to lower
them. For each kernel, at the shape the engine would hand it from the chip
smoke's SF10 statements (row counts cut to 2^22 so a kernel that does lower
finishes in seconds): compile with interpret=False, and if that succeeds run
it and compare with its XLA twin. Prints one line per kernel and a JSON
table (also written to chiprun_out/pallas_probe.json); exits 0 whenever the
probe itself ran — a kernel Mosaic rejects is a finding, not a failure.
"""

from __future__ import annotations

import faulthandler
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _cases(n: int = 1 << 22):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from starrocks_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(7)
    g = 4
    gid = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    vals = jnp.asarray(rng.random((n, 1), dtype=np.float32))
    keys64 = jnp.asarray(rng.integers(-(1 << 62), 1 << 62, n))
    nb = 1 << 16  # join build side: unique int64 keys
    build = jnp.asarray(rng.permutation(nb * 4)[:nb].astype(np.int64))
    probe = jnp.asarray(rng.integers(0, nb * 4, n).astype(np.int64))
    sbuild = jnp.sort(build)

    def hash_probe(b, p):
        tkey, trow = pk.hash_build_pallas(b, 2 * nb)
        return pk.hash_probe_pallas(tkey, trow, p)

    def hash_probe_twin(b, p):
        order = jnp.argsort(b)
        pos = jnp.clip(jnp.searchsorted(b[order], p), 0, nb - 1)
        return jnp.where(b[order][pos] == p, order[pos], -1)

    return [
        ("segment_sum_pallas", f"gid i32[{n}] vals f32[{n},1] G={g} "
         "block=2048",
         lambda: pk.segment_sum_pallas(gid, vals, g, block=2048),
         lambda: pk.segment_sum_onehot(gid, vals, g), 1e-4),
        ("topn_select_pallas", f"neg i64[{n}] k=16 block=1024",
         lambda: jax.lax.top_k(pk.topn_select_pallas(keys64, 16)[0], 16)[0],
         lambda: jax.lax.top_k(keys64, 16)[0], 0),
        ("hash_build_pallas", f"keys i64[{nb}] table={2 * nb}",
         lambda: jnp.sort(pk.hash_build_pallas(build, 2 * nb)[0])[:nb],
         lambda: sbuild, 0),
        ("hash_probe_pallas", f"table i64[{2 * nb}] probe i64[{n}] "
         "block=2048",
         lambda: hash_probe(build, probe),
         lambda: hash_probe_twin(build, probe), 0),
        ("probe_searchsorted_pallas", f"build i64[{nb}] probe i64[{n}] "
         "block=2048",
         lambda: pk.probe_searchsorted_pallas(sbuild, probe),
         lambda: jnp.searchsorted(sbuild, probe), 0),
    ]


def main() -> int:
    import jax
    import numpy as np

    import starrocks_tpu  # noqa: F401 — x64 on, as every engine program has it

    if jax.default_backend() != "tpu":
        print(f"pallas_probe: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    # a kernel that lowers but never returns must not hold the chip
    faulthandler.dump_traceback_later(420, exit=True)
    table = []
    for name, shape, kernel, twin, tol in _cases():
        row = {"kernel": name, "shape": shape, "lowered": False}
        try:
            compiled = jax.jit(kernel).lower().compile()
            row["lowered"] = True
            got, exp = np.asarray(compiled()), np.asarray(jax.jit(twin)())
            row["matches_xla_twin"] = bool(
                np.allclose(got, exp, rtol=tol, atol=0))
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the datum
            lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
            row["error"] = f"{type(e).__name__}: {lines[0][:300]}"
            row["error_detail"] = " | ".join(lines[1:6])[:1200]
        table.append(row)
        print(json.dumps(row), flush=True)
    out = {"device_kind": jax.devices()[0].device_kind,
           "jax": jax.__version__, "kernels": table}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "pallas_probe.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
