"""Device milliseconds per statement of the traced slice in operations under
the phase `compact` (`ops/common.compact`: the prefix sum and shift rounds
that give each live row a slot, `compact/index`, and the gathers that move
the columns, `compact/gather`), under whatever operator asked for it: a
join's side, or since the flat-table cell an aggregate's input after a
selective filter. Self time as `harness/scopes.py` attributes it, statements
counted as `device_ms_per_stmt` counts them. `op_agg_ms` and `op_join_ms`
hold the same operations under their operator: this metric is a part of
theirs. Nothing is reported for a program without scopes.

It under-reads by about a quarter of the index (my chip run, PR 32: 18.5 of
the ~76 ms a 75.0M-slot compaction's index takes): XLA's TPU compiler turns
the prefix sum into a `reduce-window` that carries no `tf_op`, so the reader
cannot place it under `compact` and `op_other_share` holds it. The rounds
and the gathers, which a change to `compact` would move, are all here."""

from benchmarks.harness import readers, scopes

META = {"layer": "kernels", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    totals = scopes.by_scope(run)
    n = sum(share for _, share in readers.statements_in_slice(run))
    if not n or not any(scopes.kind_of(scope) for scope in totals):
        return None
    return sum(s for scope, s in totals.items()
               if scope.rsplit("/", 1)[-1] == "compact") * 1e3 / n
