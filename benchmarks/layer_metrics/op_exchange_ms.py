"""Device milliseconds per statement of the traced slice in operations whose
`op_name` stack holds the component `exchange`: the program puts a
`jax.named_scope("exchange")` around every exchange between a mesh's shards
(`parallel/exchange.py`), inside the `sr.<kind>.<n>` scope of the operator
that asked for it. Self time, mean over the cell's chips, statements counted
as `device_ms_per_stmt` counts them. It splits in two, and the reader prints
both beside the scopes that hold them:

    exchange/pack        the bucket of each row (hash, or sampled splitters),
                         the argsort by bucket and the scatters, one a column,
                         into the padded send buffer
    exchange/collective  the all_to_all or all_gather itself (and the sample
                         all_gather of a range exchange)

`op_join_ms`, `op_agg_ms` and `op_sort_ms` hold the same operations under
their operator: this metric is a part of theirs, not beside them. Nothing is
reported for a program without the scope (one from before it)."""

from benchmarks.harness import readers, scopes, xplane

META = {"layer": "exchange", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}
PARTS = ("pack", "collective")


def exchange_scope(tf_op: str | None) -> str | None:
    """`<program>/<innermost sr. scope>/exchange/<pack|collective|other>` of
    an operation inside an exchange, None of any other."""
    if not tf_op:
        return None
    stack = tf_op.rsplit(":", 1)[0].split("/")[:-1]  # less the primitive
    if "exchange" not in stack:
        return None
    below = stack[stack.index("exchange") + 1:]
    part = below[0] if below and below[0] in PARTS else "other"
    owner = scopes.scope_of(tf_op).split("/")[:2]
    return "/".join(owner + ["exchange", part])


def by_exchange_scope(devices: dict, chips: int) -> dict:
    """{exchange scope: self seconds}, mean over the chips used."""
    used = {n: ops for n, ops in devices.items() if n < chips}
    totals = {}
    for ops in used.values():
        events = [(exchange_scope(tf_op), start, end)
                  for _, tf_op, _, start, end in ops]
        for scope, self_ps in xplane._self_times(events):
            if scope is not None:
                totals[scope] = totals.get(scope, 0.0) + (
                    self_ps / 1e12 / len(used))
    return totals


def compute(run):
    if not run.trace:
        return None
    n = sum(share for _, share in readers.statements_in_slice(run))
    totals = by_exchange_scope(scopes.read_ops(scopes.trace_path(run)),
                               run.cell.chips)
    if not n or not totals:
        return None
    for scope, s in sorted(totals.items(), key=lambda kv: -kv[1]):
        print(f"scope {scope} self_s={s:.6f}")
    for part in PARTS + ("other",):
        s = sum(v for scope, v in totals.items() if scope.endswith("/" + part))
        print(f"op_exchange_ms exchange/{part} ms_per_stmt={s * 1e3 / n:.3f}")
    return sum(totals.values()) * 1e3 / n
