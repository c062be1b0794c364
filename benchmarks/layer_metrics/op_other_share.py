"""Share of the device's busy self time in the traced slice that the operator
metrics do not name: operations without a `tf_op`, operations outside every
`sr.` scope (the `X64Split` custom calls on a program's parameters), and
scopes of another kind (union, unnest, exchange)."""

from benchmarks.harness import scopes

META = {"layer": "kernels", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    return scopes.other_share(run)
