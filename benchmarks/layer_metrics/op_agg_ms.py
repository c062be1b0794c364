"""Device milliseconds per statement of the traced slice in operations under
an aggregate's scope, `sr.agg`, whatever the phase (`limbs`, `lexsort`,
`segments`, `compact` or none)."""

from benchmarks.harness import scopes

META = {"layer": "kernels", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    return scopes.kind_ms(run, "agg")
