"""Device milliseconds per statement of the traced slice in operations whose
innermost SQL operator scope is a scan's work: `sr.scan`, `sr.filter`,
`sr.project` (self time by `harness/scopes.py`, statements counted as
`device_ms_per_stmt` counts them)."""

from benchmarks.harness import scopes

META = {"layer": "kernels", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    return scopes.kind_ms(run, "scan")
