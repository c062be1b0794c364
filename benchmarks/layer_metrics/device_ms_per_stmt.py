"""Device busy milliseconds per statement: busy time in the traced slice
over the statements in it, a statement counting by the share of its latency
that lies inside the slice (so a slice inside one long join gives that join's
busy share times its latency)."""

from benchmarks.harness import readers

META = {"layer": "device_program", "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    n = sum(share for _, share in readers.statements_in_slice(run))
    return run.trace["busy_s"] * 1e3 / n if n else None
