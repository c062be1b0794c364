"""Share of the device's busy time spent in collective operations
(all-to-all, all-reduce, all-gather, reduce-scatter, collective-permute; self
time, mean over the cell's chips) in the traced slice."""

META = {"layer": "exchange", "unit": "%", "better": "lower",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    if not run.trace:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
