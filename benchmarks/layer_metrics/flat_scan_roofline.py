"""Roofline share of a flat-table scan: the least time the chip could take
for the statements in the traced slice — rows times the bytes of the columns
each reads at the narrowest width their declared types and dictionaries
admit (`harness/flat_bytes.py`) over the chip's peak HBM bandwidth
(`harness/peaks.py`) — over the device's busy time in the slice. The bound is
HBM bandwidth: these statements filter, compact and sum, a few operations a
byte. Unlike `scan_roofline` the bytes do not follow what the host table or
the device holds, so narrowing resident columns cannot push it past 100%."""

from benchmarks.harness import flat_bytes, peaks, readers

META = {"layer": "kernels", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    in_slice = readers.statements_in_slice(run)
    if not in_slice:
        return None
    bandwidth = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"] * run.cell.chips
    least_s = sum(
        share * flat_bytes.flat_scan_bytes(
            run.tables, run.cell.variants[vi]["oracle"].COLUMNS) / bandwidth
        for vi, share in in_slice)
    return 100.0 * least_s / run.trace["busy_s"]
