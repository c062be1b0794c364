"""Roofline share of the scan: the least time the chip could take for the
statements in the traced slice — the bytes of the columns each reads
(`harness/bytes.py`, from shapes) over the chip's peak HBM bandwidth
(`harness/peaks.py`) — over the device's busy time in the slice. The bound is
HBM bandwidth: these statements do a few operations per byte. For a statement
that joins or sorts the bytes are a floor, so the share is an upper estimate."""

from benchmarks.harness import bytes as scan_bytes
from benchmarks.harness import peaks, readers

META = {"layer": "kernels", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "lat_geomean_ms"}


def compute(run):
    in_slice = readers.statements_in_slice(run)
    if not in_slice:
        return None
    bandwidth = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"] * run.cell.chips
    least_s = sum(
        share * scan_bytes.scan_bytes(
            run.tables, run.cell.variants[vi]["oracle"].COLUMNS) / bandwidth
        for vi, share in in_slice)
    return 100.0 * least_s / run.trace["busy_s"]
