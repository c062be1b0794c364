"""Bytes of the device column cache's arrays after the window (columns,
validity and selection masks, build orders), over all the cell's devices."""

META = {"layer": "placement", "unit": "GB", "better": "lower",
        "source": "program_counter", "moves": "peak_hbm_gb"}


def compute(run):
    return run.resident_bytes / 1e9
