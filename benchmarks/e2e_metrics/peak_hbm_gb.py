"""Peak HBM after the window on the fullest of the cell's devices:
`peak_bytes_in_use` plus `peak_bytes_reserved` where the backend reports it
(`harness/sut.py peak_bytes`). The scale factor a chip can hold is a cost
users pay, so speed may not be bought with memory unseen."""

META = {"unit": "GB", "better": "lower", "source": "host_clock"}


def compute(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
