"""Seconds the warm-up's statements spent in `scan_to_device` profile spans:
host columns padded, copied to the device and cached."""

META = {"layer": "placement", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "setup_s"}


def compute(run):
    return sum(d for st in run.warm["statements"].values()
               for n, _, d in st["spans"] if n == "scan_to_device")
