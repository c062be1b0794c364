"""Megabytes one chip put on the interconnect per statement: the window's
delta of the program's counter `sr_tpu_exchange_bytes_total` over the
statements completed in the window. The program bumps the counter on the
host once per run of a mesh program from that program's static shapes: an
all_to_all of n buckets of C slots sends n-1 of them, an all_gather of c
local slots sends them to the n-1 other chips, each slot as wide as its data
and validity columns and the live mask; padding counts, since it travels.
Nothing is reported for a program without the counter."""

META = {"layer": "exchange", "unit": "MB", "better": "lower",
        "source": "program_counter", "moves": "lat_geomean_ms"}


def compute(run):
    sent = run.counters.get("sr_tpu_exchange_bytes_total")
    done = len(run.window["records"])
    if sent is None or not done:
        return None
    return sent / done / 1e6
