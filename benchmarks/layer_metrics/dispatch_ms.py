"""Median milliseconds a statement spent in its `dispatch` spans, over the
sampled statements of the window: the host's part of calling the compiled
program (argument handling, enqueue), before it waits for the device."""

from benchmarks.harness import readers

META = {"layer": "device_program", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "lat_geomean_ms"}


def compute(run):
    return readers.span_median_ms(run, "dispatch")
