"""Median milliseconds a statement spent in its `device_wait` spans
(`jax.block_until_ready` on the program's result), over the sampled
statements of the window: its own device work and whatever other statements'
programs are queued on the device ahead of it."""

from benchmarks.harness import readers

META = {"layer": "device_program", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "lat_geomean_ms"}


def compute(run):
    return readers.span_median_ms(run, "device_wait")
