"""Median milliseconds a statement spent in its `fetch_results` profile span
(device result to host table), over the sampled statements of the window."""

from benchmarks.harness import readers

META = {"layer": "fetch", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "lat_geomean_ms"}


def compute(run):
    return readers.span_median_ms(run, "fetch_results")
