"""Median milliseconds a statement spent in its `optimize` profile span
(plan-cache look-up, optimizer, sub-query resolution), over the sampled
statements of the window."""

from benchmarks.harness import readers

META = {"layer": "plan", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "lat_geomean_ms"}


def compute(run):
    return readers.span_median_ms(run, "optimize")
