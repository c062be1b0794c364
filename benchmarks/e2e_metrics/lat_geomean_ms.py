"""Geometric mean, over the cell's statement templates, of each template's
median warm latency in the window, client side (TPC-H's power-style summary;
with one template it is that template's median)."""

from benchmarks.harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock"}


def compute(run):
    medians = [stats.median(ms) for ms in run.latencies.values() if ms]
    if len(medians) < len(run.latencies):
        return None  # a template the window never completed
    return stats.geomean(medians)
