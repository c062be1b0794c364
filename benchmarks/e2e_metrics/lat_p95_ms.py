"""95th percentile of all statement latencies in the window, client side.
Reported only where the window holds at least 200 statements, so that ten
samples lie beyond it."""

from benchmarks.harness import stats

META = {"unit": "ms", "better": "lower", "source": "host_clock"}
MIN_STATEMENTS = 200


def compute(run):
    ms = [m for per_template in run.latencies.values() for m in per_template]
    return stats.percentile(ms, 0.95) if len(ms) >= MIN_STATEMENTS else None
