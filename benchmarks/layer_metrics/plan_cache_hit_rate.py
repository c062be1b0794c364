"""Share of the window's statement texts answered from the text -> analyzed
plan cache."""

META = {"layer": "plan", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "lat_geomean_ms"}


def compute(run):
    hits = run.counters.get("sr_tpu_plan_cache_hits_total", 0)
    total = hits + run.counters.get("sr_tpu_plan_cache_misses_total", 0)
    return 100.0 * hits / total if total else None
