"""Mean milliseconds a statement waited for one of the serving tier's pool
workers, over the window."""

META = {"layer": "admission", "unit": "ms", "better": "lower",
        "source": "program_counter", "moves": "lat_p95_ms"}


def compute(run):
    n = run.counters.get("sr_tpu_serve_statements_total", 0)
    if not n:
        return None
    return run.counters.get("sr_tpu_serve_queue_wait_ms_total", 0) / n
