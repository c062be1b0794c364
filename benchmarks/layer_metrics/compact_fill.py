"""How full the chunks are that the window's compactions wrote: the window's
delta of the program's counter `sr_tpu_compact_rows_live_total` over that of
`sr_tpu_compact_slots_out_total`, in percent. The program bumps both on the
host once per statement, from the static shapes of the program that ran and
the live rows its overflow check counted: a compaction's output capacity is
learned from the data in the warm-up (the true count times the engine's
headroom, rounded up to 1,024 rows), so a low fill is padding every later
operator pays for. Nothing is reported for a program without the counters,
or where no statement of the window compacted."""

META = {"layer": "device_program", "unit": "%", "better": "higher",
        "source": "program_counter", "moves": "lat_geomean_ms"}


def compute(run):
    live = run.counters.get("sr_tpu_compact_rows_live_total")
    slots = run.counters.get("sr_tpu_compact_slots_out_total")
    if live is None or not slots:
        return None
    return 100.0 * live / slots
