"""Programs traced plus capacity recompiles inside the window. Nothing may
compile there: 0 in every cell."""

META = {"layer": "compile", "unit": "programs", "better": "lower",
        "source": "program_counter", "moves": "lat_geomean_ms"}


def compute(run):
    return float(sum(run.counters.get(k, 0) for k in (
        "sr_tpu_program_compiles_total", "sr_tpu_capacity_recompiles_total")))
