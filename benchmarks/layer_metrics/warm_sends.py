"""Warm-up cycles sent until one whole cycle compiled nothing, that cycle
included: 2 where the first send's program is final, 3 where the first run
published tightened capacities and the second send compiled at them. A count;
it repeats exactly."""

META = {"layer": "compile", "unit": "sends", "better": "lower",
        "source": "program_counter", "moves": "setup_s"}


def compute(run):
    return float(run.warm["cycles"])
