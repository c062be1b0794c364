"""Seconds of the warm-up cycles: tracing, XLA compilation or loading from
the persistent compile cache, first placement of the columns, and the sends'
own execution."""

META = {"layer": "compile", "unit": "s", "better": "lower",
        "source": "host_clock", "moves": "setup_s"}


def compute(run):
    return run.warm["seconds"]
