"""Seconds the warm-up's statements spent in XLA's compile, or in loading
the executable from the persistent compile cache where that hits
(`xla_compile` spans). 0.0 where nothing compiled."""

from benchmarks.harness import spans

META = {"layer": "compile", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "setup_s"}


def compute(run):
    return spans.warm_spans_s(run, ("xla_compile",))
