"""Seconds the warm-up's statements spent tracing their programs to jaxprs
and lowering those to StableHLO (`jax_trace` + `jax_lower` spans): paid on
every start, whatever the persistent compile cache holds. 0.0 where nothing
compiled."""

from benchmarks.harness import spans

META = {"layer": "compile", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "setup_s"}


def compute(run):
    return spans.warm_spans_s(run, ("jax_trace", "jax_lower"))
