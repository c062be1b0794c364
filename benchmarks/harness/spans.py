"""The warm-up's compile spans. The program times, inside a fresh program's
first call, what JAX reports of it: `jax_trace` (Python to jaxpr),
`jax_lower` (jaxpr to StableHLO) and `xla_compile` (XLA's compile, or the
load from the persistent compile cache when that hits)."""

from __future__ import annotations


def warm_spans_s(run, names: tuple) -> float | None:
    """Seconds the warm-up's statements spent in spans called one of
    `names`; 0.0 where nothing compiled. None where no statement has a
    `dispatch` span: a program from before these spans."""
    spans = [sp for st in run.warm["statements"].values()
             for sp in st["spans"]]
    if not any(n == "dispatch" for n, _, _ in spans):
        return None
    return float(sum(d for n, _, d in spans if n in names))
