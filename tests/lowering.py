"""What a statement's program looks like before XLA: the StableHLO text,
debug info included, in which every operation carries its name stack
(`jit(..)/sr.agg.2/sr.filter.3/limbs/dot_general`)."""

import re

import jax

from starrocks_tpu.sql.physical import Caps, compile_plan

SCOPED = re.compile(r'"(jit\([^"]*?sr\.[^"]*)"')


def lowered_text(session, result, debug_info: bool = True) -> str:
    """Lower the plan `result` ran, at the capacities it ended on, over the
    session's own device columns. Without `debug_info` the text holds no
    name stacks and no source lines: the same program gives the same bytes
    on two trees."""
    caps = {}
    for attempt in result.profile.children:
        caps = attempt.infos.get("capacities") or caps
    compiled = compile_plan(result.plan, session.catalog, Caps(dict(caps)))
    table = session.catalog.get_table
    inputs = tuple(session.cache.chunk_for(table(t), a, cols)
                   for t, a, cols in compiled.scans) + tuple(
        session.cache.build_order_for(table(t), a, keys, widths)
        for t, a, keys, widths in compiled.aux)
    return jax.jit(compiled.fn).lower(inputs).as_text(debug_info=debug_info)


def scope_paths(text: str, phases) -> set:
    """The name stacks of the text's operations, cut down to their `sr.`
    scopes and the phases inside them: {"sr.sort.0/sr.agg.2/lexsort", ...}."""
    keep = set(phases)
    return {"/".join(c for c in path.split("/")[:-1]
                     if c.startswith("sr.") or c in keep)
            for path in SCOPED.findall(text)}
