"""From a profiler trace to device time by SQL operator.

The program emits every plan node under a `jax.named_scope` called
`sr.<kind>.<n>` (`sql/physical.py`; kinds scan, filter, project, join, agg,
sort, limit, window, union, unnest; `<n>` is the node's pre-order number,
resolved by the statement profile's `scopes` info), nested as the plan nests,
and marks phases inside joins, aggregates and sorts (`PHASES`). JAX writes the
name stack into each HLO operation's metadata as its `op_name`, and a TPU
trace carries it per operation as the stat `tf_op` of the event's metadata
(`jit(q_1a2b3c4d)/sr.agg.2/sr.join.3/compact/scatter:`), beside `source`
(file:line), `hlo_category`, `bytes_accessed` and `flops`. A fusion carries
the name XLA recorded for it, its root's. Some operations carry none (`iota`),
and the `X64SplitLow/High` custom calls on parameters carry the parameter's
(`inputs[0][0][2]:`).

`jax.profiler.ProfileData` reaches an event's own stats only, not its
metadata's, so this file reads the XSpace protobuf itself: a walker over the
wire format, holding the few field numbers it needs (checked against
`tensorflow.tsl.profiler.protobuf.xplane_pb2` where that imports, by the
tests; nothing here imports it).

    scope of an operation   <program>/<innermost sr. component>[/<innermost
                            phase after it>], the program being the name in
                            the stack's first `jit(...)`; NO_TF_OP where the
                            metadata has no `tf_op`, `<program>/NO_SCOPE`
                            where the stack has no `sr.` component. No owner
                            is guessed.
    self time               as `xplane._self_times` defines it
"""

from __future__ import annotations

import os

from . import readers, xplane

# the program's `ops/common.py` PHASES
PHASES = ("build", "probe", "expand", "payload", "rf", "compact",
          "limbs", "lexsort", "segments", "sort")
# operator metric -> the scope kinds it adds up
KINDS = {"scan": ("scan", "filter", "project"), "agg": ("agg",),
         "join": ("join",), "sort": ("sort", "limit", "window")}
NO_TF_OP = "(no tf_op)"
NO_SCOPE = "(no sr scope)"


# --- the XSpace wire format -------------------------------------------------
# XSpace.planes=1; XPlane name=2 lines=3 event_metadata=4 stat_metadata=5
# (maps: entry key=1 value=2); XLine name=2 timestamp_ns=3 events=4;
# XEvent metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata id=1 name=2
# stats=5; XStatMetadata id=1 name=2; XStat metadata_id=1 str_value=5
# ref_value=7.

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    buf, i, n = memoryview(buf), 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple:
    key = value = None
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """{"name", "lines": [(name, timestamp_ns, [(metadata id, offset ps,
    duration ps)])], "events": {metadata id: (name, {stat name: str})}}"""
    name, lines, raw_events, stat_names = "", [], {}, {}
    for number, v in fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            line_name, t_ns, events = "", 0, []
            for ln, lv in fields(v):
                if ln == 2:
                    line_name = _text(lv)
                elif ln == 3:
                    t_ns = lv
                elif ln == 4:
                    ev = {1: 0, 2: 0, 3: 0}
                    ev.update((en, x) for en, x in fields(lv) if en in ev)
                    events.append((ev[1], ev[2], ev[3]))
            lines.append((line_name, t_ns, events))
        elif number == 4:
            key, value = _map_entry(v)
            raw_events[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in fields(value) if n == 2), "")
    events = {}
    for key, value in raw_events.items():
        ev_name, stats = "", {}
        for number, v in fields(value):
            if number == 2:
                ev_name = _text(v)
            elif number == 5:
                stat = dict(fields(v))
                if 5 in stat:
                    stats[stat_names.get(stat.get(1))] = _text(stat[5])
                elif 7 in stat:  # a string kept once, as a stat's name
                    stats[stat_names.get(stat.get(1))] = stat_names.get(stat[7])
        events[key] = (ev_name, stats)
    return {"name": name, "lines": lines, "events": events}


def read_ops(path: str) -> dict:
    """{chip: [(HLO instruction, tf_op or None, source or None, start ps,
    end ps)]} of the `XLA Ops` lines of the device planes."""
    with open(path, "rb") as f:
        space = f.read()
    devices = {}
    for number, v in fields(space):
        if number != 1:
            continue
        name = next((_text(x) for n, x in fields(v) if n == 2), "")
        m = xplane.DEVICE_PLANE.match(name)
        if not m:
            continue
        plane = _plane(v)
        for line_name, t_ns, events in plane["lines"]:
            if line_name != xplane.OPS_LINE:
                continue
            ops = []
            for mid, offset_ps, duration_ps in events:
                ev_name, stats = plane["events"].get(mid, ("", {}))
                start = t_ns * 1000 + offset_ps
                ops.append((ev_name, stats.get("tf_op"), stats.get("source"),
                            start, start + duration_ps))
            devices[int(m.group(1))] = ops
    return devices


# --- scopes -------------------------------------------------------------------

def scope_of(tf_op: str | None) -> str:
    if not tf_op:
        return NO_TF_OP
    stack = tf_op.rsplit(":", 1)[0].split("/")
    program = next((c[4:-1] for c in stack
                    if c.startswith("jit(") and c.endswith(")")), "")
    inner = max((i for i, c in enumerate(stack) if c.startswith("sr.")),
                default=None)
    if inner is None:
        return f"{program}/{NO_SCOPE}" if program else NO_SCOPE
    # the last component is the primitive (`sort` is one, and a phase too)
    phases = [c for c in stack[inner + 1:-1] if c in PHASES]
    return "/".join([program, stack[inner]] + phases[-1:])


def kind_of(scope: str) -> str | None:
    """`join` of `q_1a2b3c4d/sr.join.3/compact`; None outside any scope."""
    for c in scope.split("/"):
        if c.startswith("sr."):
            return c.split(".")[1]
    return None


def attribute(devices: dict, chips: int) -> tuple:
    """({scope: self seconds}, {(operation, scope, source): self seconds}),
    mean over the chips used."""
    used = {n: ops for n, ops in devices.items() if n < chips}
    by_scope, by_op = {}, {}
    for ops in used.values():
        events = [((xplane.op_name(name)[0], scope_of(tf_op), source or tf_op),
                   start, end) for name, tf_op, source, start, end in ops]
        for what, self_ps in xplane._self_times(events):
            s = self_ps / 1e12 / len(used)
            by_scope[what[1]] = by_scope.get(what[1], 0.0) + s
            by_op[what] = by_op.get(what, 0.0) + s
    return by_scope, by_op


def trace_path(run) -> str:
    """`run.py` writes the cell's trace here and hands `compute` only what
    `xplane.reduce` kept of it: read it again."""
    return xplane.find_xplane(os.path.join(
        run.cell.root, "benchmarks", ".traces", run.cell.name))


def _largest(seconds: dict, n: int = 10) -> list:
    return sorted(seconds.items(), key=lambda kv: -kv[1])[:n]


def by_scope(run) -> dict:
    """{scope: self seconds} over the chips the cell uses; {} without a
    device trace. Read once per run; the first reading prints the ten
    largest scopes and, for the ten largest operations, where each ran."""
    if not run.trace:
        return {}
    if not hasattr(run, "_by_scope"):
        run._by_scope, by_op = attribute(read_ops(trace_path(run)),
                                         run.cell.chips)
        for scope, s in _largest(run._by_scope):
            print(f"scope {scope} self_s={s:.6f}")
        for (name, scope, source), s in _largest(by_op):
            at = "" if kind_of(scope) else f" at={source}"
            print(f"operation {name} -> {scope} self_s={s:.6f}{at}")
    return run._by_scope


def _scoped(run) -> dict:
    """`by_scope`, or {} where no operation carries a scope at all: a
    program from before the scopes reports no operator metric, not zeros."""
    totals = by_scope(run)
    return totals if any(kind_of(scope) for scope in totals) else {}


def kind_ms(run, metric: str) -> float | None:
    """Milliseconds a statement of the traced slice spent on the device under
    the scope kinds of `metric` (a key of KINDS), statements counted as
    `device_ms_per_stmt` counts them. None without a device trace, and where
    no operation of the trace carries a scope: a program from before them."""
    totals = _scoped(run)
    n = sum(share for _, share in readers.statements_in_slice(run))
    if not n or not totals:
        return None
    return sum(s for scope, s in totals.items()
               if kind_of(scope) in KINDS[metric]) * 1e3 / n


def other_share(run) -> float | None:
    """Share (%) of the device's self time under none of KINDS' kinds."""
    totals = _scoped(run)
    if not totals:
        return None
    named = {k for kinds in KINDS.values() for k in kinds}
    other = sum(s for scope, s in totals.items()
                if kind_of(scope) not in named)
    return 100.0 * other / sum(totals.values())
