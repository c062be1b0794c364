"""From a `jax.profiler` trace (`*.xplane.pb`) to device metrics.

What a TPU trace holds (a v5e's, jax 0.9.0, looked at by hand): one plane per
chip named `/device:TPU:<n>`, whose line `XLA Ops` has one event per executed
HLO operation, named by the whole instruction (`%fusion.12 = (u32[...]) fusion(
...)`, shortened here to `fusion.12`), nested where an operation contains
others (a `while` and its body), and whose line `XLA Modules` has one event per
program run; `Async XLA Ops` (copy-start/done pairs that overlap the
operations) is not counted as busy. `/host:CPU` has one line per host thread.
All times are nanoseconds on one clock.

    busy      union of the `XLA Ops` intervals of one chip
    idle gap  an interval of the traced span in which no operation ran on it
    self time an operation's duration less that of the operations nested in it
    collective  an operation whose HLO opcode is all-reduce, all-gather,
              all-to-all, reduce-scatter, collective-permute or
              collective-broadcast (start/done halves of an asynchronous one
              included). The opcode, not the name: JAX names a reshape that
              feeds an exchange `all_to_all.56`, and a fusion may read an
              operand called `%all-gather.9`.

The profiler keeps no operation of a program that was already running when
the trace began or is still running when it ends, so the traced span is taken
from the first recorded operation to the end of the last, over the chips
used: whole programs and the gaps between them. A mark the harness writes
into the trace itself (a TraceAnnotation that carries the epoch time in its
name) puts the program's epoch-stamped profile spans on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast)(-start|-done)?\Z")
# the opcode is the first lower-case word that opens a parenthesis after the
# result's shape: `(u32[8]{0:T(1024)}, u32[]) all-reduce(u32[8]{0} %x, ...`
OPCODE = re.compile(r"(?<![\w%.\-])([a-z][a-z0-9\-]*)\(")
# idle gaps shorter than this are the device's own turn-round between two
# operations, not something the host did
SHORT_GAP_S = 50e-6
MARK = re.compile(r"bench_mark_epoch_ns=(\d+)\Z")


def mark_name(epoch_ns: int) -> str:
    return f"bench_mark_epoch_ns={epoch_ns}"


def op_name(event_name: str) -> tuple:
    """(`fusion.12`, `fusion`): name and opcode from the event's name, which
    on a TPU is the whole HLO instruction `%fusion.12 = (u32[1025,8]{...})
    fusion(bf16[...] %concatenate.4, ...)`. Where it is a bare name the
    opcode is the name without its number."""
    name, _, rest = event_name.partition(" = ")
    name = name.lstrip("%")
    m = OPCODE.search(rest)
    return name, m.group(1) if m else name.rsplit(".", 1)[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(path: str) -> dict:
    """{"devices": {chip number: [((name, opcode), start_ns, end_ns)]},
        "mark": (trace ns, epoch ns) or None}"""
    from jax.profiler import ProfileData

    devices, mark = {}, None
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    mk = MARK.match(e.name)
                    if mk:
                        mark = (e.start_ns, int(mk.group(1)))
    return {"devices": devices, "mark": mark}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> list:
    """[(what, self ns)] for events (what, start, end) of one line, which
    nest but do not otherwise overlap."""
    out, stack = [], []  # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def reduce(trace: dict, chips: int) -> dict:
    """Busy seconds (mean over the chips used), the traced span, self time by
    operation name, collective seconds, and the idle gaps of the busiest chip
    as (epoch start s, epoch end s). {} when no operation ran on a device."""
    devices = {n: ev for n, ev in trace["devices"].items() if n < chips}
    if not any(devices.values()):
        return {}
    lo = min(s for ev in devices.values() for _, s, _ in ev)
    hi = max(e for ev in devices.values() for _, _, e in ev)
    to_epoch_s = None
    if trace["mark"]:
        t_ns, epoch_ns = trace["mark"]
        to_epoch_s = lambda ns: (epoch_ns + (ns - t_ns)) / 1e9  # noqa: E731

    busy, by_name, collective_ns, gaps_of = {}, {}, 0.0, {}
    for n, events in devices.items():
        merged = _union([(s, e) for _, s, e in events])
        busy[n] = sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps_of[n] = [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for (name, opcode), self_ns in _self_times(events):
            by_name[name] = by_name.get(name, 0.0) + self_ns
            if COLLECTIVE.match(opcode):
                collective_ns += self_ns
    used = len(devices)
    return {
        "busy_s": sum(busy.values()) / used / 1e9,
        "window_s": (hi - lo) / 1e9,
        "ops_s": {name: ns / used / 1e9 for name, ns in by_name.items()},
        "collective_s": collective_ns / used / 1e9,
        # on the epoch clock where the trace holds the mark
        "slice_epoch": (to_epoch_s(lo), to_epoch_s(hi)) if to_epoch_s else None,
        "idle_gaps": [(to_epoch_s(s), to_epoch_s(e)) for s, e in gaps_of[max(busy, key=busy.get)]]
        if to_epoch_s else [],
    }


def top_ops(reduced: dict, n: int = 10) -> list:
    return [[name, s] for name, s in sorted(
        reduced["ops_s"].items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(reduced: dict, statements: dict, n: int = 10) -> list:
    """Idle seconds by what the host was doing: each idle gap of the busiest
    chip goes to the program's profile span that covers most of it
    (`optimize`, `compile_and_run`, `fetch_results`, ...), to "in a statement,
    outside its spans" when a statement was running but no span of it covers
    the gap, and to "between statements" otherwise."""
    spans, running = [], []
    for st in statements.values():
        if not st["spans"]:
            continue
        spans.extend(st["spans"])
        first = min(t for _, t, _ in st["spans"])
        running.append((first - st["queue_wait_ms"] / 1e3,
                        max(first + st["ms"] / 1e3,
                            max(t + d for _, t, d in st["spans"]))))
    spans.sort(key=lambda sp: sp[2])  # on a tie the innermost span wins
    idle = {}
    for g0, g1 in reduced["idle_gaps"]:
        if g1 - g0 < SHORT_GAP_S:
            best = f"between operations (gaps under {SHORT_GAP_S * 1e6:.0f} us)"
        else:
            best, best_overlap = "between statements", 0.0
            if any(min(g1, r1) > max(g0, r0) for r0, r1 in running):
                best = "in a statement, outside its spans"
            for name, t, d in spans:
                overlap = min(g1, t + d) - max(g0, t)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
        idle[best] = idle.get(best, 0.0) + (g1 - g0)
    return [[name, s] for name, s in sorted(
        idle.items(), key=lambda kv: -kv[1])[:n]]
