#!/usr/bin/env python3
"""One run of one benchmark cell, from the client's side of the MySQL door.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip(s): it makes the configuration's tables (from the
configuration's own `data_seed`; `--seed` orders the traffic), builds catalog, Session, ServingTier and MySQLServer (threads of
this process, engine defaults untouched), and starts the load generator as a
child that never imports JAX (`harness/client.py`). Then:

  1. warm-up: the cell's statements in cycles until one whole cycle compiles
     nothing (`sr_tpu_program_compiles_total` + `capacity_recompiles_total`
     unchanged; a join is warm from its third send), at most MAX_WARM_CYCLES;
  2. window: the cell's loop for `--seconds`; nothing starts after the
     deadline, what is in flight finishes and counts;
  3. after it: counters, memory statistics, and every statement variant's rows
     (last warm-up send and last window send) against its pandas reference.

Exits non-zero, with no result line, unless `jax.default_backend()` is "tpu"
and JAX has the chips the cell asks for; no flag admits a CPU. The last line
of stdout is the result object (correct, attempted, failed, metrics, device,
and with `--trace 1` breakdown); everything else is on earlier lines. With
`--trace 0` the metrics are the cell's end-to-end ones, with `--trace 1` its
per-layer ones, read from a `jax.profiler` trace of a slice of the window.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up runs from here to the window's start

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MAX_WARM_CYCLES = 4
COMPILE_COUNTERS = ("sr_tpu_program_compiles_total",
                    "sr_tpu_capacity_recompiles_total")


class CellFailed(Exception):
    """The run cannot give a result: no line is printed and the exit is 1."""


class Client:
    """The load generator child and its line protocol."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmarks", "harness",
                                          "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def send(self, **cmd):
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise CellFailed(
                f"load generator ended (exit {self.proc.wait()})")
        return json.loads(line)

    def close(self):
        if self.proc.poll() is None:
            try:
                self.send(op="quit")
            except OSError:
                pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def _compiles(delta: dict) -> int:
    return int(sum(delta.get(k, 0) for k in COMPILE_COUNTERS))


class TracedSlice:
    """A `jax.profiler` trace of the start of the window: device operations
    plus a host mark that carries the epoch clock; the Python tracer stays
    off (it slows the host path). The profiler drops a program that was
    already running when the trace began, so the trace begins before the
    window's first statement is sent, and it lasts `seconds` or until one
    statement has finished in it, whichever is later (a 35 s join outlasts
    any fixed slice). While it runs, the program's statement profiles are
    polled, since the program keeps only the last 64."""

    MAX_S = 120.0

    def __init__(self, trace_dir: str, seconds: float):
        import jax

        from benchmarks.harness import sut, xplane

        self.seconds = seconds
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.known = set(sut.System.statements())
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.mark_name(time.time_ns())):
            pass
        self.t0 = time.monotonic()
        self.poller = sut.SpanPoller()

    def finish(self) -> dict:
        """Wait the slice out, stop the trace, return the polled profiles."""
        import jax

        try:
            while True:
                elapsed = time.monotonic() - self.t0
                done = elapsed >= self.seconds and set(self.poller.seen) - self.known
                if done or elapsed >= self.MAX_S:
                    break
                time.sleep(0.05)
        finally:
            seen = self.poller.stop()
            jax.profiler.stop_trace()
        return seen


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             scale: float | None = None) -> dict:
    """The run, on whatever backend JAX has: `main()` lets only a TPU
    through, `tests/test_harness.py` calls this on CPU with a small `scale`
    in place of the configuration's scale factor. Prints detail lines as it
    goes and returns the result object."""
    from benchmarks.harness import cells, compare, reference, stats, sut, xplane

    cell = cells.Cell(ROOT, workload)
    traffic = cell.traffic
    import starrocks_tpu  # noqa: F401 — x64 and the compile cache, before any trace
    import jax

    device = sut.device_info()
    print(f"cell {workload} seed={seed} seconds={seconds:g} traced={int(traced)} "
          f"device={json.dumps(device)} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}")

    t0 = time.monotonic()
    sf = cell.config["scale_factor"] if scale is None else scale
    tables, gen = sut.make_tables(cell.config, ROOT, sf)
    print(f"tables sf={sf:g} seconds={time.monotonic() - t0:.1f} rows="
          + json.dumps({n: t.num_rows for n, t in tables.items()}))

    system = sut.System(cell.config, tables, gen)
    client = Client()
    run = types.SimpleNamespace(
        cell=cell, seconds=seconds, tables=tables, device=device, trace={})
    try:
        client.send(op="connect", port=system.port, clients=traffic["clients"],
                    variants=[{"name": v["name"], "sql": v["sql"]}
                              for v in cell.variants])
        client.reply()

        # --- warm-up -------------------------------------------------------
        t_warm = time.monotonic()
        digests = [set() for _ in cell.variants]
        warm_statements = {}
        for cycle in range(1, MAX_WARM_CYCLES + 1):
            before = system.counters()
            t0 = time.monotonic()
            client.send(op="warm")
            warm = client.reply()
            compiled = _compiles(_delta(system.counters(), before))
            warm_statements.update(system.statements())
            print(f"warm cycle={cycle} seconds={time.monotonic() - t0:.3f} "
                  f"compiled={compiled} errors={len(warm['errors'])}")
            if warm["errors"]:
                raise CellFailed(f"warm-up statement failed: {warm['errors'][:3]}")
            for seen, new in zip(digests, warm["digests"]):
                seen.update(new)
            if compiled == 0:
                break
        else:
            raise CellFailed(f"still compiling after {MAX_WARM_CYCLES} warm-up "
                             "cycles")
        run.warm = {"cycles": cycle, "seconds": time.monotonic() - t_warm,
                    "statements": warm_statements}

        # --- window --------------------------------------------------------
        trace_dir = os.path.join(ROOT, "benchmarks", ".traces", workload)
        slice_ = TracedSlice(trace_dir, min(traffic.get("trace_seconds", 5.0),
                                            seconds)) if traced else None
        before = system.counters()
        run.setup_s = time.monotonic() - T_START
        client.send(op="window", seconds=seconds, loop=traffic["loop"],
                    order=traffic["order"], seed=seed,
                    zipf_s=traffic.get("zipf_s", 1.0),
                    rate_per_s=traffic.get("rate_per_s", 0.0),
                    min_cycles=traffic.get("min_cycles", 0))
        polled = slice_.finish() if traced else {}
        run.window = client.reply()
        run.counters = _delta(system.counters(), before)
        run.statements = {**polled, **system.statements()}
        run.memory = sut.memory_peaks(cell.chips)
        peaks = [p for p in map(sut.peak_bytes, run.memory) if p is not None]
        run.peak_bytes = max(peaks) if peaks else None
        run.resident_bytes = system.resident_bytes()
    finally:
        client.close()
        system.close()

    # --- what the window holds ---------------------------------------------
    w = run.window
    by_template = {t["name"]: [] for t in cell.templates}
    for vi, _, _, ms in w["records"]:
        by_template[cell.variants[vi]["template"]].append(ms)
    run.latencies = by_template
    attempted = len(w["records"]) + len(w["errors"])
    for name, ms in by_template.items():
        if ms:
            print(f"template {name} n={len(ms)} median_ms={stats.median(ms):.3f} "
                  f"p95_ms={stats.percentile(ms, 0.95):.3f} "
                  f"p99_ms={stats.percentile(ms, 0.99):.3f} max_ms={max(ms):.3f}")
    print(f"window statements={attempted} failed={len(w['errors'])} "
          f"elapsed_s={w['elapsed_s']:.3f} "
          f"client_cpu_share={w['client_cpu_share']:.3f} "
          f"generator_late_ms={w['generator_late_ms']:.3f} "
          f"compiled={_compiles(run.counters)}")
    for e in w["errors"][:5]:
        print(f"FAILED statement {e}")

    # --- the reference, outside everything timed ---------------------------
    t0 = time.monotonic()
    problems = []
    for seen, new in zip(digests, w["digests"]):
        seen.update(new)
    ref = reference.Reference(cell, tables, sf)
    for v, seen, warm_rows, window_rows in zip(
            cell.variants, digests, warm["last_rows"], w["last_rows"]):
        expected = ref.expected(v)
        for when, rows in (("warm-up", warm_rows), ("window", window_rows)):
            if rows is None:
                # a variant the window's draw never picked has no window rows
                if when == "warm-up":
                    problems.append(f"{v['name']}: never answered in warm-up")
                continue
            diff = compare.first_mismatch(rows, expected, v["oracle"].KEY)
            if diff:
                problems.append(f"{v['name']} ({when}): {diff}")
        if len(seen) > 1:
            problems.append(f"{v['name']}: {len(seen)} different answers "
                            "across sends")
    print(f"reference seconds={time.monotonic() - t0:.1f} "
          f"variants={len(cell.variants)} problems={len(problems)}")
    for p in problems:
        print(f"WRONG {p}")

    # --- metrics -------------------------------------------------------------
    if traced:
        run.trace = xplane.reduce(
            xplane.read(xplane.find_xplane(trace_dir)), cell.chips)
    for i, stats_i in enumerate(run.memory):
        print(f"memory device={i} {json.dumps(stats_i)}")
    metrics = {}
    for entry, reader in cell.readers(traced):
        value = reader.compute(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = dict(device, memory_peak_bytes=run.peak_bytes)
    result = {"correct": not problems, "attempted": attempted,
              "failed": len(w["errors"]), "metrics": metrics, "device": device}
    if traced and run.trace:
        device.update(busy_s=run.trace["busy_s"], window_s=run.trace["window_s"])
        result["breakdown"] = {
            "device_ops": xplane.top_ops(run.trace),
            "idle_gaps": xplane.attribute_gaps(run.trace, run.statements)}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.harness import cells

    chips = cells.Cell(ROOT, args.workload).chips
    import starrocks_tpu  # noqa: F401
    import jax

    backend, n = jax.default_backend(), len(jax.devices())
    if backend != "tpu" or n < chips:
        print(f"benchmarks/run.py: {args.workload} needs {chips} TPU chip(s), "
              f"but JAX's default backend is {backend!r} with {n} device(s)",
              file=sys.stderr)
        return 1
    # a hung chip becomes a traceback and a non-zero exit inside the limit
    faulthandler.dump_traceback_later(1150, exit=True)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except CellFailed as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    if bool(args.trace) and "busy_s" not in result["device"]:
        print("benchmarks/run.py: the trace holds no device operation",
              file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
