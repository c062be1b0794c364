"""The system under test, as a deployment has it: the configuration's tables
in a catalog, one `Session` (with `dist_shards` where the configuration spans
chips), one `ServingTier` and the MySQL door on an ephemeral port, all threads
of this process, engine defaults untouched. Plus the only things the benchmark
reads from the program besides answers: its counters, its per-statement
profile spans, what its device cache holds, and the devices' memory
statistics."""

from __future__ import annotations

import hashlib
import threading

from .cells import load_module


class GeneratorGuard(Exception):
    """The generated tables are not the ones the configuration states."""


def make_tables(config: dict, root: str, scale: float):
    """(tables, generator module): the configuration's tables from its
    `data_seed` by its generator, checked against the row counts and column
    digests the configuration file states, so that a change to the generator
    or to numpy's streams cannot move the yardstick in silence. `scale` is the
    configuration's own except in the CPU rehearsal, which skips the check."""
    gen = load_module(root, "datagen", config["generator"])
    tables = gen.generate(scale, config["data_seed"])
    if scale != config["scale_factor"]:
        return tables, gen
    for name, want in config["expected_rows"].items():
        if tables[name].num_rows != want:
            raise GeneratorGuard(f"{name}: {tables[name].num_rows} rows, "
                                 f"configuration expects {want}")
    for column, want in config["expected_sha256"].items():
        table, name = column.split(".")
        got = hashlib.sha256(tables[table].arrays[name].tobytes()).hexdigest()
        if got != want:
            raise GeneratorGuard(f"{column}: sha256 {got}, configuration "
                                 f"expects {want}")
    return tables, gen


class System:
    def __init__(self, config: dict, tables: dict, gen):
        from starrocks_tpu.runtime.mysql_service import MySQLServer
        from starrocks_tpu.runtime.serving import ServingTier
        from starrocks_tpu.runtime.session import Session
        from starrocks_tpu.storage.catalog import Catalog

        catalog = Catalog()
        for name, table in tables.items():
            catalog.register(name, table, gen.UNIQUE_KEYS.get(name, ()),
                             gen.DISTRIBUTION.get(name, ()))
        self.session = Session(catalog, dist_shards=config["dist_shards"])
        self.tier = ServingTier(self.session)
        self.server = MySQLServer(self.session, port=0, tier=self.tier).start()
        self.port = self.server.port

    def close(self):
        self.server.shutdown()

    @staticmethod
    def counters() -> dict:
        from starrocks_tpu.runtime.metrics import metrics

        return {name: value for name, (kind, value)
                in metrics.snapshot_values().items() if kind == "counter"}

    @staticmethod
    def statements() -> dict:
        """query_id -> the retained profile entry of a finished statement,
        with its spans flattened to (name, epoch seconds, seconds). The
        program keeps the last 64 (`profile_history_size`): that is the
        sample, and the knob is not raised."""
        from starrocks_tpu.runtime.profile import PROFILE_MANAGER

        def spans(node):
            yield from (tuple(s) for s in node.get("spans", ()))
            for child in node.get("children", ()):
                yield from spans(child)

        return {e["query_id"]: {
            "sql": e["sql"], "ms": e["ms"], "queue_wait_ms": e["queue_wait_ms"],
            "spans": list(spans(e["profile"] or {}))}
            for e in PROFILE_MANAGER.snapshot()}

    def resident_bytes(self) -> int:
        return sum(a.nbytes for _, a in self.session.cache.resident_arrays())


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peaks(chips: int) -> list:
    """Per device used by the cell: `memory_stats()` as the backend gives it
    (None on a backend that keeps none, as the CPU does)."""
    import jax

    return [d.memory_stats() for d in jax.devices()[:chips]]


def peak_bytes(stats: dict | None) -> int | None:
    """Peak HBM of one device: `peak_bytes_in_use` (live buffers: resident
    columns, results) plus `peak_bytes_reserved` where the backend reports it
    (a TPU reserves a program's temporaries apart from bytes_in_use; PR 21
    read 3.7 GB + 7.4 GB after Q3 at SF10)."""
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


class SpanPoller:
    """Collects the program's retained statement profiles while a traced
    slice runs: at a dashboard's rate the last 64 statements are well under a
    second, so one reading after the window would miss the slice."""

    def __init__(self, interval_s: float = 0.2):
        self.seen: dict = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(interval_s,))
        self._thread.start()

    def _run(self, interval_s: float):
        while not self._stop.wait(interval_s):
            self.seen.update(System.statements())

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join()
        self.seen.update(System.statements())
        return self.seen
