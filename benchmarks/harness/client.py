"""The load generator: a process of its own that never imports JAX.

It speaks the MySQL wire protocol over plain sockets (client side copied
from tests/test_mysql_protocol.py::MiniMySQLClient — the yardstick may not
change when a test does), so it shares neither the server's interpreter lock
nor its chip. `run.py` starts it with `python harness/client.py` and drives it
with one JSON object per line on stdin; every command is answered by one JSON
line on stdout:

    {"op": "connect", "port": p, "clients": n, "variants": [{"name", "sql"}]}
    {"op": "warm"}                    every variant once on connection 0, then
                                      once on each other connection at once
    {"op": "window", "seconds": s, "loop": "closed"|"open", "order":
     "cycle"|"zipf", "zipf_s": 1.0, "rate_per_s": r, "min_cycles": k,
     "seed": n}
    {"op": "quit"}

A statement's latency runs from the first byte sent to the last row byte
read. In an open loop it runs from the time the statement was due, so a
stall counts against every statement behind it. No statement starts after
the window's deadline; those in flight finish and count. Where a statement
is longer than the window, `min_cycles` keeps a closed loop going until each
connection has sent its whole list that many times.
"""

from __future__ import annotations

import hashlib
import json
import queue
import random
import socket
import struct
import sys
import threading
import time

# a cold join statement compiles for minutes inside its first warm-up send
WARM_TIMEOUT_S = 1100.0
WINDOW_TIMEOUT_S = 300.0


class MySQLClient:
    """Just enough of the client side of the MySQL protocol: protocol-10
    handshake as root with an empty password, COM_QUERY, text resultsets."""

    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        self._handshake()

    def _read_n(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("server closed mid-packet")
            buf += chunk
        return bytes(buf)

    def _read_packet(self) -> bytes:
        head = self._read_n(4)
        (ln,) = struct.unpack("<I", head[:3] + b"\x00")
        self.seq = (head[3] + 1) & 0xFF
        return self._read_n(ln)

    def _send_packet(self, payload: bytes):
        self.sock.sendall(
            struct.pack("<I", len(payload))[:3] + bytes([self.seq]) + payload)
        self.seq = (self.seq + 1) & 0xFF

    @staticmethod
    def _lenenc(buf: bytes, pos: int):
        c = buf[pos]
        if c < 0xFB:
            return c, pos + 1
        if c == 0xFC:
            return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
        if c == 0xFD:
            return struct.unpack("<I", buf[pos + 1:pos + 4] + b"\x00")[0], pos + 4
        return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9

    def _handshake(self):
        greet = self._read_packet()
        if greet[0] != 0x0A:
            raise ConnectionError(f"protocol version {greet[0]}, not 10")
        # HandshakeResponse41: caps, max packet, charset, 23 zeros, user,
        # empty auth token (root has no password), database
        caps = 0x0200 | 0x8000 | 0x0008  # PROTOCOL_41|SECURE_CONN|WITH_DB
        self._send_packet(
            struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
            + bytes([45]) + b"\x00" * 23 + b"root\x00" + b"\x00"
            + b"default\x00")
        ok = self._read_packet()
        if ok[0] != 0x00:
            raise ConnectionError(f"handshake refused: {ok[:40]!r}")

    def query(self, sql: str) -> list:
        """Rows of a resultset as tuples of text cells (None = NULL). An
        error packet raises RuntimeError."""
        self.seq = 0
        self._send_packet(b"\x03" + sql.encode())
        first = self._read_packet()
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise RuntimeError(
                f"ERR {code}: {first[9:].decode('utf-8', 'replace')}")
        if first[0] == 0x00:
            return []
        ncols, _ = self._lenenc(first, 0)
        for _ in range(ncols):
            self._read_packet()
        eof = self._read_packet()
        if eof[0] != 0xFE:
            raise ConnectionError("expected EOF after column definitions")
        rows = []
        while True:
            p = self._read_packet()
            if p[0] == 0xFE and len(p) < 9:
                return rows
            pos, row = 0, []
            while pos < len(p):
                if p[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    n, pos = self._lenenc(p, pos)
                    row.append(p[pos:pos + n].decode())
                    pos += n
            rows.append(tuple(row))

    def close(self):
        self.sock.close()


def _digest(rows: list) -> str:
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


class Fleet:
    """The cell's connections and statement variants, and what the sends
    returned: per variant the digests of every answer and the rows of the
    last one."""

    def __init__(self, port: int, clients: int, variants: list):
        self.conns = [MySQLClient("127.0.0.1", port) for _ in range(clients)]
        self.variants = variants
        self.lock = threading.Lock()
        self.reset()

    def reset(self):
        self.records = []  # (variant, connection, start offset s, latency ms)
        self.errors = []
        self.digests = [set() for _ in self.variants]
        self.last_rows = [None] * len(self.variants)

    def send(self, ci: int, vi: int, t_zero: float,
             t_due: float | None = None) -> bool:
        """One statement on connection `ci`. False when the connection is of
        no more use (a timeout or a closed socket, not an error packet)."""
        t0 = time.perf_counter()
        try:
            rows = self.conns[ci].query(self.variants[vi]["sql"])
        except (RuntimeError, OSError) as e:
            with self.lock:
                self.errors.append(
                    f"{self.variants[vi]['name']}: {type(e).__name__}: {e}"[:300])
            return isinstance(e, RuntimeError)
        t1 = time.perf_counter()
        start = t0 if t_due is None else t_due
        d = _digest(rows)
        with self.lock:
            self.records.append((vi, ci, start - t_zero, (t1 - start) * 1e3))
            self.digests[vi].add(d)
            self.last_rows[vi] = rows
        return True

    def _start(self, connections, fn) -> list:
        """`fn(ci)` on a thread of its own for each connection."""
        threads = [threading.Thread(target=fn, args=(ci,)) for ci in connections]
        for t in threads:
            t.start()
        return threads

    def _begin(self, timeout_s: float) -> float:
        self.reset()
        for c in self.conns:
            c.sock.settimeout(timeout_s)
        return time.perf_counter()

    def warm(self) -> dict:
        t_zero = self._begin(WARM_TIMEOUT_S)

        def all_variants(ci):
            for vi in range(len(self.variants)):
                if not self.send(ci, vi, t_zero):
                    return

        all_variants(0)
        for t in self._start(range(1, len(self.conns)), all_variants):
            t.join()
        return self._report(time.perf_counter() - t_zero)

    def window(self, seconds: float, loop: str, order: str, seed: int,
               zipf_s: float = 1.0, rate_per_s: float = 0.0,
               min_cycles: int = 0) -> dict:
        n = len(self.variants)
        weights = [1.0 / (r + 1) ** zipf_s for r in range(n)]

        def picker(stream: int):
            if order == "cycle":
                return lambda k: k % n
            rng = random.Random(seed * 1000003 + stream)
            return lambda k: rng.choices(range(n), weights)[0]

        epoch0, cpu0 = time.time(), time.process_time()
        t_zero = self._begin(WINDOW_TIMEOUT_S)
        deadline = t_zero + seconds
        late_ms = []
        if loop == "closed":
            def closed(ci):
                pick, k = picker(ci), 0
                while ((time.perf_counter() < deadline or k < min_cycles * n)
                       and self.send(ci, pick(k), t_zero)):
                    k += 1

            workers = self._start(range(len(self.conns)), closed)
        else:
            # arrivals are a Poisson stream at rate_per_s, drawn from the
            # seed before the window; the connections are a pool that takes
            # each statement when it is due, or as soon after as one is free
            rng, pick = random.Random(seed), picker(0)
            due, t = [], 0.0
            while (t := t + rng.expovariate(rate_per_s)) < seconds:
                due.append((t_zero + t, pick(len(due))))
            jobs: queue.SimpleQueue = queue.SimpleQueue()

            def pooled(ci):
                alive = True
                while (job := jobs.get()) is not None:
                    late_ms.append((time.perf_counter() - job[0]) * 1e3)
                    if alive:
                        alive = self.send(ci, job[1], t_zero, t_due=job[0])
                    else:
                        with self.lock:
                            self.errors.append("connection lost: not sent")

            workers = self._start(range(len(self.conns)), pooled)
            for job in due:
                time.sleep(max(0.0, job[0] - time.perf_counter()))
                jobs.put(job)
            for _ in workers:
                jobs.put(None)
        for w in workers:
            w.join()
        elapsed = time.perf_counter() - t_zero
        return dict(
            self._report(elapsed), epoch_start=epoch0, seconds=seconds,
            client_cpu_share=(time.process_time() - cpu0) / elapsed,
            generator_late_ms=sorted(late_ms)[len(late_ms) // 2] if late_ms else 0.0)

    def _report(self, elapsed: float) -> dict:
        return {"records": self.records, "errors": self.errors,
                "elapsed_s": elapsed,
                "digests": [sorted(d) for d in self.digests],
                "last_rows": self.last_rows}

    def close(self):
        for c in self.conns:
            c.close()


def main() -> int:
    fleet = None
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            op = cmd.pop("op")
            if op == "connect":
                fleet = Fleet(cmd["port"], cmd["clients"], cmd["variants"])
                reply = {"ok": True}
            elif op == "warm":
                reply = fleet.warm()
            elif op == "window":
                reply = fleet.window(**cmd)
            elif op == "quit":
                return 0
            else:
                raise ValueError(f"unknown op {op!r}")
            sys.stdout.write(json.dumps(reply) + "\n")
            sys.stdout.flush()
        return 0
    finally:
        if fleet is not None:
            fleet.close()


if __name__ == "__main__":
    sys.exit(main())
