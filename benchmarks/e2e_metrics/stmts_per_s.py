"""Statements completed per second at the cell's fixed number of connections.

In a closed loop: the connections over the median time from one completion
to the next on the same connection, all connections pooled. A count over the
window would step by a whole statement per connection (1% of a 10 s window at
8 connections), and a mean would swing with a single stall: in PR 22, three of
fifteen dashboard runs held one 120 ms stall, which cost every connection one
statement and moved a mean-based rate by 0.9% against a run-to-run spread of
0.001% without it. The tail such a stall makes is `lat_p95_ms`'s to show.

In an open loop: statements completed over the time to the last completion."""

from benchmarks.harness import stats

META = {"unit": "1/s", "better": "higher", "source": "host_clock"}


def compute(run):
    records = run.window["records"]
    if run.cell.traffic["loop"] != "closed":
        return len(records) / run.window["elapsed_s"] if records else None
    done = {}
    for _, conn, start, ms in records:
        done.setdefault(conn, []).append(start + ms / 1e3)
    cycles = [b - a for times in done.values()
              for a, b in zip(sorted(times), sorted(times)[1:])]
    return len(done) / stats.median(cycles) if cycles else None
