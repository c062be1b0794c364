"""What several metric readers share: the window's sample of the program's
statement profiles, and how much of each statement lies in the traced slice."""

from __future__ import annotations

from . import stats


def window_statements(run) -> list:
    """Profile entries of statements that finished in the window (the
    program retains its last 64; a traced run also polls them during the
    slice), without those of the warm-up."""
    return [st for qid, st in run.statements.items()
            if qid not in run.warm["statements"]]


def span_median_ms(run, name: str) -> float | None:
    """Median over the sampled statements of the seconds each spent in its
    profile spans called `name`."""
    per_statement = [sum(d for n, _, d in st["spans"] if n == name)
                     for st in window_statements(run)]
    per_statement = [s for s in per_statement if s > 0]
    return stats.median(per_statement) * 1e3 if per_statement else None


def statements_in_slice(run) -> list:
    """[(variant index, share of the statement inside the traced slice)]:
    a statement counts by the part of its client-side latency that overlaps
    the slice, so a 5 s slice inside a 35 s join is a seventh of a statement."""
    if not run.trace or run.trace["slice_epoch"] is None:
        return []
    lo, hi = (t - run.window["epoch_start"] for t in run.trace["slice_epoch"])
    out = []
    for vi, _, start, ms in run.window["records"]:
        overlap = min(hi, start + ms / 1e3) - max(lo, start)
        if overlap > 0 and ms > 0:
            out.append((vi, overlap / (ms / 1e3)))
    return out
