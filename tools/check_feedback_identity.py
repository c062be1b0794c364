#!/usr/bin/env python
"""Corpus A/B: learned plan feedback must not flip any corpus plan.

Executes every corpus query once with plan_feedback on (populating the
FeedbackStore with real observations), then re-optimizes each query twice
— once with the recorded entry, once with feedback=None — and compares
the optimized-plan reprs.  Plan identity + deterministic execution implies
row byte-identity, so this is the cheap form of the "all corpus queries
byte-identical to the feedback-off path" acceptance gate: one execution
pass instead of three.

Exit 0 iff no query's plan diverges under its learned entry.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> int:
    from plan_lint import _suites

    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.feedback import plan_fingerprint
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.sql.analyzer import Analyzer
    from starrocks_tpu.sql.optimizer import optimize
    from starrocks_tpu.sql.parser import parse

    config.set("plan_feedback", True)

    t0 = time.time()
    n = with_entry = diverged = errors = 0
    for suite, catalog, queries in _suites("all"):
        sess = Session(catalog)
        for name, text in queries.items():
            n += 1
            try:
                sess.sql(text)  # records observations into the store
            except Exception as e:  # noqa: BLE001 — keep sweeping
                errors += 1
                print(f"{suite}/{name}: EXEC-ERROR {type(e).__name__}: "
                      f"{str(e)[:160]}", file=sys.stderr)
                continue
            try:
                plan = Analyzer(sess.catalog).analyze(parse(text))
                fb = sess.cache.feedback.consult(
                    plan_fingerprint(plan), sess.catalog)
                if fb is None:
                    continue
                with_entry += 1
                on = repr(optimize(plan, sess.catalog, fb))
                off = repr(optimize(plan, sess.catalog, None))
                if on != off:
                    diverged += 1
                    print(f"{suite}/{name}: PLAN DIVERGED under feedback",
                          file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — keep sweeping
                errors += 1
                print(f"{suite}/{name}: CHECK-ERROR {type(e).__name__}: "
                      f"{str(e)[:160]}", file=sys.stderr)
    print(json.dumps({
        "metric": "feedback_plan_identity",
        "queries": n,
        "with_feedback_entry": with_entry,
        "plans_diverged": diverged,
        "errors": errors,
        "seconds": round(time.time() - t0, 1),
    }))
    return 1 if (diverged or errors) else 0


if __name__ == "__main__":
    sys.exit(main())
