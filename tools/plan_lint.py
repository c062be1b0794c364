#!/usr/bin/env python
"""Plan lint CLI: run the static verifier over the full query corpus.

For every TPC-H / SSB / TPC-DS corpus query this executes the query at a
tiny scale factor with `plan_verify_level=strict`, which exercises all
three analysis passes through the production wiring (plan verifier on the
optimized plan, trace auditor + cache-key completeness on every fresh
compile), plus the distribution pass statically per plan. Any error-
severity finding fails the run (exit 1) with the op and the violated
invariant named.

Usage:
  python tools/plan_lint.py --corpus           # all three corpora
  python tools/plan_lint.py --corpus --suite tpch
  python tools/plan_lint.py --corpus --qcache  # + query cache on, 2 runs/query
  python tools/plan_lint.py --sql "select ..." # ad-hoc statement (TPC-H cat)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _suites(which):
    if which in ("tpch", "all"):
        from starrocks_tpu.storage.catalog import tpch_catalog
        from tpch_queries import QUERIES as TPCH

        yield ("tpch", tpch_catalog(sf=0.01),
               {f"q{k}": v for k, v in sorted(TPCH.items())})
    if which in ("ssb", "all"):
        from starrocks_tpu.storage.datagen.ssb import ssb_catalog
        from ssb_queries import FLAT_QUERIES

        yield ("ssb", ssb_catalog(sf=0.005), dict(sorted(FLAT_QUERIES.items())))
    if which in ("tpcds", "all"):
        from starrocks_tpu.storage.datagen.tpcds import tpcds_catalog
        from tests.tpcds_queries import QUERIES as TPCDS

        yield ("tpcds", tpcds_catalog(sf=0.01), dict(sorted(TPCDS.items())))


def lint_corpus(which: str = "all", verbose: bool = False,
                qcache: bool = False) -> int:
    import logging

    from starrocks_tpu import analysis
    from starrocks_tpu.analysis import VerifyError
    from starrocks_tpu.analysis.plan_check import check_distribution
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session

    handler = logging.StreamHandler(sys.stderr)
    analysis.logger.addHandler(handler)
    analysis.logger.setLevel(logging.WARNING)

    config.set("plan_verify_level", "strict")
    if qcache:
        # query cache on: run every query TWICE so both the store path
        # (result-key completeness audit of the real knob read-set) and
        # the validated-hit path run under strict
        config.set("enable_query_cache", True)

    t0 = time.time()
    n_queries = errors = 0
    findings_before = analysis.findings_total()
    for suite, catalog, queries in _suites(which):
        sess = Session(catalog)
        for name, text in queries.items():
            n_queries += 1
            tq = time.time()
            status = "ok"
            try:
                res = sess.sql(text)
                if qcache:
                    res = sess.sql(text)  # the validated-hit path
                # distribution pass, statically (the single-process corpus
                # run never enters the distributed executor)
                analysis.report(
                    check_distribution(res.plan, sess.catalog),
                    res.profile, level="strict", where=f"{suite}/{name}")
            except VerifyError as e:
                errors += 1
                status = "VERIFY-FAIL"
                print(f"{suite}/{name}: {e}", file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — lint shouldn't die mid-run
                errors += 1
                status = f"ERROR {type(e).__name__}: {str(e)[:200]}"
                print(f"{suite}/{name}: {status}", file=sys.stderr)
            if verbose or status != "ok":
                print(f"  {suite}/{name}: {status} "
                      f"({time.time() - tq:.1f}s)", file=sys.stderr)
    summary = {
        "metric": "plan_lint_corpus",
        **({"qcache": True} if qcache else {}),
        "queries": n_queries,
        "strict_failures": errors,
        "findings": analysis.findings_total() - findings_before,
        "seconds": round(time.time() - t0, 1),
    }
    print(json.dumps(summary))
    return 1 if errors else 0


def _rows_equal(a, b):
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        va = list(ra.values()) if isinstance(ra, dict) else list(ra)
        vb = list(rb.values()) if isinstance(rb, dict) else list(rb)
        if va != vb:
            return False
    return True


def lint_fragments(which: str = "all", verbose: bool = False) -> int:
    """Fragment-IR corpus pass: every query runs on the 8-shard mesh in
    fragment mode under strict verification (declared-placement check of
    the annotated plan + trace audit of every fragment program), then
    again through the monolithic pre-IR program — rows must be
    byte-identical (same ops in the same order, not approximately equal).
    """
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
    import logging

    from starrocks_tpu import analysis
    from starrocks_tpu.analysis import VerifyError
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session
    import starrocks_tpu.sql.distributed as D

    handler = logging.StreamHandler(sys.stderr)
    analysis.logger.addHandler(handler)
    analysis.logger.setLevel(logging.WARNING)

    # corpus scale factors are tiny; force the distributed path anyway
    D.SHARD_THRESHOLD_ROWS = 10_000
    D.SHUFFLE_AGG_MIN_GROUPS = 4_000
    config.set("plan_verify_level", "strict")

    t0 = time.time()
    n_queries = errors = mismatches = 0
    tot_frags = tot_exchanges = 0
    for suite, catalog, queries in _suites(which):
        sess = Session(catalog, dist_shards=8)
        for name, text in queries.items():
            n_queries += 1
            status = "ok"
            try:
                config.set("dist_fragments", True)
                rf = sess.sql(text).rows()
                config.set("dist_fragments", False)
                rm = sess.sql(text).rows()
                if not _rows_equal(rf, rm):
                    mismatches += 1
                    status = "ROW-MISMATCH vs monolithic"
                    print(f"{suite}/{name}: {status}", file=sys.stderr)
            except VerifyError as e:
                errors += 1
                status = "VERIFY-FAIL"
                print(f"{suite}/{name}: {e}", file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — lint shouldn't die mid-run
                errors += 1
                status = f"ERROR {type(e).__name__}: {str(e)[:200]}"
                print(f"{suite}/{name}: {status}", file=sys.stderr)
            finally:
                config.set("dist_fragments", True)
            if verbose or status != "ok":
                print(f"  {suite}/{name}: {status}", file=sys.stderr)
        de = sess.__dict__.get("_dist_executor")
        if de is not None:
            for ir, _scans in de._frag_ir_memo.values():
                tot_frags += len(ir.fragments)
                tot_exchanges += len(ir.events)
    summary = {
        "metric": "plan_lint_fragments",
        "queries": n_queries,
        "strict_failures": errors,
        "row_mismatches": mismatches,
        "fragments": tot_frags,
        "exchanges": tot_exchanges,
        "seconds": round(time.time() - t0, 1),
    }
    print(json.dumps(summary))
    return 1 if errors or mismatches else 0


def lint_sql(text: str) -> int:
    from starrocks_tpu.analysis import VerifyError
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import tpch_catalog

    config.set("plan_verify_level", "strict")
    sess = Session(tpch_catalog(sf=0.01))
    try:
        sess.sql(text)
    except VerifyError as e:
        print(e, file=sys.stderr)
        return 1
    print("clean")
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--corpus", action="store_true",
                    help="lint every corpus query")
    ap.add_argument("--suite", default="all",
                    choices=["all", "tpch", "ssb", "tpcds"])
    ap.add_argument("--sql", default=None, help="lint one ad-hoc statement")
    ap.add_argument("--qcache", action="store_true",
                    help="enable the query cache and run each corpus query "
                         "twice: strict-audits the result cache key (store "
                         "path) and the validated-hit path")
    ap.add_argument("--fragments", action="store_true",
                    help="fragment-IR corpus pass on the 8-shard mesh: "
                         "strict declared-placement verification plus "
                         "byte-identity against the monolithic pre-IR "
                         "program")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    if args.sql:
        return lint_sql(args.sql)
    if args.fragments:
        return lint_fragments(args.suite, args.verbose)
    if args.corpus:
        return lint_corpus(args.suite, args.verbose, qcache=args.qcache)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
