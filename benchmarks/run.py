"""Benchmark driver: one section per paper table/figure + the roofline table,
the xla-vs-pallas backend comparison, the per-op GEMM-Ops section, and the
serving (continuous vs static batching) section.

Prints ``name,us_per_call,derived`` CSV and, with ``--smoke`` (or an
explicit ``--json PATH``), writes the same rows machine-readably to
``BENCH_smoke.json`` — the artifact CI uploads so the bench trajectory is
diffable across commits. ``--smoke`` runs the backend comparison, GEMM-Ops
and serving sections on a reduced shape set (the CI nightly perf canary).
"""
from __future__ import annotations

import argparse

import jax

from benchmarks import gemm_backends, gemm_ops, paper_figs, serving
from benchmarks.common import Rows
from benchmarks.roofline_table import roofline_rows
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced run: backend/gemm-ops/serving sections, small shapes",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable rows (default: BENCH_smoke.json "
        "when --smoke is set)",
    )
    ap.add_argument(
        "--compare", default=None, metavar="BASELINE",
        help="regression gate: fail when any tok_s/utilization field a "
        "baseline row carries drops >15%% below the committed value "
        "(benchmarks/baseline_smoke.json in CI)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    rows = Rows()
    print("name,us_per_call,derived")
    if args.smoke:
        gemm_backends.bench_backends(rows, smoke=True)
        gemm_ops.bench_gemm_ops(rows, smoke=True)
        serving.bench_serving(rows, smoke=True)
    else:
        for bench in paper_figs.ALL:
            bench(rows)
        roofline_rows(rows)
        gemm_backends.bench_backends(rows, smoke=False)
        gemm_ops.bench_gemm_ops(rows, smoke=False)
        serving.bench_serving(rows, smoke=False)
    rows.emit()

    json_path = args.json or ("BENCH_smoke.json" if args.smoke else None)
    if json_path:
        rows.write_json(json_path, meta={
            "smoke": args.smoke, "platform": jax.default_backend(),
        })
        print(f"# wrote {json_path}")
    if args.compare:
        from benchmarks.common import compare_rows, load_rows_json

        failures = compare_rows(rows.to_json(), load_rows_json(args.compare),
                                label=args.compare)
        if failures:
            for f in failures:
                print(f"# REGRESSION {f}")
            raise SystemExit(
                f"{len(failures)} bench regression(s) vs {args.compare}"
            )
        print(f"# bench gate passed vs {args.compare}")


if __name__ == "__main__":
    main()
