#!/usr/bin/env python3
"""Schema check for the BENCH_*.json artifacts (CI bench smoke jobs).

The benchmarks (benchmarks/common.py:write_bench_section) merge one
``{meta, rows}`` section per bench into a BENCH json. CI runs
``benchmarks/bench_solver_swap.py --quick`` (→ BENCH_solver.json) and
``benchmarks/bench_batched.py --quick`` (→ BENCH_batch.json) under
``INTERPRET=1`` and then this script, so a bench regression (missing
section, empty rows, dropped telemetry keys) fails in PR instead of
rotting silently.

Required row keys are per-section (``SECTION_ROW_KEYS``); unknown sections
use the solver-bench default set.

Usage:
    python tools/check_bench_schema.py BENCH_solver.json
    python tools/check_bench_schema.py BENCH_solver.json --section bench_solver_swap
    python tools/check_bench_schema.py BENCH_batch.json --section bench_batched
    python tools/check_bench_schema.py BENCH_serve.json --section bench_serve
    python tools/check_bench_schema.py BENCH_dist.json --section bench_dist
    python tools/check_bench_schema.py BENCH_solver.json --section bench_dpp_family
    python tools/check_bench_schema.py BENCH_dist.json --section bench_solve_dtype
    python tools/check_bench_schema.py BENCH_update.json --section bench_update
"""

from __future__ import annotations

import argparse
import json
import sys

REQUIRED_ROW_KEYS = {
    "dataset",
    "rule",
    "gap_check_cadence",
    "gram_step_frac",
    "max_beta_err",
    "num_lambdas",
    "solver_iters",
    "speedup_vs_unscreened",
    "wall_time_s",
}

BATCH_ROW_KEYS = {
    "dataset",
    "rule",
    "solver",
    "backend",
    "batch_size",
    "num_lambdas",
    "wall_time_s",
    "seq_wall_time_s",
    "speedup_vs_sequential",
    "x_passes_per_query",
    "masks_identical",
    "max_beta_err",
    "beta_err_tol",
}

SERVE_ROW_KEYS = {
    "dataset",
    "rule",
    "solver",
    "backend",
    "mode",
    "b_max",
    "num_queries",
    "num_lambdas",
    "queries_per_sec",
    "p50_latency_s",
    "p99_latency_s",
    "wall_time_s",
    "n_dispatches",
    "mean_batch_fill",
    "deadline_dispatch_frac",
    "masks_identical",
}

DIST_ROW_KEYS = {
    "dataset",
    "mesh",
    "backend",
    "arm",
    "num_lambdas",
    "wall_time_s",
    "speedup_vs_open_coded",
    "masks_identical",
    "screen_dtype",
    "bytes_per_screen",
}

DPP_FAMILY_ROW_KEYS = {
    "dataset",
    "rule",
    "screen_dtype",
    "num_lambdas",
    "rejection_rate",
    "speedup_vs_unscreened",
    "wall_time_s",
    "max_beta_err",
}

SOLVE_DTYPE_ROW_KEYS = {
    "dataset",
    "solver",
    "solve_dtype",
    "effective_dtype",
    "tol",
    "gap_check_cadence",
    "solve_iters",
    "lo_iters",
    "bytes_per_solve_iter",
    "byte_ratio_vs_f32",
    "max_beta_err",
    "beta_err_tol",
    "wall_time_s",
    "converged",
}

UPDATE_ROW_KEYS = {
    "dataset",
    "backend",
    "round",
    "churn_frac",
    "n_add",
    "n_drop",
    "version",
    "update_time_s",
    "refit_time_s",
    "speedup_vs_refit",
    "argmax_rescans",
    "masks_identical",
    "max_beta_err",
    "beta_err_tol",
}

SECTION_ROW_KEYS = {
    "bench_batched": BATCH_ROW_KEYS,
    "bench_serve": SERVE_ROW_KEYS,
    "bench_dist": DIST_ROW_KEYS,
    "bench_dpp_family": DPP_FAMILY_ROW_KEYS,
    "bench_solve_dtype": SOLVE_DTYPE_ROW_KEYS,
    "bench_update": UPDATE_ROW_KEYS,
}


def check(path: str, sections: list[str]) -> int:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"{path}: unreadable ({e})")
        return 1

    if not isinstance(doc.get("sections"), dict) or not doc["sections"]:
        print(f"{path}: missing or empty top-level 'sections' dict")
        return 1

    bad = 0
    wanted = sections or sorted(doc["sections"])
    for name in wanted:
        sec = doc["sections"].get(name)
        if sec is None:
            print(f"{path}: section {name!r} missing "
                  f"(have: {sorted(doc['sections'])})")
            bad += 1
            continue
        for key in ("meta", "rows"):
            if key not in sec:
                print(f"{path}: section {name!r} missing {key!r}")
                bad += 1
        rows = sec.get("rows")
        if not isinstance(rows, list) or not rows:
            print(f"{path}: section {name!r} has no rows")
            bad += 1
            continue
        required = SECTION_ROW_KEYS.get(name, REQUIRED_ROW_KEYS)
        for i, row in enumerate(rows):
            missing = required - set(row)
            if missing:
                print(f"{path}: {name} row {i} missing keys "
                      f"{sorted(missing)}")
                bad += 1
    if bad:
        print(f"{bad} schema violation(s)")
        return 1
    counts = ", ".join(
        f"{n}={len(doc['sections'][n]['rows'])} rows" for n in wanted)
    print(f"{path}: schema OK ({counts})")
    return 0


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="BENCH_solver.json")
    ap.add_argument("--section", action="append", default=[],
                    help="require this section (repeatable); default: all")
    args = ap.parse_args(argv)
    return check(args.path, args.section)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
