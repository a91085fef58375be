"""Readings that the check's limits are set from, on the chip, in one
process (the compiled programs are shared between seeds):

* the program: whole runs of a cell (a short window at the cell's own
  load) on many seeds, each printing the check's worst readings;
* the control: for a few seeds, the configuration's plain reference
  (its ``reference`` module) put in the program's place at the precision
  below the configuration's (``"high"``), on queries drawn as the cell's
  runs draw them, read by the same check. Every answer it gives is read,
  whether its own solver claimed the gap or stopped at ``max_iter``.

    python bench/calibrate.py --workload mnist.upper.sat --seconds 8 \\
        --seeds 101 102 ... --control-seeds 201 202 203 > readings.jsonl

One JSON line per run. The limits in ``bench/checks/<cell>.json`` lie above
the largest program reading and below the smallest control reading.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def control(cell, seed: int, precision: str) -> dict:
    import numpy as np
    from bench import harness
    reference = cell.reference()
    n_q = cell.checks["sample"]
    X, Y = harness.make_data(cell, seed, n_q)
    b = cell.mix["policy"]["b_max"]
    session = cell.config["session"]
    t = time.perf_counter()
    answers, claimed = [], 0
    for i in range(0, n_q, b):
        lams, betas, masks, conv = reference.reference_path(
            X, Y[i:i + b], cell.mix["grid"], precision=precision,
            tol=session["tol"], max_iter=session["max_iter"])
        answers += [(lams[j], betas[j], masks[j]) for j in range(len(conv))]
        claimed += int(np.sum(conv))
    worst = reference.certify(np.asarray(X, np.float64), Y, answers,
                              cell.mix["grid"], session)
    return {"kind": f"control:{precision}", "seed": seed,
            "claimed_converged": claimed, "of": n_q, "check": worst,
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--precision", default="high")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_process=time.perf_counter())
        print(json.dumps({"kind": "program", "seed": seed,
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "check": {k: v["value"]
                                    for k, v in out["check"].items()}}),
              flush=True)
    for seed in args.control_seeds:
        print(json.dumps(control(cell, seed, args.precision)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
