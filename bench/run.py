"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload mnist.upper.sat --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; last in it, ``check``: each number compared with its limit.
The same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell needs, it exits 2 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# run as a script: import the benchmark as a package and the program from
# the checkout, and never a module of this directory as a top-level name
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    def log(msg):
        print(f"[{time.perf_counter() - T_PROCESS:8.3f} s] {msg}",
              file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload, ROOT)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS,
                                  log=log)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    checked = result.pop("check")
    line = {"correct": harness.is_correct(checked), **result,
            "check": checked}
    print(json.dumps(line), flush=True)
    for text in harness.check_lines(checked):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
