"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (DESIGN §6). Prints
``name,us_per_call,derived`` CSV. Default sizes are scaled for this CPU
container; pass ``--full`` for paper-size shapes (hours on CPU, the
intended scale on a real pod).

  --quick    trims the λ grid to 25 points (CI-friendly, ~2-3 min total)
"""

import sys


def main() -> None:
    full = "--full" in sys.argv
    quick = "--quick" in sys.argv
    num = 100 if full else 50   # CPU default: half-density grid
    if quick:
        num = 25

    # float64 for solver-grade duality gaps (paper used doubles)
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.cli import use_compile_cache
    use_compile_cache()

    from . import (bench_basic_rules, bench_batched, bench_dpp_family,
                   bench_group, bench_kernels, bench_roofline,
                   bench_sequential, bench_solver_swap, bench_synthetic,
                   bench_update)

    print("name,us_per_call,derived")
    bench_dpp_family.run(full=full, num_lambdas=num)      # Fig 1 / Table 1
    bench_basic_rules.run(full=full, num_lambdas=num)     # Fig 2
    bench_synthetic.run(full=full, num_lambdas=num)       # Fig 3 / Table 2
    bench_sequential.run(full=full, num_lambdas=num)      # Fig 4 / Table 3
    bench_solver_swap.run(full=full, num_lambdas=num)     # Fig 5 / Table 4
    bench_group.run(full=full, num_lambdas=num)           # Fig 6 / Table 5
    bench_kernels.run(full=full)                          # ours
    bench_roofline.run(full=full)                         # §Roofline reader
    bench_batched.run(full=full)                          # ours: serving B-axis
    bench_update.run(full=full)                           # ours: incr. updates


if __name__ == "__main__":
    main()
