"""Pallas TPU kernels for the screening + solver hot loops (+ pure-jnp
oracles in ref.py).

Kernels (each: <name>.py with pl.pallas_call + BlockSpec, validated against
ref.py in tests/test_kernels.py via interpret=True on CPU):

  edpp_screen.py   fused |Xᵀo| + ρ‖x_j‖ screening scores — one HBM pass over X
  group_screen.py  fused group scores ‖X_gᵀo‖ (Corollary 21)
  prox_step.py     fused FISTA soft-threshold + momentum update
  solver_step.py   fused FISTA iteration (gradient matvec + prox + momentum)
                   and the VMEM-resident Gram CD sweep (SolverEngine)

ops.py additionally exposes the ``BACKENDS`` registry — named
:class:`ScreenBackend` op suites (matvec / fused_scores / group_scores for
the :class:`repro.core.engine.ScreeningEngine`; fista_step / cd_gram_sweep /
prox_step for the :class:`repro.core.solver.SolverEngine`) dispatching the
λ-path hot loops: ``pallas`` (compiled Mosaic), ``interpret`` (kernel
bodies on the Pallas interpreter, for CI/CPU), and ``jnp`` (the ref.py
oracles). See docs/kernels.md and docs/solvers.md for the op contracts,
tiling/VMEM budgets and how to add a backend.
"""
from .ops import (  # noqa: F401
    BACKENDS,
    GRAM_BUCKET_MAX,
    ScreenBackend,
    cd_gram_sweep,
    edpp_screen,
    edpp_screen_scores,
    fista_step,
    group_edpp_screen,
    group_screen_scores,
    prox_step,
    screen_matvec,
)
