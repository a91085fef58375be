"""The λ step's epilogue (``core/path._step_epilogue``): one jitted program
keyed on the bucket, one packed read, and a host scatter of the kept
columns, against an eager reference of the epilogue it replaced — β
scattered back to p by ops sized by the kept count, the fitted values by
an eager matmul, and the dense float64 copy-out of β — bit for bit."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import LassoSession
from repro.core import PathConfig, ScreenSpec, SolveSpec, engine, tracing
from repro.core import path as path_mod

N, P, K = 40, 256, 6


def _problem(b, seed=0, n=N, p=P, lead=None):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p)).astype(np.float32)
    cols = [0] if lead == 0 else list(range(3, 13))
    Y = (X[:, cols] @ rng.uniform(0.5, 1.5, (len(cols), b))).T
    Y = Y + 0.01 * rng.standard_normal(Y.shape)
    Y = Y.astype(np.float32)
    return X, (Y[0] if b == 1 else Y)


def _cfg(rule="edpp", **kw):
    return PathConfig(screen=ScreenSpec(rule=rule, backend="jnp"),
                      solve=SolveSpec(backend="jnp", tol=1e-6), **kw)


def _discard_all_at(k_all):
    """A screen that discards every feature at λ step ``k_all``: a live
    step that reaches the solve loop with nothing kept. Under the strong
    rule the KKT re-check then finds the violators and adds them back, the
    way it recovers from any over-eager heuristic discard."""
    def setup(monkeypatch):
        real = engine.ScreeningEngine.screen

        def screen(self, *args, **kwargs):
            out = real(self, *args, **kwargs)
            st = getattr(tracing._local, "step", None)
            return (jnp.ones_like(out) if st is not None and st.k == k_all
                    else out)

        monkeypatch.setattr(engine.ScreeningEngine, "screen", screen)
    return setup


def _kept(res, b, k):
    return np.flatnonzero(~res.masks[b, k])


# each case: the problem, the config, the session's keywords, a setup, and
# what the path must contain for the case to test what it names
CASES = {
    "single": dict(problem=dict(b=1), cfg={}),
    "batch8": dict(problem=dict(b=8, seed=1), cfg={}),
    "group": dict(problem=dict(b=1, seed=2), cfg={}, fit=dict(groups=4),
                  check=lambda res: res.masks.shape[-1] == P // 4),
    "column0_padded": dict(
        problem=dict(b=1, seed=3, lead=0), cfg={},
        check=lambda res: any(
            0 in _kept(res, 0, k) and s.n_kept < s.bucket
            for k, s in enumerate(res.stats))),
    "zero_kept": dict(
        problem=dict(b=8, seed=4), cfg={}, setup=_discard_all_at(1),
        check=lambda res: res.stats[1].n_kept == 0
        and res.stats[2].n_kept > 0),
    "strong_kkt": dict(
        problem=dict(b=8, seed=5), cfg=dict(rule="strong"),
        setup=_discard_all_at(2),
        check=lambda res: res.stats[2].kkt_rounds > 0
        and res.stats[2].n_kept > 0),
    "checkpoint": dict(problem=dict(b=8, seed=6), cfg={}, checkpoint=True),
}


def _run(case, monkeypatch, reference: bool):
    spec = CASES[case]
    X, Y = _problem(**spec["problem"])
    saved, dense = [], {}
    ckpt = None
    if spec.get("checkpoint"):
        def ckpt(k, lam, beta):
            saved.append((k, np.array(lam), np.array(beta)))
    cfg = _cfg(**spec["cfg"], checkpoint_fn=ckpt)
    if reference:
        def eager_epilogue(beta_r, iters, gap, converged, Xr, idx, valid,
                           p):
            size = int(np.asarray(valid).sum())
            col_idx = np.asarray(idx)[:size]
            beta_full = (jnp.zeros((beta_r.shape[0], p), Xr.dtype)
                         .at[:, col_idx].set(beta_r[:, :size]))
            fitted = ((Xr @ beta_r[0])[None, :] if beta_r.shape[0] == 1
                      else beta_r @ Xr.T)
            # the dense copy-out: the step's last solve is what it read
            dense[tracing._local.step.k] = np.asarray(beta_full, np.float64)
            B = beta_full.shape[0]
            per_query = np.stack([np.asarray(a, np.float32).reshape(B)
                                  for a in (iters, gap, converged)], axis=1)
            summary = np.concatenate(
                [np.asarray(beta_r, np.float32).reshape(B, -1), per_query],
                axis=1)
            return beta_full, fitted, jnp.asarray(summary)

        monkeypatch.setattr(path_mod, "_step_epilogue", eager_epilogue)
    if "setup" in spec:
        spec["setup"](monkeypatch)
    sess = LassoSession.fit(X, config=cfg, **spec.get("fit", {}))
    res = sess.path(Y, num_lambdas=K, hi_frac=0.95)
    monkeypatch.undo()
    return res, dense, saved


@pytest.mark.parametrize("case", sorted(CASES))
def test_epilogue_matches_the_eager_reference_bit_for_bit(case, monkeypatch):
    got, _, got_saved = _run(case, monkeypatch, reference=False)
    want, dense, want_saved = _run(case, monkeypatch, reference=True)
    check = CASES[case].get("check")
    assert check is None or check(want), f"{case}: the case does not arise"
    assert got.betas.dtype == np.float64
    for attr in ("betas", "masks", "lambdas", "query_converged"):
        np.testing.assert_array_equal(getattr(got, attr),
                                      getattr(want, attr), err_msg=attr)
    # the host scatter reads what the dense float64 copy-out read
    for k in range(K):
        np.testing.assert_array_equal(
            got.betas[:, k], dense.get(k, np.zeros(got.betas[:, k].shape)))
    for a, b in zip(got.stats, want.stats):
        assert (a.n_kept, a.bucket, a.solver_iters, a.gap, a.kkt_rounds,
                a.queries_converged) == (b.n_kept, b.bucket, b.solver_iters,
                                         b.gap, b.kkt_rounds,
                                         b.queries_converged)
    if CASES[case].get("checkpoint"):
        assert [s[0] for s in got_saved] == list(range(K))
        for (k, lg, bg), (_, lw, bw) in zip(got_saved, want_saved):
            np.testing.assert_array_equal(lg, lw)
            np.testing.assert_array_equal(bg, bw)
            np.testing.assert_array_equal(bg, got.betas[:, k])
    assert any(s.n_kept for s in got.stats)
