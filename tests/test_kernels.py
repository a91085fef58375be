"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret=True executes the Pallas kernel body on CPU)."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

SHAPES = [(8, 128), (60, 300), (128, 512), (100, 1000), (7, 130), (256, 131)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_edpp_screen_kernel(shape, dtype):
    n, p = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    c = jnp.asarray(rng.standard_normal(n), dtype)
    rho = 0.37
    s_ref, ss_ref = ref.edpp_screen_ref(X, c, rho)
    mask, s, ss = ops.edpp_screen(X, c, rho, interpret=True)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ss_ref), **_tol(dtype))
    # mask consistent with scores
    np.testing.assert_array_equal(np.asarray(mask),
                                  np.asarray(s) < 1.0 - 1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_screen_matvec_kernel(shape):
    n, p = shape
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    c = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dot = ops.screen_matvec(X, c, interpret=True)
    np.testing.assert_allclose(np.asarray(dot),
                               np.asarray(ref.screen_matvec_ref(X, c)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m", [2, 5, 10])
@pytest.mark.parametrize("shape", [(60, 300), (100, 1000)])
def test_group_screen_kernel(shape, m):
    n, p = shape
    if p % m:
        pytest.skip("group size must divide p")
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    c = jnp.asarray(rng.standard_normal(n), jnp.float32)
    gs = ops.group_screen_scores(X, c, m, interpret=True)
    np.testing.assert_allclose(np.asarray(gs),
                               np.asarray(ref.group_screen_ref(X, c, m)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("p", [64, 777, 4096])
@pytest.mark.parametrize("dtype", DTYPES)
def test_prox_step_kernel(p, dtype):
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.standard_normal(p), dtype)
    g = jnp.asarray(rng.standard_normal(p), dtype)
    b = jnp.asarray(rng.standard_normal(p), dtype)
    bn_ref, zn_ref = ref.prox_step_ref(z, g, b, 0.01, 2.5, 0.6)
    bn, zn = ops.prox_step(z, g, b, 0.01, 2.5, 0.6, interpret=True)
    np.testing.assert_allclose(np.asarray(bn, np.float32),
                               np.asarray(bn_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(zn, np.float32),
                               np.asarray(zn_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fista_step_kernel(shape, dtype):
    n, p = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    r = jnp.asarray(rng.standard_normal(n), dtype)
    z = jnp.asarray(rng.standard_normal(p), dtype)
    b = jnp.asarray(rng.standard_normal(p), dtype)
    bn_ref, zn_ref = ref.fista_step_ref(X, r, z, b, 0.01, 2.5, 0.6)
    bn, zn = ops.fista_step(X, r, z, b, 0.01, 2.5, 0.6, interpret=True)
    np.testing.assert_allclose(np.asarray(bn, np.float32),
                               np.asarray(bn_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(zn, np.float32),
                               np.asarray(zn_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b", [17, 64, 130, 512])
def test_cd_gram_sweep_kernel(b):
    rng = np.random.default_rng(b)
    A = rng.standard_normal((2 * b, b)).astype(np.float32)
    A[:, -3:] = 0.0                         # padded (zero-norm) columns
    G = jnp.asarray(A.T @ A)
    c = jnp.asarray(A.T @ rng.standard_normal(2 * b).astype(np.float32))
    beta0 = jnp.asarray(rng.standard_normal(b).astype(np.float32) * 0.1)
    lam = 0.5 * float(jnp.max(jnp.abs(c)))
    out_ref = ref.cd_gram_sweep_ref(G, c, beta0, lam, sweeps=3)
    out = ops.cd_gram_sweep(G, c, beta0, lam, sweeps=3, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)
    assert np.all(np.asarray(out)[-3:] == 0)   # zero-Gram cols stay fixed


def test_cd_gram_sweep_rejects_oversized():
    b = ops.GRAM_BUCKET_MAX + 1
    G = jnp.zeros((b, b), jnp.float32)
    with pytest.raises(ValueError, match="GRAM_BUCKET_MAX"):
        ops.cd_gram_sweep(G, jnp.zeros(b), jnp.zeros(b), 0.1, interpret=True)


@pytest.mark.parametrize("kernel", ["screen_matvec", "edpp_screen_scores",
                                    "fista_step", "prox_step",
                                    "cd_gram_sweep", "group_screen_scores"])
def test_compiled_kernels_refuse_float64(kernel):
    """Mosaic has no f64: the compiled kernels raise a clear error instead
    of failing deep in lowering, while interpret mode still accepts f64."""
    n, p = 16, 128
    with jax.enable_x64(True):
        X = jnp.ones((n, p), jnp.float64)
        v_n, v_p = jnp.ones(n, jnp.float64), jnp.ones(p, jnp.float64)
        call = {
            "screen_matvec": lambda it: ops.screen_matvec(
                X, v_n, interpret=it),
            "edpp_screen_scores": lambda it: ops.edpp_screen_scores(
                X, v_n, 0.5, interpret=it),
            "fista_step": lambda it: ops.fista_step(
                X, v_n, v_p, v_p, 0.1, 0.2, 0.3, interpret=it),
            "prox_step": lambda it: ops.prox_step(
                v_p, v_p, v_p, 0.1, 0.2, 0.3, interpret=it),
            "cd_gram_sweep": lambda it: ops.cd_gram_sweep(
                X.T @ X, v_p, v_p, 0.1, interpret=it),
            "group_screen_scores": lambda it: ops.group_screen_scores(
                X, v_n, 8, interpret=it),
        }[kernel]
        with pytest.raises(TypeError, match="float64"):
            call(False)
        out = call(True)
        assert np.isfinite(np.asarray(jax.tree.leaves(out)[0])).all()


def test_kernel_screening_matches_rule():
    """Kernel-based screening decision == reference edpp_mask decision."""
    from repro.core import DualState, edpp_mask, lambda_max, v2_perp
    rng = np.random.default_rng(4)
    n, p = 50, 400
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(n), jnp.float32)
    lmax = float(lambda_max(X, y))
    lam = 0.5 * lmax
    state = DualState.at_lambda_max(X, y)
    vp = v2_perp(y, lam, state)
    centre = state.theta + 0.5 * vp
    rho = 0.5 * float(jnp.linalg.norm(vp))
    mask_k, _, _ = ops.edpp_screen(X, centre, rho, interpret=True)
    mask_ref = edpp_mask(X, y, lam, state)
    np.testing.assert_array_equal(np.asarray(mask_k), np.asarray(mask_ref))


# ---------------------------------------------------------------------------
# Batch axis: every query-side op accepts (B, ·) operands — kernels vs refs
# vs per-row single-query calls (one fitted dictionary, B queries)
# ---------------------------------------------------------------------------

BATCHES = [1, 3, 8, 17]


@pytest.mark.parametrize("batch", BATCHES)
def test_edpp_screen_kernel_batched(batch):
    n, p = 60, 300
    rng = np.random.default_rng(batch)
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    rho = jnp.asarray(rng.uniform(0.1, 1.0, batch), jnp.float32)
    s_ref, ss_ref = ref.edpp_screen_ref(X, C, rho)
    s, ss = ops.edpp_screen_scores(X, C, rho, interpret=True)
    assert s.shape == (batch, p) and ss.shape == (p,)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ss_ref), rtol=2e-5)
    # per-row: batched row b == single-query call on query b (to fp tol)
    for b in range(batch):
        s1, _ = ops.edpp_screen_scores(X, C[b], float(rho[b]),
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(s1),
                                   rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("batch", BATCHES)
def test_screen_matvec_kernel_batched(batch):
    n, p = 45, 260
    rng = np.random.default_rng(10 + batch)
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    dot = ops.screen_matvec(X, C, interpret=True)
    assert dot.shape == (batch, p)
    np.testing.assert_allclose(np.asarray(dot),
                               np.asarray(ref.screen_matvec_ref(X, C)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fista_step_kernel_batched(batch, dtype):
    n, p = 40, 200
    rng = np.random.default_rng(20 + batch)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    R = jnp.asarray(rng.standard_normal((batch, n)), dtype)
    Z = jnp.asarray(rng.standard_normal((batch, p)), dtype)
    Bo = jnp.asarray(rng.standard_normal((batch, p)), dtype)
    lam = jnp.asarray(rng.uniform(0.5, 2.0, batch), jnp.float32)
    bn_ref, zn_ref = ref.fista_step_ref(X, R, Z, Bo, 0.01, lam, 0.6)
    bn, zn = ops.fista_step(X, R, Z, Bo, 0.01, lam, 0.6, interpret=True)
    assert bn.shape == (batch, p)
    np.testing.assert_allclose(np.asarray(bn, np.float32),
                               np.asarray(bn_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(zn, np.float32),
                               np.asarray(zn_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("batch", BATCHES)
def test_prox_step_kernel_batched(batch):
    p = 333
    rng = np.random.default_rng(30 + batch)
    Z = jnp.asarray(rng.standard_normal((batch, p)), jnp.float32)
    G = jnp.asarray(rng.standard_normal((batch, p)), jnp.float32)
    Bo = jnp.asarray(rng.standard_normal((batch, p)), jnp.float32)
    lam = jnp.asarray(rng.uniform(0.5, 2.0, batch), jnp.float32)
    bn_ref, zn_ref = ref.prox_step_ref(Z, G, Bo, 0.01, lam, 0.6)
    bn, zn = ops.prox_step(Z, G, Bo, 0.01, lam, 0.6, interpret=True)
    np.testing.assert_allclose(np.asarray(bn), np.asarray(bn_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(zn), np.asarray(zn_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Mixed precision: bf16 screen copy + margin-aware f32 fallback must give
# masks BIT-IDENTICAL to the f32 engine (docs/kernels.md)
# ---------------------------------------------------------------------------

BF16_RULES = ["edpp", "dpp", "imp1", "imp2", "seq_safe", "safe", "strong",
              # per-piece margin screens (ISSUE 9): two stacked dots, each
              # banded by its own linear-regime margin
              "gap", "dome",
              "dpp_cut", "imp1_cut", "imp2_cut", "edpp_cut", "seq_safe_cut",
              "gap_cut"]


def test_bf16_margin_bounds_quantisation():
    """bf16_column_err dominates the true per-column dot error for any
    full-precision centre (Cauchy-Schwarz), in scalar and batched shapes."""
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.standard_normal((40, 120)), jnp.float32)
    Xb = X.astype(jnp.bfloat16)
    err = ops.bf16_column_err(X, Xb)
    assert err.shape == (120,)
    c = jnp.asarray(rng.standard_normal(40), jnp.float32)
    true_err = jnp.abs(Xb.astype(jnp.float32).T @ c - X.T @ c)
    margin = ops.bf16_score_margin(err, jnp.linalg.norm(c))
    assert margin.shape == (120,)
    assert np.all(np.asarray(true_err) <= np.asarray(margin))
    mB = ops.bf16_score_margin(err, jnp.ones(3))
    assert mB.shape == (3, 120)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("rule", BF16_RULES)
def test_bf16_engine_masks_bit_identical(backend, rule):
    """Sweep: the bf16 fast path + narrow f32 fallback equals the f32
    engine mask exactly, at strictly fewer screen bytes and ≤ +1 pass."""
    from repro.core import ScreeningEngine
    rng = np.random.default_rng(7)
    n, p = 48, 320
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(n), jnp.float32)
    e32 = ScreeningEngine(X, y, backend=backend)
    e16 = ScreeningEngine(X, y, backend=backend, screen_dtype="bfloat16")
    st = e32.state_at_lambda_max()
    for frac in (0.8, 0.5, 0.2):
        lam = frac * e32.lam_max
        m32 = np.asarray(e32.screen(lam, st, rule))
        m16 = np.asarray(e16.screen(lam, st, rule))
        np.testing.assert_array_equal(m16, m32, err_msg=f"{rule}@{frac}")
        assert e16.last_screen_bytes < e32.last_screen_bytes
        assert e16.last_x_passes <= e32.last_x_passes + 1


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bf16_adversarial_band_fallback(backend):
    """Columns PLANTED with scores inside the bf16 error band of the
    decision threshold: the margin fallback must fire (a bf16-only pass
    would misclassify some of them) and the final mask must still equal
    the f32 engine's bit-for-bit."""
    from repro.core import ScreeningEngine
    rng = np.random.default_rng(17)
    n, p = 32, 256
    X = rng.standard_normal((n, p)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    yn = (y / np.linalg.norm(y)).astype(np.float64)
    lmax = float(np.abs(X.astype(np.float64).T @ y.astype(np.float64)).max())
    lam = 0.5 * lmax
    eps = 1e-6                       # scr.EPS_DEFAULT
    thresh = 1.0 - eps / lam         # engine "safe" threshold at λ scale
    # safe-sphere score of a column α·ŷ is linear in α:
    #   |αŷᵀ(y/λ)| + α‖y‖(1/λ − 1/λmax) = α·slope
    ynorm = float(np.linalg.norm(y.astype(np.float64)))
    slope = ynorm * (2.0 / lam - 1.0 / lmax)
    alpha_star = thresh / slope      # score lands exactly ON the threshold
    assert alpha_star * ynorm < 0.9 * lmax   # planting can't move λ_max
    # ladder of score offsets spanning ± the expected bf16 band
    # (≈ 2·(2⁻⁹/√3)·α‖c‖, ‖c‖ = ‖y‖/λ); δ ≈ 0 is inside ANY nonzero margin
    band = 2.0 * (2.0 ** -9) / np.sqrt(3.0) * alpha_star * ynorm / lam
    n_plant = 24
    for j, d in enumerate(np.linspace(-band, band, n_plant)):
        X[:, j] = ((alpha_star + d / slope) * yn).astype(np.float32)
    Xf, yf = jnp.asarray(X), jnp.asarray(y)
    e32 = ScreeningEngine(Xf, yf, backend=backend)
    e16 = ScreeningEngine(Xf, yf, backend=backend, screen_dtype="bfloat16")
    lam = 0.5 * e32.lam_max
    m32 = np.asarray(e32.screen(lam, None, "safe"))
    m16 = np.asarray(e16.screen(lam, None, "safe"))
    np.testing.assert_array_equal(m16, m32)
    assert e16.last_fallback_cols > 0, "planted band never triggered"
    assert e16.last_x_passes == 2      # wide bf16 pass + narrow f32 re-test
    # the ladder straddles the threshold: the mask splits inside it
    planted = m32[:n_plant]
    assert planted.any() and not planted.all()


def _dome_pieces(X, y, lam):
    """The engine's dome geometry recomputed from scratch: (c, rho, ghat,
    b_cut, istar, lam_max) — the pieces dome_scores consumes."""
    import repro.core.screening as scr
    corr = np.asarray(X, np.float64).T @ np.asarray(y, np.float64)
    istar = int(np.argmax(np.abs(corr)))
    lmax = float(np.abs(corr[istar]))
    g = np.sign(corr[istar]) * np.asarray(X[:, istar], np.float64)
    gnorm = float(np.linalg.norm(g))
    ghat = (g / gnorm).astype(np.float32)
    b_cut = np.float32(1.0 / gnorm)
    c = (np.asarray(y, np.float64) / lam).astype(np.float32)
    rho = np.float32(np.linalg.norm(y) * (1.0 / lam - 1.0 / lmax))
    return c, rho, ghat, b_cut, istar, lmax


def _plant_sup_ladder(X, cols, deltas, centre, rho, ghat, b_cut, dirs=None):
    """Rescale (or overwrite, when ``dirs`` is given) the chosen columns so
    their dome/cut sup lands at (1 − eps)·(1 + δ) — the sup is positively
    homogeneous in the column, so one oracle evaluation per column fixes
    the scale exactly (up to f32 noise ≪ the ladder spacing)."""
    import repro.core.screening as scr
    eps = 1e-6
    for j, d in zip(cols, deltas):
        xj = X[:, j] if dirs is None else dirs[j]
        xj = np.asarray(xj, np.float64)
        sup = float(scr.dome_scores(
            jnp.asarray([xj @ centre], jnp.float32),
            jnp.asarray([xj @ ghat], jnp.float32),
            jnp.asarray([np.linalg.norm(xj)], jnp.float32),
            jnp.asarray(centre), jnp.asarray(rho), jnp.asarray(ghat),
            jnp.asarray(b_cut))[0])
        X[:, j] = (xj * (1.0 - eps) * (1.0 + d) / sup).astype(np.float32)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bf16_adversarial_dome_boundary(backend):
    """Columns planted with dome sup on a ladder straddling the 1 − eps
    discard threshold (the dome rule's own regime boundary): the per-piece
    margin fallback must fire and the bf16 mask must equal the f32 mask
    bit-for-bit, with the ladder splitting across the threshold."""
    from repro.core import ScreeningEngine
    rng = np.random.default_rng(23)
    n, p, n_plant = 32, 256, 16
    X = rng.standard_normal((n, p)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    c, rho, ghat, b_cut, istar, lmax = _dome_pieces(X, y, 0.5 * float(
        np.max(np.abs(X.T @ y))))
    lam = 0.5 * lmax
    c, rho, ghat, b_cut, istar, lmax = _dome_pieces(X, y, lam)
    cols = [j for j in range(p - n_plant - 1, p) if j != istar][:n_plant]
    # ± the relative bf16 band (~2·2⁻⁹/√3 ≈ 2.3e-3); δ ≈ 0 rungs sit inside
    # ANY nonzero margin, the extremes outside it
    deltas = np.linspace(-2.5e-3, 2.5e-3, n_plant)
    _plant_sup_ladder(X, cols, deltas, c, rho, ghat, b_cut)
    # planting must not move the λ_max geometry the pieces came from
    corr = np.abs(X.T @ y)
    assert int(np.argmax(corr)) == istar
    assert float(np.max(corr[cols])) < 0.9 * lmax
    Xf, yf = jnp.asarray(X), jnp.asarray(y)
    e32 = ScreeningEngine(Xf, yf, backend=backend)
    e16 = ScreeningEngine(Xf, yf, backend=backend, screen_dtype="bfloat16")
    st = e32.state_at_lambda_max()
    m32 = np.asarray(e32.screen(lam, st, "dome"))
    m16 = np.asarray(e16.screen(lam, st, "dome"))
    np.testing.assert_array_equal(m16, m32)
    assert e16.last_fallback_cols > 0, "planted dome band never triggered"
    planted = m32[cols]
    assert planted.any() and not planted.all()
    assert not m32[istar], "dome discarded istar (sup there is exactly 1)"


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_bf16_adversarial_cut_corner(backend):
    """edpp_cut columns planted AT the two-plane corner of the cut sup —
    t_star = ĝᵀx/‖x‖ ≈ t_b, where the closed form switches between the
    unclipped sphere maximiser and the spherical-cap regime — AND with sup
    on a ladder straddling the discard threshold. Both per-piece margins
    (centre dot and cut dot) are live here; masks must stay bit-identical
    with the fallback firing."""
    import repro.core.screening as scr
    from repro.core import ScreeningEngine
    rng = np.random.default_rng(29)
    n, p, n_plant = 32, 256, 16
    X = rng.standard_normal((n, p)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    corr = np.abs(X.astype(np.float64).T @ y)
    istar = int(np.argmax(corr))
    lmax = float(corr[istar])
    _, _, ghat, b_cut, _, _ = _dome_pieces(X, y, 0.5 * lmax)
    from repro.core import DualState
    st = DualState.at_lambda_max(jnp.asarray(X), jnp.asarray(y))
    lam = None
    for frac in (0.5, 0.7, 0.3, 0.9):
        test = scr.make_sphere("edpp", jnp.asarray(y), frac * lmax, st)
        centre = np.asarray(test.centre, np.float64)
        rho_s = float(test.rho)
        t_b = float(scr.dome_t_b(test.centre, test.rho, jnp.asarray(ghat),
                                 jnp.asarray(b_cut)))
        if -0.95 < t_b < 0.95:       # interior corner exists at this λ
            lam = frac * lmax
            break
    assert lam is not None, "no λ with an interior clipping corner"
    # orthonormal u ⊥ ĝ; dirs sweep t through the corner while the ladder
    # sweeps the sup through the threshold
    u = rng.standard_normal(n)
    u -= (u @ ghat) * ghat.astype(np.float64)
    u /= np.linalg.norm(u)
    cols = [j for j in range(p - n_plant - 1, p) if j != istar][:n_plant]
    t_off = np.linspace(-0.02, 0.02, n_plant)
    dirs = {j: np.clip(t_b + dt, -0.99, 0.99) * ghat.astype(np.float64)
            + np.sqrt(1.0 - np.clip(t_b + dt, -0.99, 0.99) ** 2) * u
            for j, dt in zip(cols, t_off)}
    deltas = np.linspace(-2.5e-3, 2.5e-3, n_plant)
    _plant_sup_ladder(X, cols, deltas, centre.astype(np.float32), rho_s,
                      ghat, b_cut, dirs=dirs)
    corr2 = np.abs(X.T @ y)
    assert int(np.argmax(corr2)) == istar
    assert float(np.max(corr2[cols])) < 0.9 * lmax
    Xf, yf = jnp.asarray(X), jnp.asarray(y)
    e32 = ScreeningEngine(Xf, yf, backend=backend)
    e16 = ScreeningEngine(Xf, yf, backend=backend, screen_dtype="bfloat16")
    st = e32.state_at_lambda_max()
    m32 = np.asarray(e32.screen(lam, st, "edpp_cut"))
    m16 = np.asarray(e16.screen(lam, st, "edpp_cut"))
    np.testing.assert_array_equal(m16, m32)
    assert e16.last_fallback_cols > 0, "planted corner band never triggered"
    planted = m32[cols]
    assert planted.any() and not planted.all()


@pytest.mark.parametrize("batch", [2, 9])
def test_cd_gram_sweep_kernel_batched_with_valid(batch):
    b = 48
    rng = np.random.default_rng(40 + batch)
    A = rng.standard_normal((2 * b, b)).astype(np.float32)
    A[:, -3:] = 0.0
    G = jnp.asarray(A.T @ A)
    C = jnp.asarray(rng.standard_normal((batch, b)), jnp.float32)
    beta0 = jnp.asarray(rng.standard_normal((batch, b)) * 0.1, jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(batch, b)) > 0.3, jnp.float32)
    lam = jnp.asarray(rng.uniform(0.5, 2.0, batch), jnp.float32)
    out_ref = ref.cd_gram_sweep_ref(G, C, beta0 * valid, lam, sweeps=2,
                                    valid=valid)
    out = ops.cd_gram_sweep(G, C, beta0 * valid, lam, sweeps=2, valid=valid,
                            interpret=True)
    assert out.shape == (batch, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)
    # per-query screened-out columns are pinned at zero
    assert np.all(np.asarray(out) * (1 - np.asarray(valid)) == 0)
    assert np.all(np.asarray(out)[:, -3:] == 0)   # zero-Gram cols too
