"""Incremental dictionary updates — fit-once becomes fit-forever.

Production dictionaries churn: catalog items are added and retired,
features re-embedded. A full re-`fit` on every column edit throws away
everything the screening machinery makes reusable, so this module turns a
column edit into a *plan* and applies it to the session's fitted state in
place:

  * :func:`make_plan` — validate ``add=`` / ``drop=`` into an
    :class:`UpdatePlan`. The layout rule: added columns first *recycle*
    the dropped slots in ascending drop order, leftover adds append at
    the end, leftover drops compact the survivors left preserving order.
    On the balanced churn workloads updates exist for (retire c items,
    add c items — benchmarks/bench_update.py) every edit is pure
    recycling: no column moves, so the whole update is O(n·c) in-place
    column patches instead of O(n·p) gathers.
  * :meth:`DictionaryGeometry.apply_update` (engine.py) — survivors carry
    ``sumsq`` / ``col_norms`` / every reduced-precision screen copy and
    its ``:err`` bound untouched (recycled slots are patched in place);
    only the added block pays fresh passes. Per-column reductions are
    column-independent, but XLA's *accumulation order within a column*
    is shape-dependent, so narrow-block results are not trusted a
    priori: the first update at a given (backend, shape, churn)
    recomputes at full shape with the cold path's own calls and
    **probes** the block bits against it — validated shapes take the
    O(n·c) carry from then on, failing shapes keep the full-shape
    recompute (still far cheaper than a refit). Either way the state is
    **bit-identical** to a cold fit on the edited X.
  * :func:`update_workspace` — refresh a live :class:`PathWorkspace`
    (a long-lived query stream). For a balanced edit ``|Xᵀy|`` gets one
    matvec over the added block only (behind the same probe discipline),
    and λ_max recomputes from the touched-column candidates against the
    cached argmax — the full candidate rescan runs only when the old
    argmax column was dropped (the survivors' max is still the old
    argmax otherwise, so ``max(old λ_max, touched max)`` is exact, ties
    resolving to the lower index like a cold ``argmax``). A
    shape-changing edit rebuilds the stream cold — a gemm's per-column
    rounding shifts with the column count, so the bitwise contract
    forbids carrying survivor scores across a shape change (see the
    function docstring).
  * :func:`carry_mask` — map per-version screening masks across the edit:
    surviving columns keep their discard decisions, added columns enter
    unscreened and ride the next fused pass.

Exactness contract (tested in tests/test_update.py): after
``session.update(...)`` + ``session.reset_solver_cache()``, a ``path``
call produces masks bit-identical to a cold ``LassoSession.fit`` on the
edited X, and β within ``beta_err_tol``. The eig-cache reset is part of
the recipe because warm Lipschitz starts intentionally survive updates
(that's the speedup); the *geometry* carry alone never perturbs a bit.

Mask carry-over safety: :func:`carry_mask` is exact when the dropped
columns were inactive (discarded, β=0) at the mask's λ — removing an
all-zero coordinate leaves the primal solution, hence the dual optimum
and every sphere built from it, unchanged, so prior discards stay safe.
Dropping an *active* column moves θ*; re-screen from scratch then (the
session's next ``path`` call does exactly that anyway).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .engine import _stream_fit_batched

__all__ = [
    "UpdatePlan",
    "UpdateReport",
    "carry_mask",
    "make_plan",
    "update_workspace",
]


@jax.jit
def _scatter_scores(scores, slots, blk):
    """Hinted slot scatter for a stream's |Xᵀy| — ``slots`` is sorted-
    unique by construction, same lowering win as engine._patch_slots_impl."""
    return scores.at[..., slots].set(blk, unique_indices=True,
                                    indices_are_sorted=True)


@dataclasses.dataclass(frozen=True, eq=False)
class UpdatePlan:
    """A validated column edit.

    Layout rule (see the module docstring): the first
    ``n_recycle = min(n_add, n_drop)`` added columns overwrite the
    dropped slots ``recycle_idx = drop_idx[:n_recycle]`` in place;
    residual drops ``drop_idx[n_recycle:]`` compact the survivors left;
    residual adds (``n_append``) append at the end. ``keep_idx`` lists
    the *slots* that survive compaction (recycled slots included — they
    survive holding new content) so the edited dictionary is
    ``[patched_X[:, keep_idx], X_add[:, n_recycle:]]``.
    """

    p_old: int
    n_add: int
    keep_idx: np.ndarray        # (p_keep,) surviving slots, ascending
    drop_idx: np.ndarray        # sorted unique dropped old columns

    @property
    def n_drop(self) -> int:
        return int(self.drop_idx.size)

    @property
    def n_recycle(self) -> int:
        return min(self.n_add, self.n_drop)

    @property
    def n_append(self) -> int:
        return self.n_add - self.n_recycle

    @property
    def recycle_idx(self) -> np.ndarray:
        """Dropped slots overwritten by the first added columns."""
        return self.drop_idx[:self.n_recycle]

    @property
    def pure_recycle(self) -> bool:
        """No column moves: every add lands in a dropped slot exactly."""
        return self.n_add == self.n_drop

    @property
    def p_new(self) -> int:
        return int(self.keep_idx.size) + self.n_append

    @property
    def recycle_new_idx(self) -> np.ndarray:
        """Edited positions of the recycled slots, ascending."""
        return np.searchsorted(self.keep_idx, self.recycle_idx)

    @property
    def touched_new_idx(self) -> np.ndarray:
        """Edited positions of ALL added columns, ascending (recycled
        slots, then the appended tail)."""
        p_keep = int(self.keep_idx.size)
        return np.concatenate([
            self.recycle_new_idx,
            np.arange(p_keep, p_keep + self.n_append, dtype=np.int64)])

    def dropped(self, old_idx):
        """Whether the old column(s) content was dropped (a recycled slot
        still survives, but its OLD content is gone)."""
        return np.isin(old_idx, self.drop_idx)

    def new_index(self, old_idx):
        """Map old column indices to their edited positions (-1 = content
        dropped, including recycled slots — the slot survives but holds a
        NEW column)."""
        old = np.asarray(old_idx)
        pos = np.searchsorted(self.keep_idx, old)
        pos = np.clip(pos, 0, max(self.keep_idx.size - 1, 0))
        ok = ((self.keep_idx.size > 0) & (self.keep_idx[pos] == old)
              & ~np.isin(old, self.drop_idx))
        return np.where(ok, pos, -1)


@dataclasses.dataclass(frozen=True)
class UpdateReport:
    """What one ``session.update`` did — telemetry for tests and benches."""

    version: int                # the session/geometry version after the edit
    p: int                      # edited column count
    n_add: int
    n_drop: int
    geometries_updated: int     # per-backend geometries edited in place
    eig_buckets_carried: int    # warm Lipschitz eigenvectors kept as v0
    workspaces_updated: int     # live query streams refreshed
    argmax_rescans: int         # streams whose λ_max argmax was dropped


def make_plan(p_old: int, add=None, drop=None):
    """Validate an ``add=`` / ``drop=`` edit into ``(UpdatePlan, X_add)``.

    ``drop`` is a sequence of old column indices (deduplicated, order
    irrelevant); ``add`` an (n, p_add) block. Returns the plan plus
    ``add`` as a jnp array (or None). Raises on out-of-range or
    non-integer drops, a non-2D add block, or an edit that would leave
    the dictionary empty.
    """
    if add is None and drop is None:
        raise ValueError("update needs add= and/or drop=")
    if drop is None:
        drop_idx = np.zeros(0, dtype=np.int64)
    else:
        drop_idx = np.atleast_1d(np.asarray(drop))
        if drop_idx.ndim != 1:
            raise ValueError(f"drop must be 1-D indices, got shape "
                             f"{drop_idx.shape}")
        if drop_idx.size and not np.issubdtype(drop_idx.dtype, np.integer):
            raise ValueError(f"drop must be integer indices, got dtype "
                             f"{drop_idx.dtype}")
        if drop_idx.size and (
                (drop_idx < 0).any() or (drop_idx >= p_old).any()):
            raise ValueError(f"drop indices out of range for p={p_old}: "
                             f"{drop_idx[(drop_idx < 0) | (drop_idx >= p_old)]}")
        drop_idx = np.unique(drop_idx.astype(np.int64))

    X_add = None
    n_add = 0
    if add is not None:
        X_add = jnp.asarray(add)
        if X_add.ndim != 2:
            raise ValueError(f"add must be an (n, p_add) block, got shape "
                             f"{X_add.shape}")
        n_add = int(X_add.shape[1])
        if n_add == 0:
            X_add = None
    # recycled slots (drop_idx[:min(n_add, n_drop)]) survive compaction —
    # they hold new content — so only the RESIDUAL drops remove slots
    resid_drop = drop_idx[min(n_add, drop_idx.size):]
    if resid_drop.size:
        keep_idx = np.setdiff1d(np.arange(p_old, dtype=np.int64), resid_drop)
    else:
        keep_idx = np.arange(p_old, dtype=np.int64)
    plan = UpdatePlan(p_old=int(p_old), n_add=n_add,
                      keep_idx=keep_idx, drop_idx=drop_idx)
    if plan.p_new == 0:
        raise ValueError("edit would leave an empty dictionary")
    return plan, X_add


def carry_mask(mask, plan: UpdatePlan) -> np.ndarray:
    """Map (…, p_old) screening masks onto the edited dictionary.

    Surviving columns keep their discard decisions; added columns enter
    unscreened (False = kept) — both the appended tail and the recycled
    slots, whose inherited bit belonged to the dropped content and is
    cleared. Exact when the dropped columns were inactive at the mask's λ
    (see the module docstring); the carried mask then equals the
    cold-refit mask bit for bit (tested).
    """
    m = np.asarray(mask)
    kept = np.take(m, plan.keep_idx, axis=-1)
    if plan.n_recycle:
        kept = kept.copy()
        kept[..., plan.recycle_new_idx] = 0
    if plan.n_append:
        pad = np.zeros(m.shape[:-1] + (plan.n_append,), dtype=m.dtype)
        kept = np.concatenate([kept, pad], axis=-1)
    return kept


# update_workspace's block-vs-full matvec probe results:
# (backend id, X shape, churn size, y shape) → did the (n, c) block
# matvec reproduce the (n, p) full matvec's bits at the touched columns?
# The accumulation order of a compiled gemm is fixed per executable and
# independent of the data, so one probe decides a shape for the process.
_STREAM_CARRY_OK: dict = {}


def _attach_cold(ws):
    """Rebuild the stream with the cold computation — the EXACT eager
    calls ``PathWorkspace``'s geometry attach runs (same executables →
    bitwise-identical to a fresh workspace on the edited X)."""
    geom = ws.geometry
    scores = jnp.abs(geom.backend.matvec(geom.X, ws.y))
    geom.query_passes += 1
    ws.abs_xty = scores
    istar = jnp.argmax(scores, axis=-1)
    ws.istar = np.asarray(istar)
    ws.lam_max = np.asarray(
        jnp.take_along_axis(scores, istar[:, None], axis=-1)[:, 0],
        dtype=np.float64)
    ws.v1_at_lmax, ws.ghat = _stream_fit_batched(geom.X, istar, ws.y)


def update_workspace(ws, plan: UpdatePlan, X_add=None):
    """Refresh a live :class:`~repro.core.engine.PathWorkspace` across a
    dictionary edit, touching only the edited columns where that is
    bitwise-safe.

    The workspace's geometry must already be at the edited shape (the
    session updates geometries first).

    A *balanced* edit (``plan.pure_recycle`` — the churn-workload common
    case) keeps every survivor's ``|xᵀy|`` untouched (a gemm output
    column depends only on its own column's data, so survivor bits can't
    move) and patches only the recycled slots with one narrow matvec
    over the added block; λ_max then recomputes from the touched
    candidates against the cached argmax, and only a query whose argmax
    column was dropped rescans the full candidate vector. Because XLA's
    gemm accumulation order is shape-dependent, the narrow (n, c) matvec
    is only trusted after a one-time *probe* at this (shape, churn,
    batch) validated its bits against the full matvec (the first such
    update rebuilds cold and compares); shapes that fail the probe — and
    every *shape-changing* edit, whose survivors' own cold values move
    with p — rebuild the stream with the cold computation (one full
    matvec + argmax), which is bit-identical to a fresh workspace by
    construction. See ``DictionaryGeometry.apply_update`` for the same
    probe discipline on the geometry side.

    Returns the number of queries whose cached argmax column content was
    dropped — those cannot reuse the cached λ_max whichever rebuild path
    runs (the :class:`UpdateReport` ``argmax_rescans`` telemetry).
    """
    geom = ws.geometry
    if geom.X.shape[1] != plan.p_new:
        raise ValueError(
            f"workspace geometry has p={geom.X.shape[1]} but the plan "
            f"edits to p={plan.p_new} — update the geometry first")

    n_dropped_argmax = int(np.sum(plan.dropped(np.asarray(ws.istar))))

    if not plan.pure_recycle:
        _attach_cold(ws)
        return n_dropped_argmax

    touched = plan.touched_new_idx        # == recycle_idx here, ascending —
    #                                       argmax over it prefers the
    #                                       lowest position, matching a
    #                                       cold jnp.argmax
    ck = (id(geom.backend), geom.X.shape, int(plan.n_add),
          tuple(ws.y.shape))
    carry = _STREAM_CARRY_OK.get(ck)
    scores_add = None
    if carry is not False:
        add = jnp.asarray(X_add, geom.X.dtype)
        scores_add = jnp.abs(geom.backend.matvec(add, ws.y))
        geom.update_passes += 1
    if not carry:
        _attach_cold(ws)
        if carry is None:
            _STREAM_CARRY_OK[ck] = bool(np.array_equal(
                np.asarray(scores_add),
                np.asarray(ws.abs_xty)[..., touched]))
        return n_dropped_argmax

    abs_xty = _scatter_scores(ws.abs_xty, jnp.asarray(touched, jnp.int32),
                              scores_add)
    ws.abs_xty = abs_xty

    scores = np.asarray(abs_xty)
    rows = np.arange(ws.batch)
    dropped = plan.dropped(np.asarray(ws.istar))
    istar = np.where(dropped, 0, np.asarray(ws.istar))
    if plan.n_add:
        # pure recycle: slots don't move. A cold argmax breaks ties toward
        # the lower index, and a recycled slot can sit BELOW the surviving
        # argmax, so a tie goes to whichever position is lower.
        st = scores[:, touched]
        jt = touched[st.argmax(axis=-1)]
        sj = scores[rows, jt]
        si = scores[rows, istar]
        take_add = (sj > si) | ((sj == si) & (jt < istar))
        istar = np.where(take_add, jt, istar)
    if dropped.any():
        istar = np.where(dropped, scores.argmax(axis=-1), istar)
    ws.istar = istar
    ws.lam_max = scores[rows, istar].astype(np.float64)
    ws.v1_at_lmax, ws.ghat = _stream_fit_batched(
        geom.X, jnp.asarray(istar, jnp.int32), ws.y)
    return n_dropped_argmax
