"""Reference (oracle) implementations of subsequence DTW.

These are the *trusted baselines* every optimized path (anti-diagonal
engine, Pallas kernels, distributed pipeline) is validated against.

Subsequence DTW (sDTW) recurrence, 0-based query rows ``i`` and reference
columns ``j``::

    D[i, j] = cost(q[i], r[j]) + reduce(D[i-1, j], D[i, j-1], D[i-1, j-1])

with the *subsequence* boundary condition ``D[-1, j] = 0`` for every j
(an alignment may start anywhere in the reference) and ``D[i, -1] = inf``
for ``i >= 0``.  The result is the reduction of ``D[M-1, j]`` over j —
the best alignment cost of the whole query against *some* contiguous
window of the reference (paper §2).

Both oracles here consume a :class:`repro.core.spec.DPSpec`, so every
(distance × reduction × band) combination a faster backend claims to
support can be checked cell-by-cell against the same trusted loop:
``cost`` is ``spec.cell_cost``, ``reduce`` is hard-min or the smoothed
soft-min, and a Sakoe–Chiba band leaves out-of-band cells at the
masked sentinel.  The default spec reproduces the original
squared-Euclidean hard-min oracle exactly.

Like every backend module this is the raw tuple-level layer —
``repro.backends.builtin`` wraps it into typed ``SDTWResult`` pytrees
for the ``repro.sdtw`` / ``repro.Aligner`` front door.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.spec import (DEFAULT_SPEC, DPSpec, INF,  # noqa: F401
                             NO_WINDOW, soft_exp, soft_log)
# INF re-exported for backward compatibility (ref.INF predates spec.py)


def _np_cost(spec: DPSpec, a: float, b: float) -> float:
    if spec.distance == "sqeuclidean":
        return (a - b) ** 2
    if spec.distance == "abs":
        return abs(a - b)
    return 1.0 - (a * b) / (abs(a) * abs(b) + 1e-8)


def _np_costs(spec: DPSpec, q: np.ndarray, r: np.ndarray) -> np.ndarray:
    """The (..., N) local costs of query samples ``q`` against every
    reference sample: scalars q (...,) against r (N,), or feature
    vectors q (..., D) against r (N, D), whose per-feature costs are
    added in feature order."""
    if r.ndim == 1:
        return _np_cost(spec, q[..., None], r)
    if spec.distance == "cosine":
        raise ValueError("distance 'cosine' has no multivariate form here")
    total = _np_cost(spec, q[..., None, 0], r[:, 0])
    for d in range(1, r.shape[1]):
        total = total + _np_cost(spec, q[..., None, d], r[:, d])
    return total


def _np_softmin(vals, gamma: float) -> float:
    a = -np.asarray(vals, dtype=np.float64) / gamma
    mx = np.max(a)
    if not np.isfinite(mx):          # every predecessor blocked
        return np.inf
    return float(-gamma * (mx + np.log(np.sum(np.exp(a - mx)))))


def sdtw_numpy(q: np.ndarray, r: np.ndarray,
               spec: DPSpec | None = None) -> tuple[float, int]:
    """Brute-force full-matrix sDTW. O(M*N) memory. Trusted oracle.

    q (M,) and r (N,), or multivariate q (M, D) and r (N, D), whose
    cell cost adds the per-feature costs.  Returns (cost, end_index)
    where end_index is the reference column at which the best
    alignment ends.  For soft-min specs the cost is the
    smoothed soft-min over the bottom row (matching the engine's
    streaming logsumexp readout) and the end index is the bottom row's
    hard argmin.
    """
    spec = DEFAULT_SPEC if spec is None else spec
    q = np.asarray(q, dtype=np.float64)
    r = np.asarray(r, dtype=np.float64)
    m, n = len(q), len(r)
    C = _np_costs(spec, q, r)                 # (m, n) local costs
    D = np.full((m + 1, n + 1), np.inf, dtype=np.float64)
    D[0, :] = 0.0  # subsequence: free start anywhere in the reference
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            if spec.band is not None and abs((i - 1) - (j - 1)) > spec.band:
                continue                      # out of band: stays +inf
            c = C[i - 1, j - 1]
            if i == 1:
                prev = 0.0                    # free start: D[-1, j] == 0
            elif spec.soft:
                prev = _np_softmin(
                    (D[i, j - 1], D[i - 1, j], D[i - 1, j - 1]), spec.gamma)
            else:
                prev = min(D[i, j - 1], D[i - 1, j], D[i - 1, j - 1])
            D[i, j] = c + prev
    last = D[m, 1:]
    end = int(np.argmin(last))
    if spec.soft:
        return -spec.gamma * float(_np_logsumexp(-last / spec.gamma)), end
    return float(last[end]), end


def sdtw_bottom_row(queries: np.ndarray, r: np.ndarray,
                    spec: DPSpec | None = None) -> np.ndarray:
    """The last DP row ``D[M-1, :]`` of hard-min, unbanded sDTW in
    float64, one vectorized pass per query row — the host oracle for
    paper-size checks, where :func:`sdtw_numpy`'s cell loop is far too
    slow.  Its min is the cost, its first argmin the end; the whole row
    says how far from optimal any other end is.

    The horizontal dependency ``D[i, j] = min(A[j], D[i, j-1] + c[j])``
    with ``A[j] = c[j] + min(D[i-1, j], D[i-1, j-1])`` is a min-plus
    prefix scan: ``D[i] = S + minimum.accumulate(A - S)``, ``S`` the
    running sum of the row's costs.  Row 0 is the free start
    ``D[0] = c``.  queries (B, M), r (N,) -> (B, N); multivariate
    queries (B, M, D) against r (N, D) add the per-feature costs.
    """
    spec = DEFAULT_SPEC if spec is None else spec
    if spec.soft or spec.band is not None:
        raise ValueError("sdtw_bottom_row is the hard-min unbanded "
                         f"oracle; got {spec.describe()}")
    q = np.asarray(queries, dtype=np.float64)
    r = np.asarray(r, dtype=np.float64)
    d = _np_costs(spec, q[:, 0], r)
    for i in range(1, q.shape[1]):
        c = _np_costs(spec, q[:, i], r)
        diag = np.concatenate(
            [np.full((q.shape[0], 1), np.inf), d[:, :-1]], axis=1)
        s = np.cumsum(c, axis=1)
        d = s + np.minimum.accumulate(c + np.minimum(d, diag) - s, axis=1)
    return d


def _np_logsumexp(a: np.ndarray) -> float:
    mx = np.max(a)
    if not np.isfinite(mx):
        return -np.inf
    return float(mx + np.log(np.sum(np.exp(a - mx))))


def dtw_global_numpy(q: np.ndarray, r: np.ndarray) -> float:
    """Global DTW (both ends pinned) — used by property tests
    (sDTW cost <= global DTW cost)."""
    q = np.asarray(q, dtype=np.float64)
    r = np.asarray(r, dtype=np.float64)
    m, n = len(q), len(r)
    D = np.full((m + 1, n + 1), np.inf, dtype=np.float64)
    D[0, 0] = 0.0
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            c = (q[i - 1] - r[j - 1]) ** 2
            D[i, j] = c + min(D[i - 1, j], D[i, j - 1], D[i - 1, j - 1])
    return float(D[m, n])


def _sdtw_rowscan_single(q: jnp.ndarray, r: jnp.ndarray,
                         spec: DPSpec,
                         return_window: bool = False):
    """Row-by-row scan sDTW for one (query, reference) pair.

    Sequential over both axes (inner scan carries the left cell), so it is
    slow but structurally simple — it mirrors the CPU-side generator the
    paper uses for correctness evaluation (§4).
    Returns (cost, end_index), or (cost, start, end) when
    ``return_window`` (hard-min only): the start column is propagated
    through the same scans via ``spec.start3``.
    """
    big = jnp.asarray(spec.big, q.dtype)
    banded = spec.band is not None
    n = r.shape[0]
    jj = jnp.arange(n)
    # one query sample against every reference sample: scalars, or
    # feature vectors (q (M, D) against r (N, D))
    costs = spec.cell_cost if r.ndim == 1 else spec.feature_cost

    # Virtual row -1 is all zeros (free start): D[0, j] = cost(0, j). For
    # hard-min that is min(D[-1,j]=0, D[0,j-1]>=0, D[-1,j-1]=0) = 0; for
    # soft-min the free start is the same exact-zero boundary (matching
    # the engine's free_start mask).
    row0 = costs(q[0], r)
    starts0 = jj.astype(jnp.int32)          # row 0: a path starts HERE
    if banded:
        ok0 = spec.band_valid(0, jj)
        row0 = jnp.where(ok0, row0, big)
        starts0 = jnp.where(ok0, starts0, NO_WINDOW)

    def row_step(carry, xs):
        prev_row, prev_starts = carry
        if banded:
            qi, i = xs
            valid = spec.band_valid(i, jj)
        else:
            qi = xs
        cost = costs(qi, r)

        def col_step(carry, cxs):
            left, upleft, s_left, s_upleft = carry
            if banded:
                c, up, s_up, ok = cxs
            else:
                c, up, s_up = cxs
            val = spec.cell_update(c, left, up, upleft)
            if return_window:
                start = spec.start3(left, up, upleft,
                                    s_left, s_up, s_upleft)
            else:
                start = s_left
            if banded:
                # out-of-band cells must read as blocked to their
                # neighbours, exactly like the engine's masked diagonals
                val = jnp.where(ok, val, big)
                start = jnp.where(ok, start, NO_WINDOW)
            return (val, up, start, s_up), (val, start)

        cxs = ((cost, prev_row, prev_starts, valid) if banded
               else (cost, prev_row, prev_starts))
        neg = jnp.asarray(NO_WINDOW, jnp.int32)
        _, (row, starts) = lax.scan(col_step, (big, big, neg, neg), cxs)
        return (row, starts), None

    if banded:
        xs = (q[1:], jnp.arange(1, q.shape[0]))
    else:
        xs = q[1:]
    (last_row, last_starts), _ = lax.scan(row_step, (row0, starts0), xs)
    end = jnp.argmin(last_row)
    if spec.soft:
        cost = -spec.gamma * jax.nn.logsumexp(-last_row / spec.gamma)
        # whole bottom row masked (band blocks it): +inf, like hard-min
        # and the numpy oracle, not the finite ~SOFT_BIG logsumexp
        cost = jnp.where(last_row[end] >= big / 2,
                         jnp.asarray(jnp.inf, cost.dtype), cost)
        return cost, end
    if return_window:
        return last_row[end], last_starts[end], end
    return last_row[end], end


def _dp_rowscan_single(q: jnp.ndarray, r: jnp.ndarray, spec: DPSpec,
                       return_window: bool = False):
    """Row-by-row scan of the non-sdtw recurrence families (twed / erp
    / local) for one (query, reference) pair.

    Same shape as :func:`_sdtw_rowscan_single` — sequential over both
    axes — but every cell goes through ``spec.family_cell``, the single
    definition the engine and the Pallas kernel also execute, so the
    three sweeps agree bit-for-bit on hard objectives.  Boundary
    conditions are injected by ``family_cell`` itself (the scan seeds
    carries with ``big`` garbage that every family overwrites at
    row/column 0), and the fold follows the family's
    :class:`~repro.core.spec.RecurrenceSpec`:

    * ``corner`` (twed / erp): the answer is ``D[m-1, n-1]``; a band
      that disconnects the corner reads as blocked -> ``(inf, 0)``;
    * ``cells`` (local): the lexicographic ``(value, column)`` minimum
      over every valid cell (hard), or the soft-min over all valid
      cells with the hard minimizer's column as the end (soft).
    """
    fam = spec.family
    local = fam == "local"
    if q.ndim != 1:
        raise ValueError(f"family {fam!r} is univariate: multivariate "
                         "(M, D) series run the sdtw family only")
    if return_window and local:
        raise ValueError(
            "return_window is undefined for the local family: a local "
            "alignment's span needs a full backtrack, not a start lane")
    big = jnp.asarray(spec.big, q.dtype)
    banded = spec.band is not None
    m, n = q.shape[0], r.shape[0]
    jj = jnp.arange(n)
    zero_r = jnp.zeros_like(r)
    zero_q = jnp.zeros_like(q)
    if fam == "twed":
        r_prev = jnp.concatenate([jnp.zeros((1,), r.dtype), r[:-1]])
        q_prev = jnp.concatenate([jnp.zeros((1,), q.dtype), q[:-1]])
        bt, bl = zero_r, zero_q
    elif fam == "erp":
        bt = jnp.cumsum(spec.cell_cost(r, spec.gap))
        bl = jnp.cumsum(spec.cell_cost(q, spec.gap))
        r_prev, q_prev = zero_r, zero_q
    else:
        r_prev, q_prev, bt, bl = zero_r, zero_q, zero_r, zero_q
    j_max = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)

    def row_step(carry, xs):
        prev_row, best, best_j, mx, s = carry
        qi, qpi, bli, i = xs

        def col_step(c, cxs):
            left, upleft = c
            rj, rpj, btj, up, j = cxs[:5]
            val = spec.family_cell(qi, rj, left, up, upleft, i=i, j=j,
                                   is_row0=i == 0, is_col0=j == 0,
                                   q_prev=qpi, r_prev=rpj,
                                   top_boundary=btj, left_boundary=bli)
            if banded:
                val = jnp.where(cxs[5], val, big)
            return (val, up), val

        cxs = (r, r_prev, bt, prev_row, jj)
        if banded:
            cxs = cxs + (spec.band_valid(i, jj),)
        (_, _), row = lax.scan(col_step, (big, big), cxs)
        if local:
            # lexicographic (value, column) streaming minimum; rows
            # ascend, so ties keep the first-seen row automatically
            v = jnp.min(row)
            jm = jnp.min(jnp.where(row == v, jj.astype(jnp.int32), j_max))
            take = (v < best) | ((v == best) & (jm < best_j))
            best = jnp.where(take, v, best)
            best_j = jnp.where(take, jm, best_j)
            if spec.soft:
                x = -row / spec.gamma       # masked cells underflow to 0
                row_mx = jnp.max(x)
                m_new = jnp.maximum(mx, row_mx)
                s = s * soft_exp(mx - m_new) + jnp.sum(soft_exp(x - m_new))
                mx = m_new
        return (row, best, best_j, mx, s), None

    init = (jnp.full((n,), big, q.dtype), big,
            j_max, jnp.asarray(-INF, q.dtype),
            jnp.zeros((), q.dtype))
    xs = (q, q_prev, bl, jnp.arange(m))
    (last_row, best, best_j, mx, s), _ = lax.scan(row_step, init, xs)
    if local:
        end = best_j
        if spec.soft:
            return -spec.gamma * (mx + soft_log(s)), end
        return best, end
    # corner fold (global families)
    corner = last_row[n - 1]
    blocked = corner >= big / 2 if spec.soft else jnp.isinf(corner)
    cost = jnp.where(blocked, jnp.asarray(jnp.inf, corner.dtype), corner)
    end = jnp.where(blocked, 0, n - 1)
    if return_window:
        start = jnp.where(blocked, NO_WINDOW, 0)
        return cost, start, end
    return cost, end


def sdtw_ref(queries: jnp.ndarray, reference: jnp.ndarray,
             spec: DPSpec | None = None, *,
             return_window: bool = False):
    """Batched scan-based sDTW oracle.

    queries:   (B, M) float, or (B, M, D) multivariate (sdtw family)
    reference: (N,) shared or (B, N) per-query; (N, D) or (B, N, D)
               for multivariate queries
    spec:      recurrence spec; None = squared-Euclidean hard-min unbanded
    return_window: also return the matched windows' start columns
               (hard-min specs only)
    returns:   (costs (B,), end_indices (B,)), or
               (costs (B,), starts (B,), ends (B,)) when ``return_window``
    """
    spec = DEFAULT_SPEC if spec is None else spec
    if return_window and spec.soft:
        raise ValueError(
            "return_window needs a hard-min spec: soft-min has no argmin "
            "path (use repro.align.soft.expected_alignment)")
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    single = functools.partial(
        _sdtw_rowscan_single if spec.family == "sdtw"
        else _dp_rowscan_single,
        spec=spec, return_window=return_window)
    if reference.ndim == queries.ndim - 1:         # one shared reference
        fn = jax.vmap(single, in_axes=(0, None))
    else:
        fn = jax.vmap(single, in_axes=(0, 0))
    return fn(queries, reference)
