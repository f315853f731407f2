"""Anti-diagonal (wavefront) sDTW engine — the paper's parallel pattern
expressed at the XLA level, parameterized by a ``DPSpec``.

The DP matrix is swept along anti-diagonals t = i + j; every cell on a
diagonal is independent, so each scan step is one fused vector op of
width M (the query length), vectorized again over the batch.  This is the
same wavefront the paper's kernel executes across GPU threads (§5.2);
here XLA's vector units play the role of the wavefront and the two
rotating diagonal buffers play the role of the per-thread double buffers.

The recurrence itself — per-cell cost, 3-way reduction (hard- or
soft-min), Sakoe–Chiba band mask — comes from ``repro.core.spec.DPSpec``
via ``spec.cell_cost`` / ``spec.cell_update`` / ``spec.band_valid``.
Spec fields are static under jit, so the default (unbanded hard-min
squared-Euclidean) spec compiles the exact graph this engine always
compiled, and a soft-min spec recovers the former ``core.softdtw`` fork:
the streaming bottom-row reduction becomes a running-max logsumexp of
``-D[M-1, j] / gamma`` (the underflow-safe analogue of the paper's
streaming ``__hmin2`` fold), and the whole map queries -> cost is
differentiable (see examples/audio_align.py).

For both reductions the end index is the argmin of the bottom row —
for soft-min that is the position whose smoothed alignment cost is
lowest, which converges to the hard end index as gamma -> 0.

This module is the RAW tuple-level layer: ``sdtw_engine`` returns
``(costs, ends)`` / ``(costs, starts, ends)`` for the backend adapter
in ``repro.backends.builtin`` to wrap into a typed
``repro.core.result.SDTWResult``.  Public callers go through
``repro.sdtw`` / ``repro.Aligner``, which also pick the sweep outputs
(``ExecutionPlan.outputs``) so cost, end and start all come from this
ONE fused sweep.

Complexity: (M + N - 1) scan steps of O(M) vector work ≈ O(M·N + M²).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.spec import (DEFAULT_SPEC, DPSpec, INF,  # noqa: F401
                             NO_WINDOW, SOFT_BIG, soft_exp, soft_log)
# INF re-exported for backward compatibility (engine.INF predates spec.py)


@functools.partial(jax.jit, static_argnames=("spec", "return_end",
                                             "return_window",
                                             "accum_dtype"))
def sdtw_engine(queries: jnp.ndarray,
                reference: jnp.ndarray,
                *,
                spec: DPSpec | None = None,
                return_end: bool = True,
                return_window: bool = False,
                accum_dtype=None):
    """Batched anti-diagonal sDTW under ``spec``.

    queries:   (B, M)
    reference: (N,) shared across the batch (the paper's setting) or (B, N)
    spec:      recurrence spec; None = squared-Euclidean hard-min unbanded
    return_window: also propagate the matched window's START column
               through the recurrence (``spec.start3``) — one extra
               int32 lane pair riding the same O(M) diagonal carries, no
               second sweep.  Hard-min specs only.  Returns
               (costs, starts, ends).
    accum_dtype: overrides ``spec.accum_dtype`` when given (kept for the
               benchmark harnesses that lower ``sdtw_engine.__wrapped__``)
    returns:   costs (B,) [, end_indices (B,)], or (costs, starts, ends)
               when ``return_window``

    Input validation lives in ``core.api.sdtw`` /
    ``search.SearchService`` (the shared validator in ``core.spec``);
    this function assumes well-shaped arrays.
    """
    spec = DEFAULT_SPEC if spec is None else spec
    if return_window and spec.soft:
        raise ValueError(
            "return_window needs a hard-min spec: soft-min has no argmin "
            "path (use repro.align.soft.expected_alignment)")
    if spec.family != "sdtw":
        return _dp_engine(queries, reference, spec=spec,
                          return_end=return_end,
                          return_window=return_window,
                          accum_dtype=accum_dtype)
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    B, M = queries.shape
    shared_ref = reference.ndim == 1
    N = reference.shape[-1]
    dt = jnp.dtype(accum_dtype) if accum_dtype is not None else spec.accum
    soft = spec.soft

    q = queries.astype(dt)
    r = reference.astype(dt)

    # §Perf part 2 iter 1: reverse the reference ONCE so each diagonal is
    # a contiguous slice — v[i] = r[t-i] = r_rev[(N-1-t) + i] — instead of
    # a slice + per-step flip (one fewer (B, M)-sized pass per diagonal).
    rev = jnp.flip(r, axis=-1)
    pad = ((M - 1, M - 1),) if shared_ref else ((0, 0), (M - 1, M - 1))
    r_ext = jnp.pad(rev, pad)

    ii = jnp.arange(M)

    def diag_vals(t):
        """v[i] = r[t - i] for i in 0..M-1 (masked elsewhere)."""
        start = N - 1 - t + (M - 1)
        if shared_ref:
            return lax.dynamic_slice(r_ext, (start,), (M,))
        return lax.dynamic_slice(r_ext, (0, start), (B, M))

    big = jnp.asarray(spec.big, dt)

    def step(carry, t):
        if soft:
            d1, d2, m_run, s_run, best, best_j = carry
        elif return_window:
            d1, d2, s1, s2, best, best_j, best_s = carry
        else:
            d1, d2, best, best_j = carry
        # cell (i, t-i):
        #   left   = D[i,   t-1-i] = d1[i]
        #   up     = D[i-1, t-i  ] = d1[i-1]
        #   upleft = D[i-1, t-1-i] = d2[i-1]
        rv = diag_vals(t)                      # (M,) or (B, M)
        cost = spec.cell_cost(q, rv)           # (B, M) via broadcast
        up = jnp.roll(d1, 1, axis=-1)
        upleft = jnp.roll(d2, 1, axis=-1)
        # i == 0: virtual row -1 is all zeros -> free subsequence start
        d0 = spec.cell_update(cost, d1, up, upleft, free_start=(ii == 0))
        # mask invalid cells (j = t - i outside [0, N-1], or out of band)
        j = t - ii
        valid = (j >= 0) & (j < N)
        in_band = spec.band_valid(ii, j)
        if in_band is not None:
            valid = valid & in_band
        d0 = jnp.where(valid, d0, big)
        if return_window:
            # the start column rides the same diagonal carries: row 0
            # cells BEGIN a path at their own column, every other cell
            # inherits the start of the predecessor hard-min picked
            s0_ = spec.start3(d1, up, upleft, s1,
                              jnp.roll(s1, 1, axis=-1),
                              jnp.roll(s2, 1, axis=-1))
            s0_ = jnp.where(ii == 0, j.astype(jnp.int32), s0_)
            s0_ = jnp.where(valid, s0_, NO_WINDOW)
        # streaming bottom-row reduction (paper's folded __hmin2): the
        # running (min, argmin) pair doubles as the soft path's end index
        bottom = d0[..., M - 1]
        bottom_valid = (t >= M - 1) & (t - (M - 1) < N)
        cand = jnp.where(bottom_valid, bottom, big)
        take = cand < best
        best = jnp.where(take, cand, best)
        best_j = jnp.where(take, t - (M - 1), best_j)
        if soft:
            # streaming soft-min over the bottom row via a running-max
            # logsumexp of x = -D[M-1, j] / gamma (underflow-safe)
            x = jnp.where(bottom_valid, -bottom / spec.gamma, -SOFT_BIG)
            m_new = jnp.maximum(m_run, x)
            s_run = s_run * soft_exp(m_run - m_new) + soft_exp(x - m_new)
            return (d0, d1, m_new, s_run, best, best_j), None
        if return_window:
            best_s = jnp.where(take, s0_[..., M - 1], best_s)
            return (d0, d1, s0_, s1, best, best_j, best_s), None
        return (d0, d1, best, best_j), None

    d_init = jnp.full((B, M), big, dt)
    best0 = jnp.full((B,), big, dt)
    bj0 = jnp.zeros((B,), jnp.int32)
    if soft:
        m0 = jnp.full((B,), -SOFT_BIG, dt)
        s0 = jnp.zeros((B,), dt)
        carry, _ = lax.scan(step, (d_init, d_init, m0, s0, best0, bj0),
                            jnp.arange(M + N - 1))
        _, _, m_run, s_run, best, best_j = carry
        cost_out = -spec.gamma * (m_run + soft_log(s_run))
        # no reachable bottom cell (e.g. the band blocks the whole
        # bottom row): the logsumexp of SOFT_BIG-masked cells is a
        # finite ~SOFT_BIG value — report +inf like the hard path and
        # the numpy oracle do. `best` is the hard min of the bottom
        # cells, so best >= SOFT_BIG/2 iff every one was masked.
        blocked = best >= jnp.asarray(SOFT_BIG / 2, dt)
        cost_out = jnp.where(blocked, jnp.asarray(INF, dt), cost_out)
    elif return_window:
        s_init = jnp.full((B, M), NO_WINDOW, jnp.int32)
        # NO_WINDOW: survives when no bottom cell is ever
        # reachable (e.g. a band blocking the whole bottom row), matching
        # ref and the backtrack oracle
        bs0 = jnp.full((B,), NO_WINDOW, jnp.int32)
        carry, _ = lax.scan(step,
                            (d_init, d_init, s_init, s_init, best0, bj0,
                             bs0),
                            jnp.arange(M + N - 1))
        _, _, _, _, cost_out, best_j, best_s = carry
        return cost_out, best_s, best_j
    else:
        carry, _ = lax.scan(step, (d_init, d_init, best0, bj0),
                            jnp.arange(M + N - 1))
        _, _, cost_out, best_j = carry
    if return_end:
        return cost_out, best_j
    return cost_out


def _dp_engine(queries, reference, *, spec: DPSpec, return_end: bool,
               return_window: bool, accum_dtype):
    """Anti-diagonal sweep of the non-sdtw recurrence families.

    Same wavefront as :func:`sdtw_engine` — (M + N - 1) scan steps over
    rotating diagonal buffers — but every cell goes through
    ``spec.family_cell`` (the single definition the rowscan ref and the
    Pallas kernel also execute) and the fold follows the family's
    :class:`~repro.core.spec.RecurrenceSpec`: the global families
    (twed / erp) read the single corner cell ``D[M-1, N-1]``, the local
    family streams a lexicographic ``(value, column)`` minimum (plus a
    running logsumexp for soft) over EVERY valid cell.  Boundary
    conditions live inside ``family_cell``, so the wrap-around of the
    rolled diagonal buffers at row 0 is overwritten, never read.
    """
    fam = spec.family
    local = fam == "local"
    if return_window and local:
        raise ValueError(
            "return_window is undefined for the local family: a local "
            "alignment's span needs a full backtrack, not a start lane")
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    B, M = queries.shape
    shared_ref = reference.ndim == 1
    N = reference.shape[-1]
    dt = jnp.dtype(accum_dtype) if accum_dtype is not None else spec.accum
    soft = spec.soft

    q = queries.astype(dt)
    r = reference.astype(dt)
    pad = ((M - 1, M - 1),) if shared_ref else ((0, 0), (M - 1, M - 1))

    def ext(x):
        """Reversed + padded reference-like array: one contiguous
        diagonal slice per step (same layout trick as sdtw_engine)."""
        return jnp.pad(jnp.flip(x, axis=-1), pad)

    r_ext = ext(r)
    if fam == "twed":
        zero_col = jnp.zeros(r.shape[:-1] + (1,), dt)
        r_prev_ext = ext(jnp.concatenate([zero_col, r[..., :-1]], axis=-1))
        q_prev = jnp.concatenate([jnp.zeros((B, 1), dt), q[:, :-1]],
                                 axis=-1)
        bt_ext, bl = None, None
    elif fam == "erp":
        bt_ext = ext(jnp.cumsum(spec.cell_cost(r, spec.gap), axis=-1))
        bl = jnp.cumsum(spec.cell_cost(q, spec.gap), axis=-1)   # (B, M)
        r_prev_ext, q_prev = None, None
    else:
        r_prev_ext, q_prev, bt_ext, bl = None, None, None, None

    ii = jnp.arange(M)
    j_max = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    big = jnp.asarray(spec.big, dt)
    corner_t = (M - 1) + (N - 1)

    def diag_vals(x_ext, t):
        start = N - 1 - t + (M - 1)
        if shared_ref:
            return lax.dynamic_slice(x_ext, (start,), (M,))
        return lax.dynamic_slice(x_ext, (0, start), (B, M))

    def step(carry, t):
        if local and soft:
            d1, d2, best, best_j, m_run, s_run = carry
        else:
            d1, d2, best, best_j = carry
        rv = diag_vals(r_ext, t)
        rpv = diag_vals(r_prev_ext, t) if fam == "twed" else None
        btv = diag_vals(bt_ext, t) if fam == "erp" else None
        up = jnp.roll(d1, 1, axis=-1)
        upleft = jnp.roll(d2, 1, axis=-1)
        j = t - ii
        d0 = spec.family_cell(q, rv, d1, up, upleft, i=ii, j=j,
                              is_row0=ii == 0, is_col0=j == 0,
                              q_prev=q_prev, r_prev=rpv,
                              top_boundary=btv, left_boundary=bl)
        valid = (j >= 0) & (j < N)
        in_band = spec.band_valid(ii, j)
        if in_band is not None:
            valid = valid & in_band
        d0 = jnp.where(valid, d0, big)
        if local:
            # lexicographic (value, column) streaming minimum over every
            # valid cell; diagonals ascend in t, so equal (value, column)
            # ties keep the first-seen row automatically.  The big/2
            # guard drops fully-masked diagonals (band=0 odd t), whose
            # "minimum" is the sentinel at a garbage column.
            v = jnp.min(d0, axis=-1)
            jm = jnp.min(jnp.where(d0 == v[..., None],
                                   j.astype(jnp.int32), j_max), axis=-1)
            take = ((v < best) | ((v == best) & (jm < best_j))) \
                & (v < big / 2)
            best = jnp.where(take, v, best)
            best_j = jnp.where(take, jm, best_j)
            if soft:
                x = -d0 / spec.gamma    # masked cells underflow to 0
                m_new = jnp.maximum(m_run, jnp.max(x, axis=-1))
                s_run = s_run * soft_exp(m_run - m_new) \
                    + jnp.sum(soft_exp(x - m_new[..., None]), axis=-1)
                return (d0, d1, best, best_j, m_new, s_run), None
        else:
            # corner fold: the single cell (M-1, N-1) lives on the last
            # diagonal's bottom lane; a masked corner never takes
            # (strict <), leaving the blocked sentinel + end 0
            cand = jnp.where(t == corner_t, d0[..., M - 1], big)
            take = cand < best
            best = jnp.where(take, cand, best)
            best_j = jnp.where(take, N - 1, best_j)
        return (d0, d1, best, best_j), None

    d_init = jnp.full((B, M), big, dt)
    best0 = jnp.full((B,), big, dt)
    bj0 = (jnp.full((B,), j_max, jnp.int32) if local
           else jnp.zeros((B,), jnp.int32))
    ts = jnp.arange(M + N - 1)
    if local and soft:
        m0 = jnp.full((B,), -jnp.inf, dt)
        s0 = jnp.zeros((B,), dt)
        carry, _ = lax.scan(step, (d_init, d_init, best0, bj0, m0, s0), ts)
        _, _, best, best_j, m_run, s_run = carry
        cost_out = -spec.gamma * (m_run + soft_log(s_run))
        end = best_j
    else:
        carry, _ = lax.scan(step, (d_init, d_init, best0, bj0), ts)
        _, _, best, best_j = carry
        if local:
            cost_out, end = best, best_j
        elif soft:
            # blocked corner: either never taken (best == big) or a
            # sum-of-sentinels value — both read as >= big/2 -> +inf
            blocked = best >= big / 2
            cost_out = jnp.where(blocked, jnp.asarray(INF, dt), best)
            end = jnp.where(blocked, 0, best_j)
        else:
            cost_out, end = best, best_j    # blocked corner is inf already
    if return_window:
        start = jnp.where(jnp.isinf(cost_out), NO_WINDOW, 0)
        return cost_out, start, end
    if return_end:
        return cost_out, end
    return cost_out
