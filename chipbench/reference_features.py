"""Plain multivariate subsequence-DTW reference, independent of the
program under test, for archives too long for one step per
anti-diagonal.

It z-normalizes each feature over time on the host in float64 and
sweeps the DP on the device in ``jax.numpy``, row by row.  The
recurrence is the paper's with the squared Euclidean cost of feature
vectors::

    c[i, j] = sum over d of (q[i, d] - r[j, d])**2
    D[i, j] = c[i, j] + min(D[i-1, j], D[i, j-1], D[i-1, j-1])

with a free start (``D[-1, j] = 0`` for every j).  Along a row the
recurrence is ``D[i, j] = min(a[j], D[i, j-1] + c[i, j])`` with
``a[j] = c[i, j] + min(D[i-1, j], D[i-1, j-1])``: an affine map in the
min-plus algebra for each column, whose prefix compositions a scan by
doubling computes in log2(block) passes.  The archive is swept in
blocks of columns, carrying each row's last value from block to block.
Nothing here imports ``repro``.

A sweep returns, per query, the minimum of the bottom row, its first
argmin, and the bottom-row value at a requested column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def znorm_time(x: np.ndarray) -> np.ndarray:
    """Z-normalize each feature over time, axis -2, in float64
    (population std): x (..., L, D)."""
    x = np.asarray(x, np.float64)
    mu = x.mean(axis=-2, keepdims=True)
    sd = x.std(axis=-2, keepdims=True)
    return (x - mu) / np.maximum(sd, 1e-300)


def znorm_archive(r: np.ndarray) -> np.ndarray:
    """:func:`znorm_time` of one (N, D) archive, one feature at a time
    (so the float64 copy is one column), returned as float32."""
    r = np.asarray(r)
    out = np.empty(r.shape, np.float32)
    for d in range(r.shape[1]):
        col = r[:, d].astype(np.float64)
        out[:, d] = (col - col.mean()) / max(col.std(), 1e-300)
    return out


def _minplus_scan(c, a):
    """Inclusive prefix compositions, along axis 1, of the maps
    ``x -> min(a, x + c)``: (C, A) with ``x_j = min(A_j, x_{-1} + C_j)``
    for the value ``x_{-1}`` before the first column."""
    width = c.shape[1]
    k = 1
    while k < width:
        c_prev = jnp.pad(c[:, :-k], ((0, 0), (k, 0)))
        a_prev = jnp.pad(a[:, :-k], ((0, 0), (k, 0)),
                         constant_values=jnp.inf)
        a = jnp.minimum(a, a_prev + c)
        c = c_prev + c
        k *= 2
    return c, a


@functools.partial(jax.jit, static_argnames=("block",))
def _sweep_block(q, r_t, j0, n, last, best, arg, at, target, *, block):
    """One block of columns [j0, j0 + block) for every query.

    q (P, M, D), r_t (D, N_pad); ``last`` (M, P) holds D[i, j0 - 1]
    (inf before the first block); best/arg/at (P,) fold the bottom row.
    Returns the block's (last, best, arg, at)."""
    P, M, D = q.shape
    dt = q.dtype
    inf = jnp.asarray(jnp.inf, dt)
    r = jax.lax.dynamic_slice_in_dim(r_t, j0, block, axis=1)   # (D, blk)
    cols = j0 + jnp.arange(block, dtype=jnp.int32)
    valid = cols < n

    def row(i, state):
        prev, prev_last, new_last = state
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        c = (qi[:, 0:1] - r[0][None, :]) ** 2
        for d in range(1, D):
            c = c + (qi[:, d:d + 1] - r[d][None, :]) ** 2
        c = jnp.where(valid[None, :], c, inf)
        upleft = jnp.concatenate([prev_last[:, None], prev[:, :-1]], 1)
        a = c + jnp.minimum(prev, upleft)
        cs, as_ = _minplus_scan(c, a)
        x = jnp.minimum(as_, last[i][:, None] + cs)
        return x, last[i], new_last.at[i].set(x[:, -1])

    # row -1 is the free start: zeros, and zero before the block
    zero = jnp.zeros((P,), dt)
    bottom, _, new_last = jax.lax.fori_loop(
        0, M, row, (jnp.zeros((P, block), dt), zero, last))
    v = jnp.where(valid[None, :], bottom, inf)
    k = jnp.argmin(v, axis=1).astype(jnp.int32)
    vb = jnp.take_along_axis(v, k[:, None], 1)[:, 0]
    better = vb < best
    best = jnp.where(better, vb, best)
    arg = jnp.where(better, j0 + k, arg)
    hit = (target >= j0) & (target < j0 + block)
    tv = jnp.take_along_axis(
        v, jnp.clip(target - j0, 0, block - 1)[:, None], 1)[:, 0]
    at = jnp.where(hit, tv, at)
    return new_last, best, arg, at


def sweep(queries, archive, target=None, *, dtype=jnp.float32,
          block: int = 65536):
    """Bottom-row summary of each query against the archive.

    queries (P, M, D) and archive (N, D) are already normalized (host
    arrays).  ``target`` (P,) names the column whose bottom-row value
    is returned (-1: none).  Returns float64 host arrays (best,
    argbest, at_target)."""
    queries = np.asarray(queries)
    P, M, D = queries.shape
    N = archive.shape[0]
    block = min(block, int(2 ** np.ceil(np.log2(max(N, 2)))))
    n_pad = -(-N // block) * block
    target = (np.full(P, -1, np.int32) if target is None
              else np.asarray(target, np.int32))
    r_t = jnp.pad(jnp.asarray(np.asarray(archive).T, dtype),
                  ((0, 0), (0, n_pad - N)))
    q = jnp.asarray(queries, dtype)
    inf = jnp.asarray(jnp.inf, dtype)
    last = jnp.full((M, P), inf, dtype)
    best = jnp.full((P,), inf, dtype)
    arg = jnp.zeros((P,), jnp.int32)
    at = jnp.full((P,), inf, dtype)
    tg = jnp.asarray(target)
    for j0 in range(0, n_pad, block):
        last, best, arg, at = _sweep_block(
            q, r_t, jnp.int32(j0), jnp.int32(N), last, best, arg, at, tg,
            block=block)
    return (np.asarray(best.astype(jnp.float32), np.float64),
            np.asarray(arg), np.asarray(at.astype(jnp.float32), np.float64))
