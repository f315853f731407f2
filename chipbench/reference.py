"""Plain subsequence-DTW reference, independent of the program under test.

It z-normalizes on the host in float64 and sweeps the DP on the device by
anti-diagonals in ``jax.numpy``: one ``lax.fori_loop`` step per diagonal,
every (query, reference) pair of a block side by side.  Nothing here
imports ``repro``.

The recurrence is the paper's (arXiv 2403.06931, section 2)::

    D[i, j] = (q[i] - r[j])**2 + min(D[i-1, j], D[i, j-1], D[i-1, j-1])

with a free start (``D[-1, j] = 0`` for every j), as subsequence DTW
has it.  A sweep returns, per pair, the minimum of the bottom row, its
first argmin, and the bottom-row value at a requested column.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def znorm(x: np.ndarray) -> np.ndarray:
    """Z-normalize along the last axis in float64 (population std)."""
    x = np.asarray(x, np.float64)
    mu = x.mean(axis=-1, keepdims=True)
    sd = x.std(axis=-1, keepdims=True)
    return (x - mu) / np.maximum(sd, 1e-300)


@jax.jit
def _sweep(q, refs, ref_of, target):
    """q (P, M), refs (R, N), ref_of (P,) int32, target (P,) int32 ->
    (best (P,), argbest (P,) int32, at_target (P,)) of the bottom row."""
    P, M = q.shape
    N = refs.shape[1]
    dt = q.dtype
    inf = jnp.asarray(jnp.inf, dt)
    # reversed references padded by M on both sides: the diagonal t
    # reads r[t - i] for i = 0..M-1 as one contiguous slice
    rext = jnp.pad(jnp.flip(refs, axis=1), ((0, 0), (M, M)))
    ii = jnp.arange(M, dtype=jnp.int32)

    def step(t, carry):
        d1, d2, best, arg, at = carry
        start = N - 1 - t + M
        rd = jax.vmap(lambda row: jax.lax.dynamic_slice(row, (start,),
                                                        (M,)))(rext)
        r = rd[ref_of]                                   # (P, M)
        c = (q - r) * (q - r)
        j = t - ii
        zero = jnp.zeros((P, 1), dt)
        up = jnp.concatenate([zero, d1[:, :-1]], 1)
        upleft = jnp.concatenate([zero, d2[:, :-1]], 1)
        d0 = c + jnp.minimum(jnp.minimum(up, d1), upleft)
        d0 = jnp.where((j >= 0) & (j < N), d0, inf)
        jb = t - (M - 1)
        v = d0[:, M - 1]
        ok = (jb >= 0) & (jb < N)
        better = ok & (v < best)
        best = jnp.where(better, v, best)
        arg = jnp.where(better, jb, arg)
        at = jnp.where(ok & (target == jb), v, at)
        return d0, d1, best, arg, at

    d = jnp.full((P, M), inf, dt)
    init = (d, d, jnp.full((P,), inf, dt), jnp.zeros((P,), jnp.int32),
            jnp.full((P,), inf, dt))
    _, _, best, arg, at = jax.lax.fori_loop(0, M + N - 1, step, init)
    return best, arg, at


def sweep(queries, refs, ref_of, target=None, *, dtype=jnp.float32,
          block: int = 512):
    """Bottom-row summary of (query, reference) pairs, in blocks of
    ``block`` pairs so that any number of pairs fits.

    queries (P, M) and refs (R, N) are already normalized (host arrays);
    pair p aligns ``queries[p]`` against ``refs[ref_of[p]]``.  ``target``
    (P,) names the column whose bottom-row value is returned (-1: none).
    Returns float64 host arrays (best, argbest, at_target).
    """
    queries = np.asarray(queries)
    P = queries.shape[0]
    ref_of = np.asarray(ref_of, np.int32)
    target = (np.full(P, -1, np.int32) if target is None
              else np.asarray(target, np.int32))
    refs_d = jnp.asarray(np.asarray(refs), dtype)
    out = [[], [], []]
    for lo in range(0, P, block):
        hi = min(P, lo + block)
        pad = block - (hi - lo) if P > block else 0
        sl = slice(lo, hi)
        q = np.pad(queries[sl], ((0, pad), (0, 0)))
        ro = np.pad(ref_of[sl], (0, pad))
        tg = np.pad(target[sl], (0, pad), constant_values=-1)
        res = _sweep(jnp.asarray(q, dtype), refs_d, jnp.asarray(ro),
                     jnp.asarray(tg))
        for k, a in enumerate(res):
            out[k].append(np.asarray(a.astype(jnp.float32)
                                     if a.dtype != jnp.int32 else a)
                          [:hi - lo])
    best, arg, at = (np.concatenate(o) for o in out)
    return best.astype(np.float64), arg, at.astype(np.float64)
