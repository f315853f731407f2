"""Distributed sDTW — the paper's wavefront structure lifted to a mesh.

Two composable levels:

1. **Query-batch data parallelism** over the ``("pod", "data")`` axes —
   the paper's block-per-query batching: sDTW is embarrassingly parallel
   over queries, so each device simply runs the engine on its shard.

2. **Reference sharding** over the ``"model"`` axis with a
   ``lax.ppermute`` boundary pipeline — the multi-chip generalization of
   the paper's inter-wavefront shared-memory strip (§5.2): the DP matrix
   is tiled into (row-block × reference-chunk) blocks; device *m* owns
   chunk *m*; at pipeline step *s* device *m* computes row-block
   ``s - m`` and forwards its right boundary column to device ``m+1``.
   The strip that was double-buffered shared memory on one GPU becomes a
   single ICI hop of ``row_block`` floats per query per step.

The final subsequence min is a ``pmin`` tree-reduce over the model axis
(the cross-device analogue of the paper's streaming ``__hmin2`` fold).

Raw tuple-level layer: ``repro.backends.builtin`` caches the built
shard_map pipeline per (mesh, spec, layout) and adapts its
``(costs, ends)`` into typed ``SDTWResult`` pytrees; ``repro.Aligner``
sessions dispatch straight to that cache (no outer jit needed — the
pipeline is already compiled).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.spec import DEFAULT_SPEC, DPSpec
from repro.core.spec import INF as _SPEC_INF

INF = jnp.float32(_SPEC_INF)


def sdtw_block(q_block: jnp.ndarray,
               r_chunk: jnp.ndarray,
               top: jnp.ndarray,
               left: jnp.ndarray,
               corner: jnp.ndarray,
               *,
               spec: DPSpec = DEFAULT_SPEC,
               i0=None,
               j0=None):
    """DP over one (row-block × reference-chunk) tile, batched over queries.

    q_block: (B, Rb)   query rows of this block
    r_chunk: (C,)      reference columns of this chunk
    top:     (B, C)    D[i0-1, j0:j0+C]   (virtual row above the tile)
    left:    (B, Rb)   D[i0:i0+Rb, j0-1]  (virtual column left of the tile)
    corner:  (B,)      D[i0-1, j0-1]
    spec:    recurrence spec (hard-min reductions only — soft-min's
             streaming readout does not tree-reduce across chunks)
    i0, j0:  the tile's global (row, column) offset, required when
             ``spec.band`` is set: the Sakoe–Chiba mask is a *global*
             |i - j| <= band predicate folded into each tile's local
             anti-diagonal index math
    returns  (bottom_row (B, C), right_col (B, Rb))

    §Perf part 2 iter 2: boundary-aware ANTI-DIAGONAL sweep, vectorized
    over the Rb tile rows (the same wavefront as core.engine, with the
    tile's top/left/corner boundaries injected) — Rb+C-1 scan steps of
    (B, Rb) vector work instead of the previous Rb*C sequential scalar
    column scan (~40x fewer steps, each one a fused VPU op).
    """
    B, Rb = q_block.shape
    C = r_chunk.shape[0]
    dt = q_block.dtype
    inf = jnp.asarray(INF, dt)
    ii = jnp.arange(Rb)

    # rv[i] = r[t - i] as a contiguous slice of the reversed chunk
    r_ext = jnp.pad(jnp.flip(r_chunk), (Rb - 1, Rb - 1))
    # top row padded for dynamic_slice at t in [0, Rb+C-2]
    topp = jnp.pad(top, ((0, 0), (0, Rb)), constant_values=INF)
    # topc[:, t] = D[-1, t-1]: corner at t=0, top[t-1] after
    topc = jnp.pad(jnp.concatenate([corner[:, None], top], axis=1),
                   ((0, 0), (0, Rb)), constant_values=INF)
    left_m1 = jnp.concatenate([corner[:, None], left[:, :-1]], axis=1)

    def step(carry, t):
        d1, d2, bottom, right = carry
        j = t - ii                                     # (Rb,)
        rv = lax.dynamic_slice(r_ext, (C - 1 - t + Rb - 1,), (Rb,))
        cost = spec.cell_cost(q_block, rv[None, :])    # (B, Rb)

        top_t = lax.dynamic_slice(topp, (0, jnp.minimum(t, C + Rb - 1)),
                                  (B, 1))              # D[-1, t]
        topc_t = lax.dynamic_slice(topc, (0, t), (B, 1))   # D[-1, t-1]

        # left value D[i, j-1]  (diag t-1, row i; boundary when j == 0)
        lf = jnp.where((ii == t)[None, :], left, d1)
        # up value D[i-1, j]    (diag t-1, row i-1; boundary when i == 0)
        up = jnp.where((ii == 0)[None, :], top_t,
                       jnp.roll(d1, 1, axis=1))
        # upleft D[i-1, j-1]    (diag t-2, row i-1; boundaries i==0 / j==0)
        ul = jnp.where((ii == 0)[None, :], topc_t,
                       jnp.where((ii == t)[None, :], left_m1,
                                 jnp.roll(d2, 1, axis=1)))

        d0 = spec.cell_update(cost, lf, up, ul)
        valid = (j >= 0) & (j < C)
        if spec.band is not None:
            # global Sakoe–Chiba mask in tile-local coordinates
            valid = valid & spec.band_valid(i0 + ii, j0 + j)
        d0 = jnp.where(valid[None, :], d0, inf)

        # collect the tile's bottom row / right column as produced
        jb = jnp.clip(t - (Rb - 1), 0, C - 1)
        cur = lax.dynamic_slice(bottom, (0, jb), (B, 1))
        valid_b = (t >= Rb - 1) & (t - (Rb - 1) < C)
        bottom = lax.dynamic_update_slice(
            bottom, jnp.where(valid_b, d0[:, Rb - 1:Rb], cur), (0, jb))
        right = jnp.where((j == C - 1)[None, :], d0, right)
        return (d0, d1, bottom, right), None

    d_init = jnp.full((B, Rb), inf, dt)
    bottom0 = jnp.full((B, C), inf, dt)
    right0 = jnp.full((B, Rb), inf, dt)
    (d0, d1, bottom, right), _ = lax.scan(
        step, (d_init, d_init, bottom0, right0),
        jnp.arange(Rb + C - 1))
    return bottom, right


def _pipeline_local(q: jnp.ndarray, r_local: jnp.ndarray, *,
                    axis_name: str, n_dev: int, row_block: int,
                    spec: DPSpec = DEFAULT_SPEC):
    """Per-device body of the reference-sharded pipeline (inside shard_map)."""
    B, M = q.shape
    C = r_local.shape[0]
    assert M % row_block == 0, (M, row_block)
    nblocks = M // row_block
    nsteps = nblocks + n_dev - 1
    m = lax.axis_index(axis_name)

    q_blocks = q.reshape(B, nblocks, row_block)
    perm = [(i, i + 1) for i in range(n_dev - 1)]

    def step(s, state):
        top, recv_left, recv_corner, last_bottom = state
        b = s - m                                  # my row-block this step
        active = (b >= 0) & (b < nblocks)
        bsafe = jnp.clip(b, 0, nblocks - 1)
        qb = jnp.take(q_blocks, bsafe, axis=1)     # (B, Rb)

        is_first_dev = m == 0
        # device 0 has no left neighbour: left = +inf, corner = 0 for the
        # first block (virtual row -1 == 0) and +inf below it.
        left = jnp.where(is_first_dev, INF, recv_left)
        corner = jnp.where(b == 0, 0.0,
                           jnp.where(is_first_dev, INF, recv_corner))
        top_eff = jnp.where(b == 0, 0.0, top)      # virtual row -1 == 0

        bottom, right = sdtw_block(qb, r_local, top_eff, left, corner,
                                   spec=spec, i0=bsafe * row_block,
                                   j0=m * C)

        top = jnp.where(active, bottom, top)
        last_bottom = jnp.where(b == nblocks - 1, bottom, last_bottom)

        # hand the right boundary to the next chunk (ICI hop); also keep
        # its last element as next step's corner on the receiving side.
        sent = lax.ppermute(right, axis_name, perm)          # (B, Rb)
        new_corner = recv_left[:, -1]                        # D[b*Rb-1, j0-1]
        return (top, sent, new_corner, last_bottom)

    top0 = jnp.zeros((B, C), jnp.float32)
    recv0 = jnp.full((B, row_block), INF, jnp.float32)
    corner0 = jnp.full((B,), INF, jnp.float32)
    lb0 = jnp.full((B, C), INF, jnp.float32)
    _, _, _, last_bottom = lax.fori_loop(
        0, nsteps, step, (top0, recv0, corner0, lb0))

    local_end = jnp.argmin(last_bottom, axis=1)              # (B,)
    local_min = jnp.take_along_axis(last_bottom, local_end[:, None],
                                    axis=1)[:, 0]
    # global chunk offset for end index
    local_end = local_end + m * C
    # tree-reduce the subsequence min across chunks
    all_min = lax.all_gather(local_min, axis_name)           # (n_dev, B)
    all_end = lax.all_gather(local_end, axis_name)
    k = jnp.argmin(all_min, axis=0)
    best = jnp.take_along_axis(all_min, k[None], axis=0)[0]
    end = jnp.take_along_axis(all_end, k[None], axis=0)[0]
    return best, end


def make_sdtw_distributed(mesh: Mesh, *,
                          batch_axes: Sequence[str] = ("data",),
                          ref_axis: str = "model",
                          row_block: int = 64,
                          spec: DPSpec | None = None):
    """Build a jit-able distributed sDTW: queries sharded over
    ``batch_axes`` (DP), reference sharded over ``ref_axis`` (pipeline).

    Returned fn: (queries (B, M), reference (N,)) -> (costs (B,), ends (B,)).
    B must divide by prod(mesh[batch_axes]); N by mesh[ref_axis];
    M by row_block.
    """
    spec = DEFAULT_SPEC if spec is None else spec
    if spec.soft:
        raise ValueError(
            "distributed backend does not support soft-min (the final "
            "pmin tree-reduce is hard-min shaped): use engine")
    n_ref = mesh.shape[ref_axis]
    batch_axes = tuple(batch_axes)

    local = functools.partial(_pipeline_local, axis_name=ref_axis,
                              n_dev=n_ref, row_block=row_block, spec=spec)

    def wrapped(q, r):
        best, end = local(q.astype(jnp.float32), r.astype(jnp.float32))
        return best, end

    fn = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(batch_axes, None), P(ref_axis)),
        out_specs=(P(batch_axes), P(batch_axes)),
        check_vma=False,
    )
    return jax.jit(fn)
