"""Fused soft-DTW backward: checkpointed forward + reverse wavefront
sweeps, tile-level E-matrix reconstruction, and a ``jax.custom_vjp``
that makes the kernel backend differentiable at kernel speed.

The engine's gradient path materializes the (B, M, N) cost tensor and
lets ``jax.grad`` unroll an anti-diagonal scan backwards through it.
This module instead runs the soft-DTW backward the way SoftDTW-CUDA
runs it — as its OWN anti-diagonal recurrence — on the same
carry-channel executor as the forward pass (``kernels/wavefront.py``):

  * **Forward sweep** (``checkpoint=True``): the ordinary soft-min
    wavefront kernel, additionally streaming out each reference
    block's ENTRY boundary strip — the F values at columns
    ``r*W - 1`` — an O(M * N/W) residual instead of O(M * N).
  * **Reverse sweep** (``reverse=True``): the suffix recurrence

        B[i, j] = C[i, j] + smin_gamma(B[i, j+1], B[i+1, j], B[i+1, j+1])

    run as a forward wavefront in FLIPPED coordinates
    (i' = m-1-i, j' = n_pad-1-j) over ``prepare_queries(flip(q))`` x
    ``swizzle_reference_reverse(r)``.  The repo's forward convention is
    NOT symmetric, so the reverse plan mirrors its boundary rules
    rather than re-running the forward rules on flipped operands:

      - forward row 0 has a FREE START (its reduced predecessor is
        replaced by exactly 0, so row-0 cells never chain
        horizontally)  ->  reverse flipped row m-1 drops the
        horizontal operand;
      - forward row m-1 feeds the ``-gamma*logsumexp`` readout at
        every column (every bottom cell can END a path, horizontal
        bottom moves allowed)  ->  reverse flipped row 0 carries a
        0-weight TERMINATION operand in the upleft slot and drops
        up/upleft predecessors.

    Its own bottom-row fold recomputes the total cost (every complete
    path starts at exactly one row-0 cell) — a free parity check.
  * **Tile pass** (plain jnp, under jit): per reference block, the F
    and B tiles are recomputed from their boundary strips with the
    same skewed ``lax.scan`` shape as ``align.soft``, giving

        E[i, j] = d sdtw_gamma / d C[i, j]
                = exp((cost - F[i, j] - B[i, j] + C[i, j]) / gamma)

    one (B, M, W) tile at a time.  Cost gradients fold each tile into
    (B, M) / (N,) accumulators immediately — no O(M * N) buffer ever
    exists on the gradient path.  Out-of-band and PAD_VALUE cells
    vanish numerically (their exponent is ~ -1e30/gamma); rows whose
    band blocks every alignment (cost == +inf) are masked to E == 0
    explicitly, matching the engine's gradient-zeroing ``where``.

:func:`sdtw_soft_fused` is the custom_vjp front door the kernel
backend dispatches soft specs through: the primal is the plain
forward kernel (no checkpoint overhead when nobody differentiates);
under ``jax.grad`` the fwd rule runs the checkpointed pair and the
bwd rule folds tiles into cost gradients.  :func:`soft_alignment_fused`
materializes E itself (the ``outputs=("soft_alignment",)`` /
``expected_alignment`` serving path) from the same two sweeps.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.spec import PAD_VALUE, DPSpec, soft_exp
from repro.kernels import ops
from repro.kernels.wavefront import (LANES, KernelPlan, band_grid_blocks,
                                     wavefront_call)


def _geometry(spec: DPSpec, m: int, n: int, w: int):
    """(block width W, padded length, total blocks, executed blocks)."""
    W = LANES * w
    n_pad = ops.ceil_to(n, W)
    R = n_pad // W
    Gf = band_grid_blocks(m, spec.band, R, w)
    return W, n_pad, R, Gf


# ------------------------------------------------------------- sweeps
@functools.partial(jax.jit, static_argnames=("spec", "segment_width",
                                             "interpret"))
def _checkpoint_sweeps(queries, reference, *, spec: DPSpec,
                       segment_width: int, interpret: bool):
    """Run the checkpointed forward + reverse kernel pair.

    queries (B, m), reference (n,) — already normalized.  Returns
    ``(cost, end, rev_cost, fwd_ckpt, rev_ckpt)`` with the per-query
    vectors still BATCH-PADDED to a SUBLANES multiple (callers slice
    ``[:B]``) and the checkpoints shaped (G', Gf, S, m), S queries of
    the plan's rows per step in each of G' grid steps.
    ``rev_cost`` is the reverse sweep's own total-cost readout — equal
    to ``cost`` up to float error (parity diagnostic)."""
    w = segment_width
    m = queries.shape[1]
    q32 = queries.astype(jnp.float32)
    r32 = reference.astype(jnp.float32)
    rf = ops.swizzle_reference(r32, w)
    R = rf.shape[0]
    fwd = ops.plan_rows(KernelPlan(spec=spec, m=m, segment_width=w,
                                   num_ref_blocks=R, checkpoint=True),
                        queries.shape[0])
    cost, end, fck = wavefront_call(fwd, ops.prepare_queries(q32), rf,
                                    interpret=interpret)
    rev = dataclasses.replace(fwd, reverse=True)
    rcost, _rend, rck = wavefront_call(
        rev, ops.prepare_queries(jnp.flip(q32, axis=1)),
        ops.swizzle_reference_reverse(r32, w), interpret=interpret)
    return (cost.reshape(-1), end.reshape(-1), rcost.reshape(-1),
            fck, rck)


def _unpack_ckpt(ck, batch: int, grid_blocks: int, m: int):
    """(G', Gf, S, m) kernel checkpoints -> (batch, Gf, m) with the
    (grid step, row) packing of the sweep undone."""
    return ck.transpose(0, 2, 1, 3).reshape(-1, grid_blocks, m)[:batch]


# -------------------------------------------------------------- tiles
def _tile(C, left_col, *, spec: DPSpec, j0: int, shift: int,
          reverse: bool):
    """One block's DP tile from its left boundary column.

    C: (B, m, W) local cell costs (flipped both ways for a reverse
    tile); left_col: (B, m) the boundary column at local j = -1 (the
    kernel's checkpoint strip; ``big`` at the first block).  ``j0`` is
    the tile's global column origin in the sweep's own coordinates,
    ``shift`` the reverse band shift (``m - n_pad``; 0 forward).
    Returns the (B, m, W) accumulator tile.

    Same skewed-diagonal ``lax.scan`` shape as
    ``align.soft.sdtw_soft_from_costs``; ``reverse`` swaps in the
    reverse boundary rules of ``KernelPlan.cell``.
    """
    B, m, W = C.shape
    dt = C.dtype
    big = jnp.asarray(spec.big, dt)
    ii = jnp.arange(m)
    T = m + W - 1
    tt = jnp.arange(T)
    gather = jnp.clip(tt[None, :] - ii[:, None], 0, W - 1)     # (m, T)
    Cs = jnp.take_along_axis(C, jnp.broadcast_to(gather[None],
                                                 (B, m, T)), axis=2)
    # the boundary column one row up == the upleft boundary
    left_up = jnp.concatenate(
        [jnp.full((B, 1), big, dt), left_col[:, :-1]], axis=1)
    is_row0 = ii == 0
    is_last = ii == m - 1

    def step(carry, xs):
        d1, d2 = carry
        cost, t = xs                                           # (B, m)
        edge = (t - ii) == 0            # local column 0: read boundary
        left = jnp.where(edge, left_col, d1)
        up = jnp.roll(d1, 1, axis=-1)
        upleft = jnp.where(edge, left_up, jnp.roll(d2, 1, axis=-1))
        if reverse:
            d0 = cost + spec.reduce3(
                jnp.where(is_last, big, left),
                jnp.where(is_row0, big, up),
                jnp.where(is_row0, jnp.zeros_like(upleft), upleft))
        else:
            d0 = spec.cell_update(cost, left, up, upleft,
                                  free_start=is_row0)
        jl = t - ii
        valid = (jl >= 0) & (jl < W)
        in_band = spec.band_valid(ii, j0 + jl + shift)
        if in_band is not None:
            valid = valid & in_band
        return (jnp.where(valid, d0, big), d1), None

    d_init = jnp.full((B, m), big, dt)

    def step_collect(carry, xs):
        new_carry, _ = step(carry, xs)
        return new_carry, new_carry[0]

    _, out = lax.scan(step_collect, (d_init, d_init),
                      (jnp.moveaxis(Cs, 2, 0), tt))
    Ds = jnp.moveaxis(out, 0, 2)                            # (B, m, T)
    unskew = ii[:, None] + jnp.arange(W)[None, :]           # t = i + jl
    return jnp.take_along_axis(Ds, jnp.broadcast_to(unskew[None],
                                                    (B, m, W)), axis=2)


def _e_tile(qn, rp, cost, f_left, b_left_flipped, r, *,
            spec: DPSpec, W: int, n_pad: int, R: int):
    """E and C tiles of original reference block ``r`` (traced: the
    callers scan over blocks, so one tile set is live at a time).

    qn (B, m) queries, rp (n_pad,) padded reference, cost (B,) total
    soft costs, f_left/b_left_flipped (B, m) the forward/reverse
    checkpoint strips bounding this block.  Returns (E, C), both
    (B, m, W), with E := 0 where cost is not finite (blocked band).
    """
    m = qn.shape[1]
    j0 = r * W
    rc = lax.dynamic_slice(rp, (j0,), (W,))
    C = spec.cell_cost(qn[:, :, None], rc[None, None, :]) \
        .astype(jnp.float32)
    F = _tile(C, f_left, spec=spec, j0=j0, shift=0, reverse=False)
    # the B tile is computed in flipped coordinates (original block r
    # == flipped block R-1-r, rows reversed) and flipped back
    Bt = _tile(jnp.flip(C, (1, 2)), b_left_flipped, spec=spec,
               j0=(R - 1 - r) * W, shift=m - n_pad, reverse=True)
    Bo = jnp.flip(Bt, (1, 2))
    # valid cells satisfy F + B - C >= cost (the through-(i,j) partition
    # of the path Gibbs measure), so the exponent is <= 0 up to float
    # error; masked/pad cells sit at ~ -1e30/gamma and underflow to 0
    E = soft_exp((cost[:, None, None] - F - Bo + C) / spec.gamma)
    return jnp.where(jnp.isfinite(cost)[:, None, None], E, 0.0), C


# ---------------------------------------------------------- gradients
@functools.partial(jax.jit, static_argnames=("spec", "segment_width"))
def _fold_grads(queries, reference, cost, fck, rck, ct, *,
                spec: DPSpec, segment_width: int):
    """Fold ct-weighted E tiles into (d cost / d queries,
    d cost / d reference) block by block — a ``lax.scan`` over blocks,
    so peak extra memory is one block's tile set, never O(M * N)."""
    B, m = queries.shape
    n = reference.shape[0]
    W, n_pad, R, Gf = _geometry(spec, m, n, segment_width)
    qn = queries.astype(jnp.float32)
    rp = jnp.pad(reference.astype(jnp.float32), (0, n_pad - n),
                 constant_values=PAD_VALUE)
    ctw = ct.astype(jnp.float32)[:, None, None]

    def block(gq, xs):
        r, f_left, b_left = xs
        E, _ = _e_tile(qn, rp, cost, f_left, b_left, r,
                       spec=spec, W=W, n_pad=n_pad, R=R)
        rc = lax.dynamic_slice(rp, (r * W,), (W,))
        diff = qn[:, :, None] - rc[None, None, :]
        if spec.distance == "sqeuclidean":
            g = (2.0 * ctw) * E * diff            # dC/dq = 2 (q - r)
        elif spec.distance == "abs":
            g = ctw * E * jnp.sign(diff)          # dC/dq = sign(q - r)
        else:                                     # pragma: no cover
            raise ValueError(
                f"fused kernel backward supports sqeuclidean/abs, got "
                f"{spec.distance!r} (the registry should have routed "
                f"this spec elsewhere)")
        return gq + g.sum(axis=2), -g.sum(axis=(0, 1))   # dC/dr = -dC/dq

    gq, gr = lax.scan(block, jnp.zeros((B, m), jnp.float32),
                      _block_xs(fck, rck, B, Gf, m))
    # band-skipped trailing blocks carry no gradient
    gr = jnp.pad(gr.reshape(-1), (0, (R - Gf) * W))[:n]
    return gq.astype(queries.dtype), gr.astype(reference.dtype)


def _block_xs(fck, rck, batch: int, grid_blocks: int, m: int):
    """Per-block scan operands: (block index, forward left strip,
    reverse left strip of the same original block), block-major."""
    fl = _unpack_ckpt(fck, batch, grid_blocks, m)
    bl = _unpack_ckpt(rck, batch, grid_blocks, m)
    return (jnp.arange(grid_blocks), jnp.moveaxis(fl, 1, 0),
            jnp.moveaxis(jnp.flip(bl, axis=1), 1, 0))


# --------------------------------------------------------- custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _sdtw_soft_kernel(queries, reference, spec, segment_width,
                      interpret):
    # primal: the plain forward kernel — no checkpoint overhead when
    # nobody differentiates (jax only invokes the fwd/bwd rules under
    # transposition)
    return ops.sdtw_wavefront(queries, reference,
                              segment_width=segment_width,
                              interpret=interpret, spec=spec)


def _sdtw_soft_fwd(queries, reference, spec, segment_width, interpret):
    B, m = queries.shape
    n = reference.shape[0]
    if ops.band_blocks_all(spec, m, n):
        out = (jnp.full((B,), jnp.inf, jnp.float32),
               jnp.zeros((B,), jnp.int32))
        return out, (queries, reference)
    cost, end, _rcost, fck, rck = _checkpoint_sweeps(
        queries, reference, spec=spec, segment_width=segment_width,
        interpret=interpret)
    cost = cost[:B]
    end = jnp.minimum(end[:B], n - 1)
    return (cost, end), (queries, reference, cost, fck, rck)


def _sdtw_soft_bwd(spec, segment_width, interpret, res, cts):
    ct_cost = cts[0]               # cts[1] is the int end's float0 ct
    queries, reference = res[0], res[1]
    if ops.band_blocks_all(spec, queries.shape[1], reference.shape[0]):
        return jnp.zeros_like(queries), jnp.zeros_like(reference)
    _, _, cost, fck, rck = res
    return _fold_grads(queries, reference, cost, fck, rck, ct_cost,
                       spec=spec, segment_width=segment_width)


_sdtw_soft_kernel.defvjp(_sdtw_soft_fwd, _sdtw_soft_bwd)


def _validate_soft(spec: DPSpec, who: str) -> None:
    if not spec.soft:
        raise ValueError(f"{who} needs a softmin spec "
                         f"(reduction='softmin'), got {spec.describe()}")
    if spec.distance == "cosine":
        raise ValueError("kernel backend does not support cosine "
                         "(see kernels/wavefront.KernelPlan)")


def sdtw_soft_fused(queries, reference, *, spec: DPSpec,
                    segment_width: int = 8,
                    interpret: bool | None = None):
    """Soft-min sDTW (costs, ends) through the Pallas kernel, made
    differentiable by the fused reverse-sweep custom_vjp.

    queries (B, M), reference (N,) — NOT normalized here (normalize
    upstream, like ``ops.sdtw_wavefront``).  Forward-only callers pay
    exactly the plain kernel; ``jax.grad`` routes through the
    checkpointed forward + reverse pair and the tile fold instead of
    differentiating through an O(M*N) engine sweep.
    """
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    _validate_soft(spec, "sdtw_soft_fused")
    return _sdtw_soft_kernel(queries, reference, spec,
                             int(segment_width),
                             ops._resolve_interpret(interpret))


# ------------------------------------------------------ E materialized
@functools.partial(jax.jit, static_argnames=("spec", "segment_width",
                                             "interpret"))
def _soft_align_impl(queries, reference, *, spec: DPSpec,
                     segment_width: int, interpret: bool):
    B, m = queries.shape
    n = reference.shape[0]
    W, n_pad, R, Gf = _geometry(spec, m, n, segment_width)
    cost, end, _rcost, fck, rck = _checkpoint_sweeps(
        queries, reference, spec=spec, segment_width=segment_width,
        interpret=interpret)
    cost = cost[:B]
    end = jnp.minimum(end[:B], n - 1)
    qn = queries.astype(jnp.float32)
    rp = jnp.pad(reference.astype(jnp.float32), (0, n_pad - n),
                 constant_values=PAD_VALUE)

    def block(xs):
        r, f_left, b_left = xs
        return _e_tile(qn, rp, cost, f_left, b_left, r,
                       spec=spec, W=W, n_pad=n_pad, R=R)[0]

    tiles = lax.map(block, _block_xs(fck, rck, B, Gf, m))  # (Gf, B, m, W)
    E = jnp.moveaxis(tiles, 0, 2).reshape(B, m, Gf * W)
    # band-skipped trailing blocks: all out of band
    E = jnp.pad(E, ((0, 0), (0, 0), (0, (R - Gf) * W)))[:, :, :n]
    return cost, end, E


def soft_alignment_fused(queries, reference, *, spec: DPSpec,
                         segment_width: int = 8,
                         interpret: bool | None = None):
    """(costs (B,), ends (B,), E (B, M, N)) from ONE fused
    forward+reverse kernel pair — the expected-alignment serving path.

    E itself is the requested O(M*N) output; everything upstream of it
    (both sweeps, the checkpoints) stays tiled.  Inputs are not
    normalized here.
    """
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    _validate_soft(spec, "soft_alignment_fused")
    B, m = queries.shape
    n = reference.shape[0]
    if ops.band_blocks_all(spec, m, n):
        return (jnp.full((B,), jnp.inf, jnp.float32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B, m, n), jnp.float32))
    return _soft_align_impl(queries, reference, spec=spec,
                            segment_width=int(segment_width),
                            interpret=ops._resolve_interpret(interpret))
