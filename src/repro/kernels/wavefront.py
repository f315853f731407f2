"""Carry-channel wavefront executor — ONE generic Pallas kernel body for
every sDTW wavefront variant.

The paper's kernel (§5.2) is a single hard-min recurrence, but the repo
needs three variants of the same wavefront: distance-only, distance +
start-pointer window lanes, and the soft-min (logsumexp) reduction.
Each variant differs only in WHAT rides the wavefront, never in HOW the
wavefront moves — so this module splits the two concerns:

  * a :class:`CarryChannel` describes one typed value that rides the
    wavefront (dtype, init sentinels, boundary-strip dtype).  The
    executor gives every channel the same mechanical treatment — the
    per-segment left/up/upleft registers, the ``__shfl_up`` lane roll,
    the inter-block VMEM boundary strip — so adding a channel never
    duplicates a carry path;
  * a stream fold turns bottom-row cells into the kernel's outputs
    as they are produced (the paper's folded ``__hmin2``):
    :class:`MinArgminFold` keeps the streaming (min, argmin[, argstart])
    triple, :class:`SoftMinFold` keeps a running
    ``-gamma * logsumexp(-x/gamma)`` accumulator pair next to the hard
    argmin twin (end index + blocked detection);
  * a :class:`KernelPlan` binds a ``DPSpec`` to concrete channels, a
    fold, the grid geometry and the band-skip decision;
    :func:`wavefront_call` assembles the ``fori_loop`` body, the VMEM
    scratch and the ``pallas_call`` outputs from the plan.

DESIGN — mapping channels back to the paper's AMD/HIP mechanisms:

  * wavefront thread  -> VPU **lane** (128 per step); each lane owns a
    contiguous ``segment_width`` (w) slice of the reference, the
    paper's thread-coarsening knob (Fig. 3); pipeline skew puts lane l
    on query row ``i = t - l`` at step t.
  * per-thread double buffer -> each channel's rotating ``prev_row``
    VREG array carried through ``lax.fori_loop`` — one per channel, so
    the int32 start lanes and the f32 cost lanes advance in lockstep.
  * ``__shfl_up``     -> :meth:`CarryChannel.roll_carry`: a +1 lane
    roll of the channel's last-cell vector; one boundary value crosses
    lanes per step per channel, nothing else.
  * inter-wavefront shared-memory strip -> one VMEM scratch column PER
    CHANNEL carried across the (sequential) reference-block grid axis.
    Grid steps are sequential on TPU, so the read pointer (t+1) always
    leads the write pointer (t-(L-1)) by L rows and ONE buffer per
    channel suffices where the paper needed two (concurrent
    wavefronts).
  * ``__hmin2`` streaming min -> the stream folds: bottom-row
    cells fold into per-lane VMEM accumulators as they are produced and
    reduce across lanes once, at the LAST EXECUTED reference block.
    The soft-min fold is the logsumexp analogue: per-lane running
    (max, scaled-sum) pairs merged into one global
    ``-gamma * logsumexp`` at finalize.
  * batch of queries  -> grid axis 0, packed SUBLANES queries to a
    group in the sublane dimension (the paper's block-per-query
    batching).  A step carries ``plan.rows_per_step`` queries: one
    group, or two consecutive groups as one (2 * SUBLANES, LANES) tile
    whenever the batch fills two groups (``ops.plan_rows``), so the
    serial step's fixed latency (scalar window arithmetic, XLU round
    trips, the fold's VMEM load and store) is paid once for 16
    queries.  Each query's arithmetic is the same at either height.
  * lane chains -> ``plan.lanes_per_chain`` (L) < LANES: the lanes
    split into k = LANES / L independent chains, each with
    ``rows_per_step`` queries of its own, and every reference block
    runs as k sub-blocks of m + L - 1 steps in which lane c*L + p is
    on row t - p of chain c's queries against reference lanes u*L + p
    of sub-block u.  A short query then fills and drains the pipeline
    in L - 1 steps a sub-block, not LANES - 1 a block.  Each sub-block
    tiles its L reference lanes across the chains once
    (:func:`_tile_chains`), each chain's lane 0 reads its own strip,
    and the fold keeps one accumulator set per sub-block, so every
    lane folds exactly the cells, in the order, of a one-chain plan and
    the outputs agree with it bit for bit.
  * multivariate series -> ``plan.features`` > 1: each reference block
    holds a (D, w, LANES) tile.  Where ``plan.cost_tile`` (the tile
    fits, ``ops.plan_rows``), the MXU builds each sub-block's cell
    costs before its steps, as ``|q|^2 + |r|^2 - 2 q.r`` over the
    features (:func:`_build_cost_tile`), and a step reads its w costs
    with strided loads.  Otherwise each query row packs one reversed
    copy per feature (:func:`feature_stride` lanes apart) and every
    step sums the D per-feature costs of its w cells on the VPU
    (:func:`_feature_costs`) before the recurrence reads them.

Band-skip: with a Sakoe–Chiba band every cell (i, j) with
``j > (m - 1) + band`` is out of band for EVERY query row, so trailing
reference blocks whose columns all satisfy that are never visited —
:attr:`KernelPlan.grid_blocks` trims the pallas grid itself (fewer grid
steps, not just dead lanes), ~O(N / band) fewer steps for tight bands.
Outputs are bit-for-bit identical to the masked full-grid kernel: a
skipped block's cells are all masked to the big sentinel, which can
never win a fold, and no later block reads its boundary strip.  A
chained plan keeps the block as the unit: the sub-blocks of a kept
block that lie past the band run, masked.

The DP cell recurrence and the subsequence boundary conditions
(``D[-1, j] = 0``, ``D[i, -1] = +inf``) are identical to
``repro.core.ref``; the cell semantics (cost, reduction, band mask,
start-pointer tie-break) all come from ``repro.core.spec.DPSpec`` —
this module owns only the wavefront mechanics.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spec import (KERNEL_BIG, NO_WINDOW, SOFT_BIG, DPSpec,
                             soft_exp, soft_log)

LANES = 128          # TPU VPU lane count (the paper's wavefront width = 64)
SUBLANES = 8         # queries per packed group (sublane packing); a
#                      grid step carries one group or two

CHAIN_LANES = (16, 32, 64, LANES)   # lanes a chain may span: no fewer
#                                    than 16, since each chain more adds
#                                    a sub-block's set-up (its reference
#                                    tile, its carry) to every block

TILE_VMEM_HEADROOM = 16 * 2 ** 20   # VMEM a cost-tile plan asks for
#                                    beyond KernelPlan.vmem_bytes: the
#                                    compiler keeps the tile build's
#                                    matmul results in buffers of its own

_J_MAX = 2 ** 31 - 1   # lexicographic-min column sentinel (int32 max):
#                        any real column index beats it, so it doubles
#                        as "no eligible cell seen yet" in the local
#                        (value, column) fold

# Extra kernel operands the non-sdtw recurrence families ride along the
# ONE pallas_call: 'r'-kind arrays are swizzled like the reference (one
# (w, LANES) tile per grid block), 'q'-kind like the prepared queries
# (one reversed+padded row pack per batch group).
_EXTRA_KIND = {
    "r_prev": "r",   # twed: r[j-1] with the r[-1] = 0 convention
    "bt": "r",       # erp: gap-cost prefix over the reference
    "bl": "q",       # erp: gap-cost prefix over each query
}


def query_pack_len(m: int, features: int = 1) -> int:
    """Lane width of one prepared query row: LANES-1 leading zeros, the
    reversed query, then 2*LANES-1 zeros.  The last per-step window
    starts at m + LANES - 2, so the two aligned lane tiles every window
    is read from (:func:`_lane_window`) stay inside the row.  A row of
    ``features`` > 1 holds one such pack per feature, each
    :func:`feature_stride` lanes apart."""
    if features == 1:
        return m + 3 * LANES - 2
    return features * feature_stride(m)


def feature_stride(m: int, lanes_per_chain: int = LANES) -> int:
    """Lanes between the packs of consecutive features of a
    multivariate query row: one query pack rounded up to whole lane
    tiles, so that every feature's window lies at the same lane offset
    of its tiles.  For chains of L < LANES lanes the pack is the
    chains' packs of L-1 leading zeros, the reversed query and 2L-1
    zeros, interleaved in L-lane pieces (:func:`step_pack_len`)."""
    L = lanes_per_chain
    return -(-(m + 3 * L - 2) // L) * LANES


def step_pack_len(m: int, features: int = 1,
                  lanes_per_chain: int = LANES) -> int:
    """Lane width of one query row as a grid step reads it.  At one
    chain, the prepared row itself (:func:`query_pack_len`), whose
    univariate length (not whole lane tiles) names m.  At k = LANES / L
    chains, lane tile tau holds positions [tau L, (tau + 1) L) of each
    chain's pack, chain c in lanes [c L, (c + 1) L): every window a step
    reads then lies in two aligned tiles, at the same offset for every
    chain."""
    if lanes_per_chain == LANES:
        return query_pack_len(m, features)
    return features * feature_stride(m, lanes_per_chain)


def strip_len(m: int, lanes_per_chain: int = LANES) -> int:
    """Lane width of a boundary strip: m rows of each chain, in tiles
    of L rows of every chain (at one chain, m rows padded to whole
    tiles)."""
    return -(-m // lanes_per_chain) * LANES


def _ceil_to(x: int, k: int) -> int:
    return (x + k - 1) // k * k


def _aligned(col):
    """(tile start, lane offset) of a dynamic lane column: Mosaic only
    slices the lane dimension at provable multiples of LANES."""
    off = lax.rem(col, LANES)
    return pl.multiple_of(col - off, LANES), off


def _window_at(start, lanes_per_chain):
    """Where the L-lane window of every chain starting at position
    ``start`` of its pack lies, packed as :func:`step_pack_len` packs
    it: (the first of its two aligned lane tiles, the offset into it,
    the rotation of the first tile, the rotation of the second).  Lane
    p of a chain reads the first tile, rotated, while p < L - offset,
    and the second after."""
    L = lanes_per_chain
    if L == LANES:      # one rotation amount, and no division for the
        #                 base: three scalar ops fewer a window
        base, off = _aligned(start)
        shift = lax.rem(LANES - off, LANES)
        return base, off, shift, shift
    off = lax.rem(start, L)
    base = pl.multiple_of(lax.div(start, L) * LANES, LANES)
    return base, off, lax.rem(LANES - off, LANES), L - off


def _lane_window(ref, start, p, lanes_per_chain=LANES):
    """``ref[0, :, start:start + L]`` of every chain at a dynamic,
    unaligned start (``p``: each lane's place in its chain): the two
    aligned lane tiles covering it, each rotated into place and
    spliced where the first ends."""
    L = lanes_per_chain
    base, off, lo_shift, hi_shift = _window_at(start, L)
    lo = pltpu.roll(ref[0, :, pl.ds(base, LANES)], lo_shift, 1)
    hi = pltpu.roll(ref[0, :, pl.ds(base + LANES, LANES)], hi_shift, 1)
    return jnp.where(p < L - off, lo, hi)


def _feature_costs(plan, q_ref, r_ref, t, p):
    """The w cell costs of every lane at step ``t`` of a multivariate
    plan, one (rows, LANES) array per segment slot: for each feature,
    its query window (read as :func:`_lane_window` reads one) against
    the slot's reference row, the per-feature terms added in feature
    order as :meth:`DPSpec.feature_cost` adds them.  All features'
    windows share one lane offset (:func:`feature_stride`), so the
    offset is worked out once a step."""
    spec, cdt = plan.spec, plan.compute_dtype
    L = plan.lanes_per_chain
    stride = feature_stride(plan.m, L)
    base, off, lo_shift, hi_shift = _window_at(plan.m - 1 + L - 1 - t, L)
    keep = p < L - off
    costs = [None] * plan.segment_width
    for d in range(plan.features):
        at = pl.multiple_of(base + d * stride, LANES)
        lo = pltpu.roll(q_ref[0, :, pl.ds(at, LANES)], lo_shift, 1)
        hi = pltpu.roll(q_ref[0, :, pl.ds(at + LANES, LANES)], hi_shift, 1)
        qd = jnp.where(keep, lo, hi).astype(cdt)
        for k in range(plan.segment_width):
            term = spec.cell_cost(qd, r_ref[0, d, k:k + 1, :].astype(cdt))
            costs[k] = term if d == 0 else costs[k] + term
    return costs


def _split3(x):
    """float32 ``x`` as three bfloat16-exact float32 parts, hi + mid +
    lo = x to float32's 24 bits: each part the top 16 bits of what the
    ones before it leave.  Six of the nine products of two split
    operands, all but mid.lo, lo.mid and lo.lo, are how the TPU
    multiplies float32 at ``Precision.HIGHEST``."""
    def top(v):
        bits = lax.bitcast_convert_type(v, jnp.uint32)
        return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    hi = top(x)
    mid = top(x - hi)
    return hi, mid, top(x - hi - mid)


# the six products of two float32 operands split by _split3, as the
# TPU multiplies float32 at Precision.HIGHEST: (reference part, query
# part) of each contraction block of a cost-tile plan
_HIGHEST_PRODUCTS = ((0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0))


def _cost_lhs(plan, r_ref, aug_ref, lhs_ref):
    """The reference side of a block's cost tiles, once a block: its
    rows (features, a row of ones, ``|r|^2``) split by :func:`_split3`
    into the ``_HIGHEST_PRODUCTS`` blocks of the contraction, row kappa
    of slot k at ``aug_ref`` row kappa w + k, then transposed into
    ``lhs_ref`` as bfloat16: row (u w + k) L + p holds reference lane
    u L + p, slot k, of sub-block u."""
    D, w, L = plan.features, plan.segment_width, plan.lanes_per_chain
    depth, n_rows = plan.tile_depth, plan.features + 2
    x = r_ref[0].astype(jnp.float32)                     # (D, w, LANES)
    parts = _split3(jnp.concatenate(
        [x, jnp.ones((1, w, LANES), jnp.float32),
         jnp.sum(x * x, axis=0, keepdims=True)], axis=0))
    for b, (ri, _) in enumerate(_HIGHEST_PRODUCTS):
        for d in range(n_rows):
            aug_ref[pl.ds((b * n_rows + d) * w, w), :] = parts[ri][d]
    pad = depth - len(_HIGHEST_PRODUCTS) * n_rows
    if pad:
        aug_ref[pl.ds((depth - pad) * w, pad * w), :] = jnp.zeros(
            (pad * w, LANES), jnp.float32)
    for k in range(w):
        lhs = aug_ref[pl.ds(k, depth, stride=w), :].T.astype(jnp.bfloat16)
        for u in range(plan.chains):
            lhs_ref[pl.ds((u * w + k) * L, L), :] = lhs[u * L:(u + 1) * L]


def _build_cost_tile(plan, q_ref, u, lhs_ref, prev_ref, cost_ref):
    """Sub-block ``u``'s cell costs, built on the MXU before its steps:
    ``cost_ref[k, s * T + t]`` holds, in lane c L + p, the cost of row
    t - p of chain c's query s against reference lane u L + p, slot k
    (T = ``plan.tile_steps``), so step t reads its w costs as w
    sublane-strided loads.

    For each piece of LANES frames and each chain, one bfloat16 matmul
    of the sub-block's rows of ``lhs_ref`` (:func:`_cost_lhs`) against
    the chain's query operand (:func:`_tile_queries`), accumulated in
    float32, gives ``|q|^2 + |r|^2 - 2 q.r`` of every slot, reference
    lanes as rows and frames along lanes.  Rolling lane p's row by p
    skews it, a transpose turns it back, and its first p frames come
    from the previous piece, whose first L frames ``prev_ref`` keeps."""
    w, L, chains = plan.segment_width, plan.lanes_per_chain, plan.chains
    depth = plan.tile_depth
    base = pl.multiple_of(u * w * L, w * L)
    own = lax.broadcasted_iota(jnp.int32, (L, LANES), 0) >= \
        jnp.bitwise_and(lax.broadcasted_iota(jnp.int32, (L, LANES), 1),
                        L - 1)
    for k in range(w):          # frames before a query: any finite cost
        prev_ref[k] = jnp.zeros((L, LANES), jnp.float32)

    def piece(j, carry):
        at = pl.multiple_of(j * LANES, LANES)
        costs_t = [jnp.dot(lhs_ref[pl.ds(base, w * L), :],
                           q_ref[0, pl.ds(c * depth, depth), pl.ds(at, LANES)],
                           preferred_element_type=jnp.float32)
                   for c in range(chains)]              # (w L, LANES) each
        for k in range(w):
            skewed = jnp.concatenate(
                [pltpu.roll(x[k * L:(k + 1) * L], 0, 1, stride=1,
                            stride_axis=0) for x in costs_t], axis=0).T
            cost_ref[k, pl.ds(at, L), :] = jnp.where(own, skewed[:L],
                                                     prev_ref[k])
            if L < LANES:
                cost_ref[k, pl.ds(at + L, LANES - L), :] = skewed[L:]
            prev_ref[k] = skewed[:L]
        return carry

    lax.fori_loop(0, plan.rows_per_step * plan.tile_steps // LANES, piece, 0)


def _tile_chains(x, u, lanes_per_chain):
    """``x`` (..., LANES) with its lanes [u L, (u + 1) L) copied into
    every chain's L lanes: one rotation brings them to lanes [0, L),
    then each doubling rotates the filled lanes up by as many."""
    L, axis = lanes_per_chain, x.ndim - 1
    lane = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    y = pltpu.roll(x, lax.rem(LANES - u * L, LANES), axis)
    filled = L
    while filled < LANES:
        y = jnp.where(lane < filled, y, pltpu.roll(y, filled, axis))
        filled *= 2
    return y


def _one_chain_lanes(acc_ref, c, lanes_per_chain, lane):
    """Chain ``c``'s accumulators as a one-chain plan holds them: lane
    u L + p of the result is lane c L + p of sub-block u's
    accumulator, which saw exactly the cells, in the same order, that
    lane u L + p of a one-chain plan sees."""
    L = lanes_per_chain
    out = None
    for u in range(LANES // L):
        x = acc_ref[u]
        shift = (u - c) * L % LANES
        if shift:
            x = pltpu.roll(x, shift, 1)
        out = x if out is None else jnp.where(
            (lane >= u * L) & (lane < (u + 1) * L), x, out)
    return out


# ------------------------------------------------------------- channels
@dataclasses.dataclass(frozen=True)
class CarryChannel:
    """One typed value riding the wavefront.

    The executor mechanically instantiates, for every channel: the
    rotating ``prev_row`` registers (the paper's per-thread double
    buffer), the lane roll (``__shfl_up``), and a VMEM boundary strip
    of ``strip_dtype`` carried across reference blocks.  Only the cell
    update (what value each DP cell writes into the channel) is
    plan-specific — see :meth:`KernelPlan.cell`.

    ``prev_init`` seeds the rotating registers (read only by junk lanes
    whose row index is out of [0, m): any finite value works; 0 keeps
    the pre-refactor f32 graph).  ``edge_init`` is the "no value
    crossed the boundary" sentinel: lane 0's left column at block 0,
    and strip reads beyond the query length.
    """

    name: str
    prev_init: float | int
    edge_init: float | int
    strip_dtype_name: str = "float32"
    use_compute_dtype: bool = True   # registers in the plan's compute
    #                                  dtype (False: the strip dtype)

    @property
    def strip_dtype(self):
        return jnp.dtype(self.strip_dtype_name)

    def reg_dtype(self, compute_dtype):
        return jnp.dtype(compute_dtype) if self.use_compute_dtype \
            else self.strip_dtype

    # ------------------------------------------------------------ hooks
    def init_carry(self, strip_ref, *, p, entered, w, compute_dtype,
                   lanes_per_chain=LANES):
        """(prev_row registers, left column, prev-left) at t = 0."""
        dt = self.reg_dtype(compute_dtype)
        edge = jnp.asarray(self.edge_init, dt)
        prev0 = tuple(jnp.full(p.shape, self.prev_init, dt)
                      for _ in range(w))
        # t=0: only each chain's lane 0 is active (row 0); its left
        # column is the previous sub-block's strip (``entered``: one
        # ran before in this batch step) or the edge sentinel
        strip0 = self.read_strip(strip_ref, 0, compute_dtype=compute_dtype,
                                 lanes_per_chain=lanes_per_chain)
        left0 = jnp.where(p == 0,
                          jnp.where(entered, strip0, edge), edge)
        prev_left0 = jnp.full(p.shape, self.edge_init, dt)
        return (prev0, left0, prev_left0)

    def roll_carry(self, last, *, p, strip_val, use_strip,
                   compute_dtype):
        """``__shfl_up`` analogue: the neighbour lane's last cell
        becomes my left value; each chain's lane 0 reads the previous
        sub-block's boundary strip (or the edge sentinel past the
        query) in place of the lane below it, which is another
        chain's."""
        dt = self.reg_dtype(compute_dtype)
        rolled = pltpu.roll(last, 1, 1)
        lane0 = jnp.where(use_strip, strip_val,
                          jnp.asarray(self.edge_init, dt))
        return jnp.where(p == 0, lane0, rolled)

    def read_strip(self, strip_ref, t, *, compute_dtype,
                   lanes_per_chain=LANES):
        """Strip row ``t`` of every chain's queries, in each chain's
        lane 0 (the only lane that reads it): the aligned lane tile
        holding row t of every chain, rotated so that each chain's
        row lands in its lane 0."""
        base, _, shift, _ = _window_at(t, lanes_per_chain)
        tile = strip_ref[:, pl.ds(base, LANES)]
        return pltpu.roll(tile, shift, 1) \
            .astype(self.reg_dtype(compute_dtype))

    def write_strip(self, strip_ref, i, last, *, p, lanes_per_chain=LANES):
        """Publish the channel's right column (each chain's lane L-1)
        as strip row ``i`` for the next sub-block: a read-modify-write
        of the aligned lane tile holding row i of every chain."""
        L = lanes_per_chain
        base, off, _, _ = _window_at(i, L)
        # lane c L + L - 1 -> lane c L + off
        col = pltpu.roll(last, lax.rem(off + (1 + LANES - L), LANES), 1)
        tile = strip_ref[:, pl.ds(base, LANES)]
        strip_ref[:, pl.ds(base, LANES)] = jnp.where(
            p == off, col.astype(self.strip_dtype), tile)

    def strip_shape(self, m: int, rows: int, lanes_per_chain: int = LANES):
        return pltpu.VMEM((rows, strip_len(m, lanes_per_chain)),
                          self.strip_dtype)


# ---------------------------------------------------------------- folds
#
# A fold keeps per-lane accumulators in VMEM (``accumulators()`` gives
# their dtypes; the executor allocates them), folds each step's cells
# into them (``update``, on the refs) and, once a batch step's last
# reference block has run, reduces them across lanes (``finalize``, on
# their (S, LANES) values) to one (S, 1) column per output.
def _fill(ref, value):
    ref[...] = jnp.full(ref.shape, value, ref.dtype)


@dataclasses.dataclass(frozen=True)
class MinArgminFold:
    """Streaming (min, argmin[, argstart]) over bottom-row cells — the
    paper's folded ``__hmin2``, plus the int32 argmin/argstart twins."""

    with_window: bool = False

    def accumulators(self):
        return (jnp.float32, jnp.int32) + (jnp.int32,) * self.with_window

    def init(self, scr):
        _fill(scr[0], KERNEL_BIG)
        _fill(scr[1], NO_WINDOW)
        if self.with_window:
            _fill(scr[2], NO_WINDOW)

    def _segment_best(self, rows, j_base, w):
        """(value, global column[, start]) of the best cell in each
        lane's w-wide segment, with the shared strict-< tie-break
        (earliest column wins)."""
        best_v, best_k = rows["cost"][0], jnp.zeros_like(j_base)
        best_s = rows["start"][0] if self.with_window else None
        for k in range(1, w):
            val = rows["cost"][k]
            take = val < best_v
            best_v = jnp.where(take, val, best_v)
            best_k = jnp.where(take, k, best_k)
            if self.with_window:
                best_s = jnp.where(take, rows["start"][k], best_s)
        return best_v, j_base + best_k, best_s

    def update(self, scr, *, at_bottom, rows, j_base, plan, in_grid=None):
        best_v, best_j, best_s = self._segment_best(
            rows, j_base, plan.segment_width)
        cand = best_v.astype(jnp.float32)
        take = at_bottom & (cand < scr[0][...])
        scr[0][...] = jnp.where(take, cand, scr[0][...])
        scr[1][...] = jnp.where(take, best_j, scr[1][...])
        if self.with_window:
            scr[2][...] = jnp.where(take, best_s, scr[2][...])

    def _cross_lane(self, accs):
        """(min, its earliest column, and the lane-hit mask) per query,
        each (S, 1): a where/min reduce across lanes — every column
        belongs to exactly one lane, so the hit lane is unique."""
        mv, cols = accs[0], accs[1]                       # (S, L)
        best = jnp.min(mv, axis=1, keepdims=True)
        end = jnp.min(jnp.where(mv == best, cols, _J_MAX), axis=1,
                      keepdims=True)
        return best, end, (mv == best) & (cols == end)

    def finalize(self, accs, plan):
        best, end, hit = self._cross_lane(accs)
        if not self.with_window:
            return [best, end]
        return [best, end, jnp.min(jnp.where(hit, accs[2], _J_MAX),
                                   axis=1, keepdims=True)]


@dataclasses.dataclass(frozen=True)
class SoftMinFold:
    """Streaming soft-min over bottom-row cells.

    Per lane, a running-max logsumexp pair ``(m, s)`` accumulates
    ``x = -D[M-1, j] / gamma`` over the w bottom cells the lane
    produces per reference block (the soft analogue of the folded
    ``__hmin2``); finalize merges the per-lane pairs into one global
    ``-gamma * logsumexp(-x/gamma)``.  A hard (min, argmin) twin rides
    along for the end index (the engine's bottom-row hard argmin, which
    converges to the hard end as gamma -> 0) and for blocked-band
    detection (all bottom cells masked -> +inf, engine parity).
    """

    def accumulators(self):
        # hard twin (min, argmin), then the running max m and the
        # scaled sum s
        return MinArgminFold().accumulators() + (jnp.float32, jnp.float32)

    def init(self, scr):
        MinArgminFold().init(scr[:2])
        _fill(scr[2], -SOFT_BIG)
        _fill(scr[3], 0.0)

    def update(self, scr, *, at_bottom, rows, j_base, plan, in_grid=None):
        MinArgminFold().update(scr[:2], at_bottom=at_bottom, rows=rows,
                               j_base=j_base, plan=plan)
        gamma = plan.spec.gamma
        xs = [-(rows["cost"][k].astype(jnp.float32)) / gamma
              for k in range(plan.segment_width)]
        mx = xs[0]
        for x in xs[1:]:
            mx = jnp.maximum(mx, x)
        m_run, s_run = scr[2][...], scr[3][...]
        # m_safe >= every exponent, so no exp below can overflow; the
        # at_bottom gate means each lane folds its w bottom cells
        # exactly once per reference block
        m_safe = jnp.maximum(m_run, mx)
        add = xs[0] * 0.0
        for x in xs:
            add = add + soft_exp(x - m_safe)
        s_new = s_run * soft_exp(m_run - m_safe) + add
        scr[2][...] = jnp.where(at_bottom, m_safe, m_run)
        scr[3][...] = jnp.where(at_bottom, s_new, s_run)

    def finalize(self, accs, plan):
        best, idx, _ = MinArgminFold()._cross_lane(accs[:2])
        m_l, s_l = accs[2], accs[3]                       # (S, L)
        m_g = jnp.max(m_l, axis=1, keepdims=True)         # (S, 1)
        s_g = jnp.sum(s_l * soft_exp(m_l - m_g), axis=1, keepdims=True)
        cost = -plan.spec.gamma * (m_g + soft_log(s_g))
        # blocked band: every bottom cell was masked to ~SOFT_BIG — the
        # logsumexp is a finite ~SOFT_BIG value; report +inf like the
        # engine and the numpy oracle.  (Pad-dominated paths stay
        # finite ~1e12 << SOFT_BIG/2: the kernel's long-standing
        # blocked-band-with-reachable-padding semantics, see ops.py.)
        blocked = best >= jnp.asarray(SOFT_BIG / 2, jnp.float32)
        return [jnp.where(blocked, jnp.asarray(jnp.inf, jnp.float32), cost),
                idx]


@dataclasses.dataclass(frozen=True)
class CornerFold:
    """Global-corner fold for the twed/erp families: the answer is the
    single cell ``(m-1, n-1)``, captured as the wavefront produces it.

    Works for hard and soft reductions alike — the corner VALUE already
    carries the reduction; the fold only has to find the one (lane,
    segment-slot, step) triple that computes it.  A corner still holding
    ~``plan.big`` at finalize means the band disconnected the global
    path (every operand masked): report ``(+inf, end 0)``, engine
    parity.  Pad columns (j >= n) can never pollute the corner — the DP
    flows strictly left-to-right, so cell (m-1, n-1) never reads them.
    """

    def accumulators(self):
        return (jnp.float32,)

    def init(self, scr):
        _fill(scr[0], KERNEL_BIG)

    def update(self, scr, *, at_bottom, rows, j_base, plan, in_grid=None):
        acc = scr[0][...]
        for k in range(plan.segment_width):
            hit = at_bottom & (j_base + k == plan.n - 1)
            acc = jnp.where(hit, rows["cost"][k].astype(jnp.float32), acc)
        scr[0][...] = acc

    def finalize(self, accs, plan):
        # exactly one lane ever wrote the corner; min() selects it
        corner = jnp.min(accs[0], axis=1, keepdims=True)  # (S, 1)
        blocked = corner >= jnp.asarray(plan.big / 2, jnp.float32)
        return [jnp.where(blocked, jnp.asarray(jnp.inf, jnp.float32),
                          corner),
                jnp.where(blocked, jnp.asarray(0, jnp.int32),
                          jnp.asarray(plan.n - 1, jnp.int32))]


@dataclasses.dataclass(frozen=True)
class LocalCellsFold:
    """Every-valid-cell lexicographic ``(value, column)`` minimum — the
    local-alignment family's free-end fold.

    Unlike the bottom-row folds, EVERY in-grid cell with a real column
    (``j < n``) is a candidate end.  Per lane a streaming lex pair
    (best value, best column) accumulates; finalize takes the cross-
    lane min value and then the smallest column among the lanes
    achieving it — lane order is NOT column order on a wavefront, so an
    argmin-by-lane would break engine tie parity.  Cells still holding
    ~``plan.big`` (band-masked) never take, mirroring the engine's
    ``v < big/2`` guard.
    """

    def accumulators(self):
        return (jnp.float32, jnp.int32)                   # lex value, column

    def init(self, scr):
        _fill(scr[0], KERNEL_BIG)
        _fill(scr[1], _J_MAX)

    def update(self, scr, *, at_bottom, rows, j_base, plan, in_grid=None):
        big_half = jnp.asarray(plan.big / 2, jnp.float32)
        bv, bj = scr[0][...], scr[1][...]
        for k in range(plan.segment_width):
            j = j_base + k
            cand = rows["cost"][k].astype(jnp.float32)
            elig = in_grid & (j < plan.n) & (cand < big_half)
            take = elig & ((cand < bv) | ((cand == bv) & (j < bj)))
            bv = jnp.where(take, cand, bv)
            bj = jnp.where(take, j, bj)
        scr[0][...] = bv
        scr[1][...] = bj

    def _cross_lane(self, accs):
        mv = accs[0]                                          # (S, L)
        best = jnp.min(mv, axis=1, keepdims=True)             # (S, 1)
        js = jnp.where(mv == best, accs[1], _J_MAX)
        return best, jnp.min(js, axis=1, keepdims=True)

    def finalize(self, accs, plan):
        return list(self._cross_lane(accs))


@dataclasses.dataclass(frozen=True)
class SoftCellsFold:
    """Soft local-alignment fold: a running logsumexp over EVERY valid
    cell, next to the hard lex twin (end index, gamma -> 0 limit).

    Eligibility must exclude pad columns explicitly: a PAD_VALUE
    column's local cell floors to exactly 0 (``min(~1e12, 0)``), which
    would weigh ``exp(0/gamma) = 1`` in the logsumexp — unlike the
    bottom-row folds, padding is NOT self-masking here.  Ineligible
    cells contribute ``exp(-inf) = 0`` exactly; the running max starts
    at the FINITE ``-SOFT_BIG`` so ``-inf - m_run`` stays ``-inf``
    (never the ``-inf - -inf = nan`` trap).  Band-masked in-band cells
    carry ~``SOFT_BIG`` and underflow to weight 0, exactly like the
    engine's masked diagonals.
    """

    def accumulators(self):
        # lex twin, then the running max m and the scaled sum s
        return LocalCellsFold().accumulators() + (jnp.float32, jnp.float32)

    def init(self, scr):
        LocalCellsFold().init(scr[:2])
        _fill(scr[2], -SOFT_BIG)
        _fill(scr[3], 0.0)

    def update(self, scr, *, at_bottom, rows, j_base, plan, in_grid=None):
        LocalCellsFold().update(scr[:2], at_bottom=at_bottom, rows=rows,
                                j_base=j_base, plan=plan, in_grid=in_grid)
        gamma = plan.spec.gamma
        neg_inf = jnp.asarray(-jnp.inf, jnp.float32)
        xs = []
        for k in range(plan.segment_width):
            elig = in_grid & (j_base + k < plan.n)
            xs.append(jnp.where(
                elig, -(rows["cost"][k].astype(jnp.float32)) / gamma,
                neg_inf))
        mx = xs[0]
        for x in xs[1:]:
            mx = jnp.maximum(mx, x)
        m_run, s_run = scr[2][...], scr[3][...]
        m_safe = jnp.maximum(m_run, mx)
        add = jnp.zeros_like(m_safe)
        for x in xs:
            add = add + soft_exp(x - m_safe)
        scr[2][...] = m_safe
        scr[3][...] = s_run * soft_exp(m_run - m_safe) + add

    def finalize(self, accs, plan):
        _, end = LocalCellsFold()._cross_lane(accs[:2])
        m_l, s_l = accs[2], accs[3]                           # (S, L)
        m_g = jnp.max(m_l, axis=1, keepdims=True)             # (S, 1)
        s_g = jnp.sum(s_l * soft_exp(m_l - m_g), axis=1, keepdims=True)
        return [-plan.spec.gamma * (m_g + soft_log(s_g)), end]


# ----------------------------------------------------------------- plan
def band_grid_blocks(m: int, band: int | None, num_ref_blocks: int,
                     segment_width: int) -> int:
    """Reference blocks a banded wavefront must actually visit: block b
    owns columns [b*LANES*w, (b+1)*LANES*w), and every cell with
    ``j > (m-1) + band`` is out of band for every query row."""
    if band is None:
        return num_ref_blocks
    block_cols = LANES * segment_width
    return max(1, min(num_ref_blocks,
                      (m - 1 + band) // block_cols + 1))


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """A ``DPSpec`` bound to concrete wavefront machinery: channels,
    fold, grid geometry and the band-skip decision.  Frozen and
    hashable — safe as a jit static argument."""

    spec: DPSpec
    m: int                       # query length
    segment_width: int           # reference cells per lane (paper's w)
    num_ref_blocks: int          # total blocks in the swizzled layout
    compute_dtype_name: str = "float32"
    with_window: bool = False    # int32 start-pointer channel + output
    band_skip: bool = True       # trim the grid for Sakoe–Chiba specs
    reverse: bool = False        # soft-DTW reverse sweep (B matrix):
    #                              flipped operands, reversed boundary
    #                              rules (see kernels/backward.py)
    checkpoint: bool = False     # emit each block's entry boundary
    #                              strip as an extra output (the fused
    #                              backward's O(M * N/W) residual)
    n: int | None = None         # TRUE reference length (pre-padding);
    #                              required by the non-sdtw families,
    #                              whose folds are defined by it (the
    #                              global corner j == n-1, the local
    #                              valid-cell set j < n).  sdtw plans
    #                              leave it None so their jit cache
    #                              stays keyed on padded shapes alone.
    features: int = 1            # D of multivariate (B, M, D) inputs:
    #                              each cell's cost adds D per-feature
    #                              terms, computed inside the kernel
    rows_per_step: int = SUBLANES  # queries a serial step carries: one
    #                              packed group, or two whenever the
    #                              batch fills two (ops.plan_rows).
    #                              Every plan kind gains from two: its
    #                              loop body at 16 rows is under twice
    #                              its body at 8 (PERF.md section 5)
    lanes_per_chain: int = LANES  # L: lanes one dependency chain spans.
    #                              k = LANES / L chains a step, each
    #                              with rows_per_step queries of its
    #                              own; a reference block runs as k
    #                              sub-blocks of m + L - 1 steps, so a
    #                              short query fills L - 1 steps, not
    #                              LANES - 1 (ops.plan_rows)
    cost_tile: bool = False      # a multivariate plan's cell costs
    #                              come from a per-sub-block tile the
    #                              MXU builds (_build_cost_tile), not
    #                              from the VPU inside each step; set
    #                              by ops.plan_rows where the tile fits

    def __post_init__(self):
        if self.features < 1:
            raise ValueError(f"features must be >= 1, got {self.features}")
        if self.lanes_per_chain not in CHAIN_LANES:
            raise ValueError(
                f"lanes_per_chain is one of {CHAIN_LANES}, got "
                f"{self.lanes_per_chain}")
        if self.chains > 1 and (self.reverse or self.checkpoint):
            raise ValueError(
                "reverse and checkpoint sweeps run one chain: the fused "
                "soft backward's residual is one strip per reference "
                f"block (got lanes_per_chain={self.lanes_per_chain})")
        if self.features > 1 and (self.spec.family != "sdtw"
                                  or self.reverse or self.checkpoint):
            raise ValueError(
                "multivariate plans run the sdtw forward sweep only: "
                f"got family {self.spec.family!r}, reverse={self.reverse}, "
                f"checkpoint={self.checkpoint}")
        if self.cost_tile and (self.features == 1
                               or self.spec.distance != "sqeuclidean"):
            raise ValueError(
                "the cost tile expands the squared Euclidean cost of "
                "multivariate plans; got features="
                f"{self.features}, distance {self.spec.distance!r}")
        if self.rows_per_step not in (SUBLANES, 2 * SUBLANES):
            raise ValueError(
                f"rows_per_step is {SUBLANES} or {2 * SUBLANES} (one or "
                f"two query groups a step), got {self.rows_per_step}")
        if self.spec.family != "sdtw":
            if self.n is None:
                raise ValueError(
                    f"a {self.spec.family!r}-family plan needs the true "
                    "reference length: its fold is defined by n (the "
                    "global corner / the valid-cell set) — pass n= to "
                    "build_plan")
            if self.with_window:
                raise ValueError(
                    f"family {self.spec.family!r} has no matched-window "
                    "start pointers on the kernel backend (window "
                    "outputs ride the sdtw free-start recurrence); use "
                    "engine or ref for family window outputs")
            if self.reverse or self.checkpoint:
                raise ValueError(
                    "reverse/checkpoint sweeps implement the soft-DTW "
                    f"backward; family {self.spec.family!r} plans do "
                    "not support them")
            if self.compute_dtype_name != "float32":
                raise ValueError(
                    f"family {self.spec.family!r} runs the kernel in "
                    "float32 (transition costs and boundary prefixes "
                    "must match the engine grid bit-for-bit); got "
                    f"compute_dtype={self.compute_dtype_name}")
        if self.spec.distance == "cosine":
            raise ValueError(
                "kernel backend does not support cosine (PAD_VALUE "
                "padding columns would not lose the argmin): use "
                "engine or ref")
        if self.spec.soft and self.with_window:
            raise ValueError(
                "with_window needs a hard-min spec: soft-min has no "
                "argmin path (use repro.align.soft)")
        if self.spec.soft and self.compute_dtype_name != "float32":
            raise ValueError(
                "the soft-min channel accumulates logsumexp pairs in "
                f"float32; got compute_dtype={self.compute_dtype_name}")
        if self.reverse and not self.spec.soft:
            raise ValueError(
                "reverse sweeps exist for the soft-DTW backward (the "
                "B matrix of the E-matrix identity); hard-min plans "
                "have no reverse mode")
        if self.checkpoint and self.with_window:
            raise ValueError(
                "checkpoint plans carry only the cost channel's "
                "boundary strips; with_window is not supported")

    # -------------------------------------------------------- geometry
    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute_dtype_name)

    @property
    def chains(self) -> int:
        """Independent lane chains a serial step carries."""
        return LANES // self.lanes_per_chain

    @property
    def queries_per_step(self) -> int:
        """Queries one grid step carries: rows x chains."""
        return self.rows_per_step * self.chains

    @property
    def tile_steps(self) -> int:
        """T: the cost tile's steps a query, m + L - 1 rounded up so
        that a query operand row of ``rows_per_step`` x T frames fills
        whole lane tiles."""
        return _ceil_to(self.m + self.lanes_per_chain - 1,
                        LANES // self.rows_per_step)

    @property
    def tile_depth(self) -> int:
        """Rows of one chain's contraction in the cost tile's matmul:
        the ``_HIGHEST_PRODUCTS`` blocks of the features, a row for
        ``|q|^2`` and one for ``|r|^2``, rounded up to whole bfloat16
        sublane tiles."""
        return _ceil_to(len(_HIGHEST_PRODUCTS) * (self.features + 2),
                        2 * SUBLANES)

    @property
    def big(self) -> float:
        """The masked-cell / edge sentinel.  Hard-min uses KERNEL_BIG
        (bf16-survivable); soft-min uses SOFT_BIG so ``-big / gamma``
        stays finite in f32 inside the logsumexp (see core.spec)."""
        return SOFT_BIG if self.spec.soft else KERNEL_BIG

    @property
    def family(self) -> str:
        return self.spec.family

    @property
    def extra_inputs(self) -> tuple[str, ...]:
        """Names of the family's extra kernel operands, in pallas_call
        order (kinds in ``_EXTRA_KIND``): twed rides the shifted
        reference, erp its two gap-cost prefixes; sdtw and local need
        none."""
        if self.family == "twed":
            return ("r_prev",)
        if self.family == "erp":
            return ("bt", "bl")
        return ()

    @property
    def channels(self) -> tuple[CarryChannel, ...]:
        cost = CarryChannel(name="cost", prev_init=0.0,
                            edge_init=self.big,
                            strip_dtype_name="float32",
                            use_compute_dtype=True)
        if not self.with_window:
            return (cost,)
        start = CarryChannel(name="start", prev_init=NO_WINDOW,
                             edge_init=NO_WINDOW,
                             strip_dtype_name="int32",
                             use_compute_dtype=False)
        return (cost, start)

    @property
    def fold(self):
        fold_kind = self.spec.recurrence.fold
        if fold_kind == "corner":
            return CornerFold()
        if fold_kind == "cells":
            return SoftCellsFold() if self.spec.soft else LocalCellsFold()
        if self.spec.soft:
            return SoftMinFold()
        return MinArgminFold(with_window=self.with_window)

    @property
    def num_outputs(self) -> int:
        n = 3 if self.with_window else 2
        return n + 1 if self.checkpoint else n

    @property
    def grid_blocks(self) -> int:
        """Grid steps actually executed along the reference axis.

        Identical for forward and reverse sweeps: a band keeps
        ``band_grid_blocks`` blocks alive in both directions (forward
        trims TRAILING blocks, reverse — whose flipped column j' maps
        to original column n_pad-1-j' — skips the same count of
        LEADING flipped blocks via :attr:`block_offset`)."""
        if not self.band_skip:
            return self.num_ref_blocks
        return band_grid_blocks(self.m, self.spec.band,
                                self.num_ref_blocks, self.segment_width)

    @property
    def skipped_blocks(self) -> int:
        return self.num_ref_blocks - self.grid_blocks

    @property
    def block_offset(self) -> int:
        """First reference-layout block the grid actually executes.

        Forward band-skip drops trailing blocks (offset 0); a reverse
        sweep's dead columns — original ``j > (m-1) + band`` — sit at
        the LEADING flipped columns ``j' < n_pad - m - band``, so the
        reverse grid starts ``skipped_blocks`` blocks in.  Grid step r
        reads layout block ``r + block_offset``."""
        return self.skipped_blocks if self.reverse else 0

    @property
    def band_shift(self) -> int:
        """Column shift applied inside the band mask: a reverse sweep
        computes in flipped coordinates (i' = m-1-i, j' = n_pad-1-j),
        where ``i - j = (m - n_pad) - (i' - j')`` — so
        ``|i' - j' + band_shift| <= band`` tests the ORIGINAL band."""
        if not self.reverse:
            return 0
        return self.m - self.num_ref_blocks * LANES * self.segment_width

    def geometry(self) -> dict:
        """The plan's work shape as plain numbers — what a tuning trial
        or a bench row records next to its wall-clock: how many grid
        steps run, how wide each block is, and how much of the padded
        reference is PAD_VALUE overhead (padding rises with
        ``segment_width``, which is exactly the trade the paper's
        Fig. 3 sweep measures)."""
        block_cols = LANES * self.segment_width
        return {
            "segment_width": self.segment_width,
            "block_cols": block_cols,
            "num_ref_blocks": self.num_ref_blocks,
            "grid_blocks": self.grid_blocks,
            "skipped_blocks": self.skipped_blocks,
            "block_offset": self.block_offset,
            "padded_cols": self.num_ref_blocks * block_cols,
        }

    def work(self, batch: int, n: int) -> dict:
        """What one dispatch of this plan does for ``batch`` queries
        against a reference of true length ``n``, as plain integers:

          * ``rows_per_step``: the plan's queries per serial step in
            each chain, and ``lanes_per_chain`` (L): the lanes one
            chain spans, LANES / L chains a step;
          * ``grid_steps``: steps along the batch (``rows_per_step`` x
            chains queries each, the last one's rest padded) x
            executed reference blocks;
          * ``loop_steps``: serial wavefront steps, chains x
            ``m + L - 1`` per grid step (the pipeline fills and drains
            every sub-block of L lanes' columns);
          * ``lane_cells``: cells those steps update, ``rows_per_step``
            x LANES x ``segment_width`` each, padding, pad queries and
            pipeline fill included;
          * ``cells_real``: the real query x real column cells inside
            the executed blocks, never more than ``lane_cells``;
          * ``feature_cells``: ``cells_real`` x the plan's features,
            the per-feature cost terms the real cells add.
          * ``cost_tile``: 1 where the plan's cell costs come from the
            MXU's cost tile, else 0.

        Forward and reverse sweeps execute the same number of blocks
        and hold the same real columns, so both read the same work."""
        block_cols = LANES * self.segment_width
        if not 0 < n <= self.num_ref_blocks * block_cols:
            raise ValueError(
                f"reference length n={n} does not fit the plan's "
                f"{self.num_ref_blocks} blocks of {block_cols} columns")
        rows, per = self.rows_per_step, self.queries_per_step
        grid_steps = _ceil_to(batch, per) // per * self.grid_blocks
        loop_steps = grid_steps * self.chains * (
            self.m + self.lanes_per_chain - 1)
        cells_real = batch * self.m * min(n, self.grid_blocks * block_cols)
        return {
            "rows_per_step": rows,
            "lanes_per_chain": self.lanes_per_chain,
            "grid_steps": grid_steps,
            "loop_steps": loop_steps,
            "lane_cells": loop_steps * rows * block_cols,
            "cells_real": cells_real,
            "feature_cells": cells_real * self.features,
            "cost_tile": int(self.cost_tile),
        }

    def vmem_bytes(self) -> int:
        """VMEM one grid step of this plan holds, counted at 4 bytes an
        element: the query-laid operand blocks (:func:`step_pack_len`
        lanes a row) and the reference-laid ones, each double-buffered,
        the output blocks likewise, then the scratch: one boundary strip
        per channel, the fold's accumulators for every chain and, in a
        chained plan, each sub-block's reference-laid tiles.  A chained
        plan's query block and strip are about ``chains`` times the
        one-chain plan's.  A cost-tile plan's query block is the
        matmul's operand (:func:`_tile_queries`), and its scratch holds
        the sub-block's augmented reference and the w x rows x T cost
        tile in place of the reference tile."""
        rows, L, D = self.rows_per_step, self.lanes_per_chain, self.features
        w = self.segment_width
        kinds = [_EXTRA_KIND[name] for name in self.extra_inputs]
        q_laid = (1 + kinds.count("q")) * rows * step_pack_len(self.m, D, L)
        r_laid = (D + kinds.count("r")) * w * LANES
        out = (self.num_outputs - self.checkpoint) * rows * LANES \
            + self.checkpoint * rows * strip_len(self.m)
        scratch = len(self.channels) * rows * strip_len(self.m, L) \
            + len(self.fold.accumulators()) * self.chains * rows * LANES
        if not self.cost_tile:
            scratch += (self.chains > 1) * r_laid
            return 4 * (2 * (q_laid + r_laid + out) + scratch)
        # bfloat16 query operand and reference side, float32 the rest
        q_laid = self.chains * self.tile_depth * rows * self.tile_steps
        scratch += self.tile_depth * w * LANES \
            + w * L * LANES + w * rows * self.tile_steps * LANES
        return 4 * (2 * (r_laid + out) + scratch) \
            + 2 * (2 * q_laid + w * LANES * self.tile_depth)

    def vmem_limit_bytes(self) -> int | None:
        """VMEM the ``pallas_call`` asks the compiler for: a cost-tile
        plan's :meth:`vmem_bytes` and ``TILE_VMEM_HEADROOM`` for the
        compiler's own buffers (the tile build's matmul results); None,
        the compiler's default, for every other plan."""
        if not self.cost_tile:
            return None
        return self.vmem_bytes() + TILE_VMEM_HEADROOM

    # ------------------------------------------------------------ cell
    def cell(self, qv, rv, *, is_row0, i_l, j_col, vals3, extras=None,
             cost=None):
        """One DP cell across every channel.

        ``vals3`` maps channel name -> (left, up, upleft) carries; the
        return maps channel name -> the cell's new value.  Semantics
        come entirely from the spec: ``cell_cost`` + ``cell_update``
        (with the free-start row-0 boundary) for the cost channel,
        ``start3`` (the shared strict-< tie-break) for the start
        channel, ``band_valid`` masking both.

        Non-sdtw families route through the ONE shared
        :meth:`DPSpec.family_cell` definition instead (the same f32
        graph the rowscan ref and the anti-diagonal engine run), fed
        from ``extras``: per-cell values of the family's extra operands
        (``q_prev``/``r_prev`` for twed, ``bt``/``bl`` prefixes for
        erp).  The boundary injection lives inside ``family_cell``, so
        the carries' edge sentinels are simply overridden at row/col 0.

        ``cost`` is the cell's local cost where the caller has it
        already (multivariate plans, :func:`_feature_costs`); then
        ``qv`` and ``rv`` go unused.
        """
        spec = self.spec
        big = jnp.asarray(self.big, self.compute_dtype)
        left, up, upleft = vals3["cost"]
        if spec.family != "sdtw":
            ex = extras or {}
            val = spec.family_cell(
                qv, rv, left, up, upleft, i=i_l, j=j_col,
                is_row0=is_row0, is_col0=(j_col == 0),
                q_prev=ex.get("q_prev"), r_prev=ex.get("r_prev"),
                top_boundary=ex.get("bt"), left_boundary=ex.get("bl"),
                big=big)
            in_band = spec.band_valid(i_l, j_col)
            if in_band is not None:
                val = jnp.where(in_band, val, big)
            return {"cost": val}
        if cost is None:
            cost = spec.cell_cost(qv, rv)
        if self.reverse:
            # the reverse recurrence B[i,j] = C[i,j] + smin(B[i,j+1],
            # B[i+1,j], B[i+1,j+1]) run as a FORWARD sweep in flipped
            # coordinates, with the forward convention's boundary rules
            # mirrored (see kernels/backward.py for the derivation):
            #   flipped row 0   (original m-1): no up/upleft
            #     predecessor, but every cell may TERMINATE a path —
            #     the 0-weight operand, the mirror of free_start;
            #   flipped row m-1 (original 0): no horizontal operand —
            #     forward row-0 cells never chain left-to-right
            #     (free_start replaces their reduced predecessor).
            # Order matters for m == 1 (both rules apply): left and up
            # read big, upleft reads the termination 0 -> B == C.
            is_rowlast = i_l == self.m - 1
            val = cost + spec.reduce3(
                jnp.where(is_rowlast, big, left),
                jnp.where(is_row0, big, up),
                jnp.where(is_row0, jnp.zeros_like(upleft), upleft))
        else:
            val = spec.cell_update(cost, left, up, upleft,
                                   free_start=is_row0)
        in_band = spec.band_valid(i_l, j_col + self.band_shift)
        if in_band is not None:
            # Sakoe–Chiba mask folded into the lane index math: lane l,
            # segment slot k owns global column j_col while computing
            # query row i_l — out-of-band cells read as big so no path
            # can cross them.
            val = jnp.where(in_band, val, big)
        out = {"cost": val}
        if self.with_window:
            # start pointer of the predecessor the hard-min picked;
            # row-0 cells BEGIN a path at their own global column
            s_left, s_up, s_upleft = vals3["start"]
            start = spec.start3(left, up, upleft, s_left, s_up, s_upleft)
            start = jnp.where(is_row0, j_col, start)
            if in_band is not None:
                start = jnp.where(in_band, start, NO_WINDOW)
            out["start"] = start
        return out


def build_plan(spec: DPSpec, *, m: int, segment_width: int,
               num_ref_blocks: int, compute_dtype=jnp.float32,
               with_window: bool = False,
               band_skip: bool = True,
               n: int | None = None, features: int = 1) -> KernelPlan:
    """Convenience constructor accepting a jnp dtype object."""
    return KernelPlan(spec=spec, m=m, segment_width=segment_width,
                      num_ref_blocks=num_ref_blocks,
                      compute_dtype_name=jnp.dtype(compute_dtype).name,
                      with_window=with_window, band_skip=band_skip, n=n,
                      features=features)


# ------------------------------------------------------------- executor
def _generic_kernel(q_ref, r_ref, *refs, plan: KernelPlan):
    """One (batch step, reference-block) grid cell, assembled from the
    plan's channels and fold.

    q_ref:  (1, rows, Mp)      reversed+padded queries (see ops.py),
                               ``plan.rows_per_step`` of each chain,
                               packed as :func:`step_pack_len` says; a
                               multivariate plan's row holds one pack
                               per feature
    r_ref:  (1, w, LANES)      reference block,
                               [k, l] = r[blk*LANES*w + l*w + k];
                               (1, D, w, LANES) for D features
    refs:   ``plan.extra_inputs`` family operand refs (laid out like
            q_ref or r_ref per ``_EXTRA_KIND``), then plan.num_outputs
            output refs, one boundary strip per channel, the fold's
            accumulators (one set per sub-block) and, in a chained
            plan, one sub-block tile per reference-laid
            operand; a cost-tile plan holds the augmented reference
            and the cost tile there instead (:func:`_build_cost_tile`),
            and its q_ref is (1, K, rows T), the matmul's query operand.

    Chain c spans lanes [c L, (c + 1) L).  Sub-block u of the block
    gives lane c L + p the reference lanes u L + p: the same columns
    for every chain, each against its own queries.
    """
    channels = plan.channels
    fold = plan.fold
    n_out, n_ch = plan.num_outputs, len(channels)
    n_acc = len(fold.accumulators())
    ex_names = plan.extra_inputs
    ex_refs = dict(zip(ex_names, refs[:len(ex_names)]))
    refs = refs[len(ex_names):]
    out_refs = refs[:n_out]
    strip_refs = refs[n_out:n_out + n_ch]
    scr = refs[n_out + n_ch:n_out + n_ch + n_acc]
    tile_refs = refs[n_out + n_ch + n_acc:]

    rblk = pl.program_id(1)
    m, w = plan.m, plan.segment_width
    L, chains = plan.lanes_per_chain, plan.chains
    cdt = plan.compute_dtype
    lane = lax.broadcasted_iota(jnp.int32, (plan.rows_per_step, LANES), 1)
    # each lane's place in its chain: lane c L + p is on row t - p
    p = jnp.bitwise_and(lane, L - 1)

    @pl.when(rblk == 0)
    def _init():
        fold.init(scr)

    if plan.checkpoint:
        # publish the block's ENTRY boundary: at this point the strip
        # still holds the whole previous block's right column (the
        # read pointer t+1 leads the write pointer t-127 by LANES rows,
        # so nothing is overwritten yet).  At rblk == 0 the strip holds
        # the previous batch group's garbage — the edge sentinel is the
        # true boundary there.
        refs[plan.num_outputs - 1][0, 0] = jnp.where(
            rblk > 0, strip_refs[0][...].astype(jnp.float32),
            jnp.full((plan.rows_per_step, strip_len(m)), plan.big,
                     jnp.float32))

    def ref_row(ref, k):                  # (1, LANES): reference slot k
        return ref[0, k:k + 1, :].astype(cdt)

    def sweep(u, r_src, ex_src, acc):
        """Sub-block ``u``: m + L - 1 serial steps of every chain over
        ``r_src`` (the block, or its sub-block tile) and ``ex_src``
        (the family operands, the same way), folding into ``acc``."""
        # global ref index of lane's k=0 cell; a reverse band-skip grid
        # starts block_offset layout blocks in (leading flipped columns
        # are out of band for every row), forward grids start at 0
        j_base = ((rblk + plan.block_offset) * LANES + u * L + p) * w
        entered = (rblk > 0) | (u > 0)

        def step(t, carry):
            # lane c L + p is computing query row i = t - p this step
            i_l = t - p                                   # (S, L) int32
            is_row0 = (i_l == 0)

            # q value for (query s, lane p) = q[s, t - p]; q_ref stores
            # the REVERSED query so this is an ascending slice (no lane
            # flip).  A multivariate plan reads its w cell costs from
            # the sub-block's cost tile, or sums its features' windows
            # into them.
            costs = [None] * w
            qv = None
            if plan.cost_tile:
                costs = [tile_refs[-1][k, pl.ds(t, plan.rows_per_step,
                                               stride=plan.tile_steps), :]
                         for k in range(w)]
            elif plan.features > 1:
                costs = _feature_costs(plan, q_ref, r_src, t, p)
            else:
                qv = _lane_window(q_ref, m - 1 + L - 1 - t, p,
                                  L).astype(cdt)

            # per-step family operand values, laid out exactly like qv
            # / r_blk.  q_prev = q[i_l - 1] is the t-1 slice of the same
            # reversed pack (start clamped so t = 0 never reads past
            # the pad; lane 0's masked convention value 0 is injected
            # instead).
            ex_step = {}
            if plan.family == "twed":
                qp = _lane_window(q_ref, m - 1 + L - 1
                                  - jnp.maximum(t - 1, 0), p,
                                  L).astype(cdt)
                ex_step["q_prev"] = jnp.where(is_row0, jnp.zeros_like(qp),
                                              qp)
            elif plan.family == "erp":
                ex_step["bl"] = _lane_window(
                    ex_src["bl"], m - 1 + L - 1 - t, p, L).astype(cdt)

            rows = {ch.name: [] for ch in channels}
            lefts = {ch.name: c[1] for ch, c in zip(channels, carry)}
            for k in range(w):
                vals3 = {}
                for ch, (prev_row, _, prev_left) in zip(channels, carry):
                    up = prev_row[k]
                    upleft = prev_left if k == 0 else prev_row[k - 1]
                    vals3[ch.name] = (lefts[ch.name], up, upleft)
                ex_k = None
                if plan.family == "twed":
                    ex_k = dict(ex_step,
                                r_prev=ref_row(ex_src["r_prev"], k))
                elif plan.family == "erp":
                    ex_k = dict(ex_step, bt=ref_row(ex_src["bt"], k))
                rv = None if plan.features > 1 else ref_row(r_src, k)
                new = plan.cell(qv, rv, is_row0=is_row0,
                                i_l=i_l, j_col=j_base + k, vals3=vals3,
                                extras=ex_k, cost=costs[k])
                for ch in channels:
                    rows[ch.name].append(new[ch.name])
                    lefts[ch.name] = new[ch.name]

            # streaming fold when a lane finishes its bottom row (the
            # family folds additionally see the in-grid mask: the local
            # valid-cell fold is not a bottom-row fold)
            fold.update(acc, at_bottom=(i_l == m - 1), rows=rows,
                        j_base=j_base, plan=plan,
                        in_grid=(i_l >= 0) & (i_l < m))

            # lane roll + boundary-strip read, mechanically per channel
            t_next = jnp.minimum(t + 1, m - 1)
            use_strip = entered & ((t + 1) < m)
            new_carry = []
            for ch, strip_ref, (_, left_in, _) in zip(channels, strip_refs,
                                                      carry):
                last = rows[ch.name][w - 1]               # (S, L)
                strip_val = ch.read_strip(strip_ref, t_next,
                                          compute_dtype=cdt,
                                          lanes_per_chain=L)
                next_left = ch.roll_carry(last, p=p,
                                          strip_val=strip_val,
                                          use_strip=use_strip,
                                          compute_dtype=cdt)
                new_carry.append((tuple(rows[ch.name]), next_left, left_in))

            # publish right columns for the next sub-block (each
            # chain's lane L-1 is on row t - (L - 1))
            i_last = t - (L - 1)

            @pl.when((i_last >= 0) & (i_last < m))
            def _store():
                for ch, strip_ref in zip(channels, strip_refs):
                    ch.write_strip(strip_ref, i_last, rows[ch.name][w - 1],
                                   p=p, lanes_per_chain=L)

            return tuple(new_carry)

        carry0 = tuple(ch.init_carry(strip_ref, p=p, entered=entered, w=w,
                                     compute_dtype=cdt, lanes_per_chain=L)
                       for ch, strip_ref in zip(channels, strip_refs))
        lax.fori_loop(0, m + L - 1, step, carry0)

    if plan.cost_tile:
        aug_ref, lhs_ref, prev_ref, cost_ref = tile_refs
        _cost_lhs(plan, r_ref, aug_ref, lhs_ref)

        def sub_block(u, carry):
            _build_cost_tile(plan, q_ref, u, lhs_ref, prev_ref, cost_ref)
            sweep(u, None, ex_refs, [a.at[u] for a in scr])
            return carry

        lax.fori_loop(0, chains, sub_block, 0)
    elif chains == 1:           # the block is the one sub-block's tile
        sweep(0, r_ref, ex_refs, [a.at[0] for a in scr])
    else:
        # the reference block and the family operands laid out like
        # it, as each sub-block's tiles; the query-laid ones as they are
        r_names = [name for name in ex_names if _EXTRA_KIND[name] == "r"]
        ex_src = dict(ex_refs, **dict(zip(r_names, tile_refs[1:])))

        def sub_block(u, carry):
            for src, dst in zip([r_ref] + [ex_refs[n] for n in r_names],
                                tile_refs):
                dst[...] = _tile_chains(src[...], u, L)
            sweep(u, tile_refs[0], ex_src, [a.at[u] for a in scr])
            return carry

        lax.fori_loop(0, chains, sub_block, 0)

    @pl.when(rblk == plan.grid_blocks - 1)
    def _finalize():
        # each chain's per-query results, in its own L lanes of the
        # lane-dense output tile
        tiles = None
        for c in range(chains):
            cols = fold.finalize(
                [_one_chain_lanes(a, c, L, lane) for a in scr], plan)
            if tiles is None:
                tiles = [jnp.broadcast_to(col, lane.shape) for col in cols]
            else:
                mine = (lane >= c * L) & (lane < (c + 1) * L)
                tiles = [jnp.where(mine, col, tile)
                         for col, tile in zip(cols, tiles)]
        for out_ref, tile in zip(out_refs, tiles):
            out_ref[0] = tile.astype(out_ref.dtype)


def _step_queries(x, plan: KernelPlan):
    """Prepared query packs (G, SUBLANES, Mp) -> the (steps, rows, Mp')
    rows grid steps read: ``plan.queries_per_step`` consecutive queries
    a step (the last step's rest zero queries), query c * rows + s of a
    step in sublane s of chain c.  At one chain that is consecutive
    groups stacked, which moves no data under the TPU's (8, 128)
    tiling.  At k chains of L lanes each feature's pack, from position
    LANES - L on (its last L - 1 leading zeros), is cut into L-lane
    pieces, and lane tile tau of the step row holds piece tau of every
    chain (:func:`step_pack_len`)."""
    G, S, Mp = x.shape
    rows, per, L = plan.rows_per_step, plan.queries_per_step, \
        plan.lanes_per_chain
    x = x.reshape(G * S, Mp)
    x = jnp.pad(x, ((0, -(G * S) % per), (0, 0)))
    if plan.chains == 1:
        return x.reshape(-1, rows, Mp)
    D = plan.features
    stride = Mp // D                      # lanes of one feature's pack
    tiles = feature_stride(plan.m, L) // LANES
    x = x.reshape(-1, D, stride)[:, :, LANES - L:LANES - L + tiles * L]
    x = x.reshape(-1, plan.chains, rows, D, tiles, L)
    return x.transpose(0, 2, 3, 4, 1, 5).reshape(
        -1, rows, step_pack_len(plan.m, D, L))


def _tile_queries(x, plan: KernelPlan):
    """Prepared multivariate query packs (G, SUBLANES, D * stride) -> a
    cost-tile plan's (steps, chains x ``tile_depth``, rows * T) bfloat16
    query operands.  Chain c's rows, lane s * T + i: ``-2 q[i]``'s D
    features, ``|q[i]|^2`` and 1 of chain c's query s (as
    :func:`_step_queries` places the queries), split by
    :func:`_split3` into the query parts of ``_HIGHEST_PRODUCTS``;
    frames i >= m are zero queries."""
    G, S, Mp = x.shape
    rows, per, D = plan.rows_per_step, plan.queries_per_step, plan.features
    m, T, depth = plan.m, plan.tile_steps, plan.tile_depth
    q = x.reshape(G * S, D, Mp // D)[:, :, LANES - 1:LANES - 1 + m]
    q = jnp.flip(q, axis=2).astype(jnp.float32)       # (B, D, m) in order
    q = jnp.pad(q, ((0, -(G * S) % per), (0, 0), (0, T - m)))
    parts = _split3(jnp.concatenate(
        [-2.0 * q, jnp.sum(q * q, axis=1, keepdims=True),
         jnp.ones((q.shape[0], 1, T), jnp.float32)], axis=1))
    q = jnp.concatenate([parts[qi] for _, qi in _HIGHEST_PRODUCTS], axis=1)
    q = jnp.pad(q, ((0, 0), (0, depth - q.shape[1]), (0, 0)))
    q = q.reshape(-1, plan.chains, rows, depth, T)
    return q.transpose(0, 1, 3, 2, 4).reshape(
        -1, plan.chains * depth, rows * T).astype(jnp.bfloat16)


def wavefront_call(plan: KernelPlan, q_rev_pad: jnp.ndarray,
                   r_layout: jnp.ndarray, *extras: jnp.ndarray,
                   interpret: bool | None = None):
    """Execute a :class:`KernelPlan` as one ``pallas_call``.

    q_rev_pad: (G, SUBLANES, Mp) reversed queries from
               ``ops.prepare_queries``, Mp = ``query_pack_len(m)``
               (a reverse plan takes the FLIPPED queries prepared the
               same way, against ``ops.swizzle_reference_reverse``)
    r_layout:  (R, w, LANES) pre-swizzled reference blocks, or
               (R, D, w, LANES) for a plan of D features, whose
               queries are packed (G, SUBLANES, query_pack_len(m, D))
    extras:    ``plan.extra_inputs`` family operands, in order, each
               packed like q_rev_pad ('q'-kind) or r_layout ('r'-kind)
               — see ``ops.family_extras``.  They ride the SAME
               pallas_call through plan-driven in_specs; no family
               adds a second kernel.
    interpret: None = ``ops.default_interpret()`` (compiled on TPU).
    returns    (costs (G, SUBLANES) f32, ends (G, SUBLANES) i32), plus
               starts in the middle for window plans, plus a trailing
               (G', grid_blocks, rows, m) f32 boundary-strip tensor for
               checkpoint plans, one strip per grid step:
               ``plan.rows_per_step`` queries in packed order,
               G' = ceil(G * SUBLANES / rows) — every channel rides the
               SAME pallas_call, never a second sweep.

    A grid step takes ``plan.queries_per_step`` consecutive queries
    (:func:`_step_queries`): ``rows_per_step`` (one or two packed
    groups) in each of the plan's chains.  A batch that does not fill
    its last step gains zero queries, trimmed from the outputs like
    batch padding.
    """
    G, S, Mp = q_rev_pad.shape
    D = plan.features
    if r_layout.shape[1:-2] != ((D,) if D > 1 else ()):
        raise ValueError(
            f"reference layout {tuple(r_layout.shape)} does not match the "
            f"plan's {D} feature(s)")
    R, w, lanes = r_layout.shape[0], *r_layout.shape[-2:]
    if len(extras) != len(plan.extra_inputs):
        raise ValueError(
            f"family {plan.family!r} plans take extra operands "
            f"{plan.extra_inputs} (got {len(extras)}): build them with "
            "ops.family_extras(spec, queries, reference, ...)")
    if S != SUBLANES or lanes != LANES:
        raise ValueError(
            f"operand layout mismatch: queries packed {S} per group "
            f"(want {SUBLANES}), reference {lanes} lanes (want {LANES})")
    if w != plan.segment_width or R != plan.num_ref_blocks:
        raise ValueError(
            f"reference layout {tuple(r_layout.shape)} does not match "
            f"the plan (segment_width={plan.segment_width}, "
            f"num_ref_blocks={plan.num_ref_blocks})")
    if Mp != query_pack_len(plan.m, D):
        raise ValueError(
            f"query pack length {Mp} != query_pack_len(m, features) = "
            f"{query_pack_len(plan.m, D)} (m={plan.m}, features={D})")

    if interpret is None:
        from repro.kernels.ops import default_interpret  # imports us
        interpret = default_interpret()
    kernel = functools.partial(_generic_kernel, plan=plan)
    rows, L = plan.rows_per_step, plan.lanes_per_chain
    grid = (_ceil_to(G * SUBLANES, plan.queries_per_step)
            // plan.queries_per_step, plan.grid_blocks)
    # per-query results leave as lane-dense (rows, LANES) tiles, every
    # lane of a chain holding its query's value
    dtypes = [jnp.float32, jnp.int32] + ([jnp.int32] * plan.with_window)
    out_shape = [jax.ShapeDtypeStruct((grid[0], rows, LANES), dt)
                 for dt in dtypes]
    out_specs = [pl.BlockSpec((1, rows, LANES), lambda b, r: (b, 0, 0))
                 for _ in dtypes]
    ms = strip_len(plan.m)
    if plan.checkpoint:
        # one (rows, m) entry-boundary strip per executed block: the
        # O(M * N/block) residual the fused soft backward
        # re-materializes E tiles from (kernels/backward.py)
        out_shape.append(jax.ShapeDtypeStruct(
            (grid[0], plan.grid_blocks, rows, ms), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, rows, ms),
                                      lambda b, r: (b, r, 0, 0)))
    off = plan.block_offset
    r_block = (1, w, LANES) if D == 1 else (1, D, w, LANES)
    q_block = ((1, plan.chains * plan.tile_depth, rows * plan.tile_steps)
               if plan.cost_tile else (1, rows, step_pack_len(plan.m, D, L)))
    in_specs = [
        pl.BlockSpec(q_block, lambda b, r: (b, 0, 0)),
        # grid step r reads layout block r + offset (reverse band-skip
        # grids start past the leading out-of-band flipped blocks)
        pl.BlockSpec(r_block,
                     lambda b, r: (r + off,) + (0,) * (len(r_block) - 1)),
    ]
    for name, arr in zip(plan.extra_inputs, extras):
        if _EXTRA_KIND[name] == "r":
            if arr.shape != r_layout.shape:
                raise ValueError(
                    f"family operand {name!r} {tuple(arr.shape)} must "
                    f"be swizzled like the reference layout "
                    f"{tuple(r_layout.shape)}")
            in_specs.append(
                pl.BlockSpec((1, w, LANES), lambda b, r: (r + off, 0, 0)))
        else:
            if arr.shape != q_rev_pad.shape:
                raise ValueError(
                    f"family operand {name!r} {tuple(arr.shape)} must "
                    f"be packed like the prepared queries "
                    f"{tuple(q_rev_pad.shape)}")
            in_specs.append(pl.BlockSpec(q_block, lambda b, r: (b, 0, 0)))
    scratch = [ch.strip_shape(plan.m, rows, L) for ch in plan.channels]
    scratch += [pltpu.VMEM((plan.chains, rows, LANES), dt)
                for dt in plan.fold.accumulators()]
    if plan.cost_tile:        # the augmented reference, the cost tile
        scratch += [
            pltpu.VMEM((plan.tile_depth * w, LANES), jnp.float32),
            pltpu.VMEM((w * LANES, plan.tile_depth), jnp.bfloat16),
            pltpu.VMEM((w, L, LANES), jnp.float32),
            pltpu.VMEM((w, rows * plan.tile_steps, LANES), jnp.float32)]
    elif plan.chains > 1:     # each sub-block's tile of the reference
        #                       and of the operands laid out like it
        scratch += [pltpu.VMEM(r_block, r_layout.dtype)] + [
            pltpu.VMEM((1, w, LANES), x.dtype)
            for name, x in zip(plan.extra_inputs, extras)
            if _EXTRA_KIND[name] == "r"]
    q_rev_pad = (_tile_queries if plan.cost_tile else _step_queries)(
        q_rev_pad, plan)
    extras = tuple(_step_queries(x, plan) if _EXTRA_KIND[name] == "q"
                   else x for name, x in zip(plan.extra_inputs, extras))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes())
    # a stable name for the profiler's trace: the HLO op is named
    # after it, whatever jitted function dispatches the kernel
    name = "sdtw_wavefront_reverse" if plan.reverse else "sdtw_wavefront"
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        out_shape=tuple(out_shape), scratch_shapes=scratch,
        interpret=interpret, name=name, **kwargs,
    )(q_rev_pad, r_layout, *extras)
    # query c * rows + s of a grid step sits in sublane s, lanes of
    # chain c: back to the prepared (G, SUBLANES) order
    out = [x[:, :, ::L].transpose(0, 2, 1).reshape(-1, SUBLANES)[:G]
           for x in out[:len(dtypes)]] + \
        [x[..., :plan.m] for x in out[len(dtypes):]]
    if plan.with_window:
        costs, ends, starts = out
        return costs, starts, ends
    return tuple(out)             # (costs, ends[, checkpoints])
