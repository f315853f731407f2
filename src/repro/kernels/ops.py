"""jit'd public wrappers for the Pallas kernels: padding, the DTWax-style
offline reference swizzle, dtype policy, and unpadding.

The reference reorder mirrors DTWax's offline reference layout
optimization (paper §3): element ``r[(b*LANES + l)*w + k]`` lands at
``r_layout[b, k, l]`` so that each kernel step reads one fully-coalesced
(w, LANES) VMEM tile per reference block.

Preparation (padding + swizzle) is split from dispatch so callers that
align many query batches against the same reference — notably
``repro.Aligner`` sessions and ``repro.search.ReferenceIndex`` — can
pay the layout cost once and feed the cached ``(R, w, LANES)`` blocks
straight into :func:`sdtw_wavefront_prepped`. The one-shot
:func:`sdtw_wavefront` wrapper goes through the exact same prep +
dispatch code path; an ``Aligner`` additionally closes the cached
layout over a jitted prepare+dispatch closure, so its warm calls are
dispatch-only.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.spec import (DEFAULT_SPEC, NO_WINDOW,  # noqa: F401
                             PAD_VALUE, DPSpec)
# PAD_VALUE re-exported: cost >= (q - 1e6)^2 never wins — the dtype
# rationale (and why it rules out cosine) lives with the other
# sentinels in core/spec.py.
from repro.kernels.wavefront import (CHAIN_LANES, LANES, SUBLANES,
                                     KernelPlan, build_plan,
                                     feature_stride, query_pack_len,
                                     wavefront_call)
from repro.kernels.normalizer import normalizer_pallas


DEFAULT_SEGMENT_WIDTH = 8
#   The untuned per-lane reference segment width (the paper's thread-
#   coarsening knob w, Fig. 3).  ``repro.tune`` searches
#   DEFAULT_WIDTH_CANDIDATES around it per workload; the default always
#   sits in the candidate set so a tuned width can never lose to it on
#   the same measurements.

DEFAULT_WIDTH_CANDIDATES = (2, 4, 8, 14, 16, 32)
#   The paper's Fig. 3 sweep points (AMD optimum: 14) plus the TPU
#   sublane-aligned powers of two.


def validate_segment_width(w) -> int:
    """The candidate-width contract: a positive int (bools rejected —
    ``True`` silently meaning width 1 is a bug, not a knob)."""
    if isinstance(w, bool) or not isinstance(w, int):
        raise ValueError(
            f"segment_width must be an int >= 1 (or the string 'auto' "
            f"where autotuning is supported), got {w!r}")
    if w < 1:
        raise ValueError(f"segment_width must be >= 1, got {w}")
    return w


def width_candidates(n: int, candidates=None) -> tuple:
    """Validated, sorted, deduplicated candidate widths for a reference
    of length ``n``.

    Widths whose padded layout (``ceil_to(n, LANES * w)``) is more than
    4x the real reference are dropped — a sweep that is mostly
    PAD_VALUE columns can never win a tuning trial, so measuring it is
    pure budget waste on short references.  The smallest candidate
    always survives, so the set is never empty.
    """
    if n < 1:
        raise ValueError(f"reference length must be >= 1, got {n}")
    cands = sorted({validate_segment_width(w) for w in
                    (DEFAULT_WIDTH_CANDIDATES if candidates is None
                     else candidates)})
    if not cands:
        raise ValueError("empty segment-width candidate set")
    kept = [w for w in cands if ceil_to(n, LANES * w) <= 4 * n]
    return tuple(kept) if kept else (cands[0],)


def default_interpret() -> bool:
    """Pallas ``interpret`` default: compiled on TPU, interpreted
    everywhere else — so the same call site runs the real kernel on TPU
    and the reference interpreter on CPU CI. Explicit ``interpret=``
    arguments always win."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


def ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def swizzle_reference(r: jnp.ndarray, segment_width: int) -> jnp.ndarray:
    """(N,) -> (R, w, LANES) with [b, k, l] = r[(b*LANES + l)*w + k];
    a multivariate (N, D) -> (R, D, w, LANES) with [b, d, k, l] =
    r[(b*LANES + l)*w + k, d]."""
    w = segment_width
    n_pad = ceil_to(r.shape[0], LANES * w)
    if r.ndim == 2:
        r = jnp.pad(r, ((0, n_pad - r.shape[0]), (0, 0)),
                    constant_values=PAD_VALUE)
        return r.reshape(-1, LANES, w, r.shape[1]).transpose(0, 3, 2, 1)
    r = jnp.pad(r, (0, n_pad - r.shape[0]), constant_values=PAD_VALUE)
    return r.reshape(-1, LANES, w).transpose(0, 2, 1)


def swizzle_reference_reverse(r: jnp.ndarray,
                              segment_width: int) -> jnp.ndarray:
    """(N,) -> (R, w, LANES) REVERSE layout for the soft-DTW backward
    sweep: ``flip(r)`` LEFT-padded with PAD_VALUE to the same
    ``R * LANES * w`` capacity as :func:`swizzle_reference`, then
    swizzled identically.

    Left-padding makes reverse layout block r' cover exactly the
    columns of forward block ``R - 1 - r'`` (in flipped order), so the
    forward and reverse sweeps' checkpoint strips line up
    block-for-block (see ``kernels/backward.py``).  Flipped column j'
    maps to original column ``n_pad - 1 - j'``; the pad cells sit at
    flipped columns ``[0, n_pad - n)`` and behave exactly like the
    forward right-pad — their ~1e12 costs carry weight
    ``exp(-1e12/gamma) == 0`` in every soft fold."""
    w = segment_width
    n_pad = ceil_to(r.shape[0], LANES * w)
    rflip = jnp.flip(r)
    rflip = jnp.pad(rflip, (n_pad - r.shape[0], 0),
                    constant_values=PAD_VALUE)
    return rflip.reshape(-1, LANES, w).transpose(0, 2, 1)


def unswizzle_reference(r_layout: jnp.ndarray) -> jnp.ndarray:
    """(R, w, LANES) -> (R*LANES*w,) inverse of :func:`swizzle_reference`
    (padded tail included); (R, D, w, LANES) -> (R*LANES*w, D)."""
    if r_layout.ndim == 4:
        return r_layout.transpose(0, 3, 2, 1).reshape(
            -1, r_layout.shape[1])
    return r_layout.transpose(0, 2, 1).reshape(-1)


def prepare_queries(q: jnp.ndarray) -> jnp.ndarray:
    """(B, M) -> (G, SUBLANES, query_pack_len(M)): each query reversed
    behind LANES-1 zeros, zero-padded on the right to the pack length.
    A multivariate (B, M, D) -> (G, SUBLANES, query_pack_len(M, D)):
    each feature packed so, ``feature_stride(M)`` lanes apart."""
    if q.ndim == 3:
        B, M, D = q.shape
        stride = feature_stride(M)
        q = jnp.pad(q, ((0, ceil_to(B, SUBLANES) - B), (0, 0), (0, 0)))
        qrev = jnp.flip(q, axis=1).transpose(0, 2, 1)      # (Bp, D, M)
        qrev = jnp.pad(qrev, ((0, 0), (0, 0),
                              (LANES - 1, stride - M - (LANES - 1))))
        return qrev.reshape(-1, SUBLANES, D * stride)
    B, M = q.shape
    b_pad = ceil_to(B, SUBLANES)
    q = jnp.pad(q, ((0, b_pad - B), (0, 0)))
    qrev = jnp.flip(q, axis=1)
    mp = query_pack_len(M)
    qrev = jnp.pad(qrev, ((0, 0), (LANES - 1, mp - M - (LANES - 1))))
    return qrev.reshape(-1, SUBLANES, mp)


def validate_prepped(q_prepped, r_layout, *, m: int, n: int,
                     segment_width: int) -> None:
    """Shaped errors for mis-packed kernel operands.

    A reference layout swizzled for one ``segment_width`` but
    dispatched with another used to fail deep inside the pallas_call
    with an opaque shape assert; these checks name the mismatch and the
    fix instead.
    """
    if getattr(r_layout, "ndim", None) not in (3, 4) or \
            r_layout.shape[-2:] != (segment_width, LANES):
        raise ValueError(
            f"reference layout {tuple(getattr(r_layout, 'shape', ()))} "
            f"does not match segment_width={segment_width}: expected "
            f"(R, {segment_width}, {LANES}) (or (R, D, {segment_width}, "
            f"{LANES}) for D features) from "
            f"swizzle_reference(reference, segment_width="
            f"{segment_width}) — the layout must be swizzled with the "
            f"same segment_width it is dispatched with")
    n_padded = r_layout.shape[0] * segment_width * LANES
    if n > n_padded:
        raise ValueError(
            f"reference length n={n} exceeds the padded layout "
            f"capacity {n_padded} (= {r_layout.shape[0]} blocks x "
            f"{segment_width} x {LANES}); segment_width must divide "
            f"the layout the reference was padded for — re-swizzle "
            f"with swizzle_reference(reference, {segment_width})")
    features = layout_features(r_layout)
    if getattr(q_prepped, "ndim", None) != 3 or \
            q_prepped.shape[1] != SUBLANES or \
            q_prepped.shape[2] != query_pack_len(m, features):
        raise ValueError(
            f"query pack {tuple(getattr(q_prepped, 'shape', ()))} does "
            f"not match m={m} and {features} feature(s): expected (G, "
            f"{SUBLANES}, {query_pack_len(m, features)}) from "
            f"prepare_queries")


def layout_features(r_layout) -> int:
    """D of a multivariate (R, D, w, LANES) reference layout, 1 of a
    univariate (R, w, LANES) one."""
    return int(r_layout.shape[1]) if r_layout.ndim == 4 else 1


# A chained plan's serial step ran up to 3.4% longer than the one-chain
# step on a v5e (PERF.md section 6), so chains are taken only
# where they cut the steps by at least this share.
CHAIN_STEP_GROWTH = 0.04
# VMEM a chained plan may hold (KernelPlan.vmem_bytes): an eighth of a
# v5e's 128 MiB.  A chain more grows the query block and the strip
# about as many times; a one-chain plan keeps what it needs.
CHAIN_VMEM_BUDGET = 16 * 2 ** 20
# VMEM a multivariate plan may hold with its cost tile
# (KernelPlan.vmem_bytes): half of a v5e's 128 MiB.  The tile grows
# with the query length; a plan whose tile does not fit computes its
# costs on the VPU in each step.
COST_TILE_VMEM_BUDGET = 64 * 2 ** 20
# Features from which the cost tile pays: below it the VPU's per-step
# cost is fewer bundles than the tile's build and reads (a v5e's
# compiled loops, PERF.md section 5).
COST_TILE_MIN_FEATURES = 5


def plan_rows(plan: KernelPlan, batch: int) -> KernelPlan:
    """``plan`` with the queries per serial step a dispatch of ``batch``
    queries runs, as rows a chain and chains a step.

    Rows: two packed groups (``2 * SUBLANES``) whenever the batch fills
    at least two groups, else one.  Chains: the most (2, 4 or 8, each
    of at least 16 lanes) such that

      * the batch fills them: ``rows x chains`` is within the batch
        rounded up to the rows, so no step carries a chain of pad
        queries;
      * they pay: a chain of L lanes fills and drains in L - 1 steps
        of each sub-block of ``m + L - 1``, where one chain of LANES
        pays LANES - 1 in each block, and the ratio of the steps,
        ``(m + LANES - 1) / (m + L - 1)``, must reach ``1 +
        CHAIN_STEP_GROWTH``, so long queries keep one chain;
      * they fit: :meth:`KernelPlan.vmem_bytes` within
        ``CHAIN_VMEM_BUDGET``;

    else one.  Reverse and checkpoint sweeps keep one.  Every query's
    arithmetic is the same at any rows and chains.

    Then a squared-Euclidean plan of at least
    ``COST_TILE_MIN_FEATURES`` features takes the MXU's cost tile
    (``KernelPlan.cost_tile``) where its :meth:`KernelPlan.vmem_bytes`
    is within ``COST_TILE_VMEM_BUDGET``; the tile changes only where
    each cell's cost comes from, and the order of its float32
    additions."""
    rows = 2 * SUBLANES if batch > SUBLANES else SUBLANES
    plan = dataclasses.replace(plan, rows_per_step=rows,
                               lanes_per_chain=LANES, cost_tile=False)
    if plan.reverse or plan.checkpoint:
        return plan
    for lanes in CHAIN_LANES[:-1]:                  # the most chains first
        chained = dataclasses.replace(plan, lanes_per_chain=lanes)
        if (chained.queries_per_step <= ceil_to(batch, rows)
                and plan.m + LANES - 1 >= (1 + CHAIN_STEP_GROWTH)
                * (plan.m + lanes - 1)
                and chained.vmem_bytes() <= CHAIN_VMEM_BUDGET):
            plan = chained
            break
    if (plan.features >= COST_TILE_MIN_FEATURES
            and plan.spec.distance == "sqeuclidean"):
        tiled = dataclasses.replace(plan, cost_tile=True)
        if tiled.vmem_bytes() <= COST_TILE_VMEM_BUDGET:
            return tiled
    return plan


def kernel_plan(spec: DPSpec | None = None, *, m: int, n: int,
                segment_width: int = 8, compute_dtype=jnp.float32,
                with_window: bool = False,
                batch: int | None = None, features: int = 1,
                checkpoint: bool = False) -> KernelPlan:
    """The :class:`~repro.kernels.wavefront.KernelPlan` a dispatch of
    these (unpadded) shapes executes — band-skip geometry included, so
    callers (search stats, benchmarks) can read ``plan.grid_blocks``
    vs ``plan.num_ref_blocks`` without running the kernel.  Given the
    ``batch``, the plan also carries the rows and chains a step that
    batch runs (:func:`plan_rows`); without it, one group a step in
    one chain.  ``checkpoint``: the fused soft backward's forward
    sweep, which keeps one chain."""
    sp = DEFAULT_SPEC if spec is None else spec
    blocks = ceil_to(n, LANES * segment_width) // (LANES * segment_width)
    plan = build_plan(sp, m=m,
                      segment_width=segment_width, num_ref_blocks=blocks,
                      compute_dtype=compute_dtype, with_window=with_window,
                      n=n if sp.family != "sdtw" else None,
                      features=features)
    if checkpoint:
        plan = dataclasses.replace(plan, checkpoint=True)
    return plan if batch is None else plan_rows(plan, batch)


def band_blocks_all(spec: DPSpec, m: int, n: int) -> bool:
    """Does the band exclude every fold-eligible cell, so that no
    alignment exists?  Static in (m, n, band): such a call is answered
    without dispatching the kernel."""
    if spec.band is None:
        return False
    if spec.family in ("twed", "erp"):
        # global families: the corner (m-1, n-1) sits |m-n| off the
        # diagonal — a tighter band disconnects the global path
        return spec.band < abs(m - n)
    if spec.family == "local":
        return False                 # cell (0, 0) is always in band
    return m - 1 - spec.band > n - 1


def wavefront_work(spec: DPSpec | None = None, *, batch: int, m: int,
                   n: int, segment_width: int = 8,
                   features: int = 1,
                   checkpoint: bool = False) -> dict | None:
    """:meth:`KernelPlan.work` of one dispatch of these (unpadded)
    shapes, or None where :func:`band_blocks_all` answers the call and
    no kernel runs.  The work does not depend on the compute dtype or
    on the outputs asked for; it does on ``checkpoint`` (the fused soft
    backward's sweeps), which keeps one chain."""
    sp = DEFAULT_SPEC if spec is None else spec
    if band_blocks_all(sp, m, n):
        return None
    return kernel_plan(sp, m=m, n=n, segment_width=segment_width,
                       batch=batch, features=features,
                       checkpoint=checkpoint).work(batch, n)


_WORK_COUNTERS = tuple((k, f"kernel.wavefront.{k}") for k in
                       ("grid_steps", "loop_steps", "lane_cells",
                        "cells_real", "feature_cells"))


def count_wavefront(work: dict) -> None:
    """Add one wavefront dispatch's :meth:`KernelPlan.work` to the
    process-wide counters ``kernel.wavefront.dispatches`` /
    ``.grid_steps`` / ``.loop_steps`` / ``.lane_cells`` /
    ``.cells_real`` / ``.feature_cells`` (equal to ``.cells_real`` for
    univariate dispatches) of :func:`repro.obs.default_registry`, and to
    ``.wide_dispatches`` where the plan carried two query groups a
    step, to ``.chained_dispatches`` where it carried more than one
    lane chain, and to ``.cost_tile_dispatches`` where the MXU built
    its cell costs (each over ``.dispatches``: how often each engages).

    Process-wide because the chip and its kernels belong to the
    process, not to a session.  Call it on the host once per dispatch
    that ran, never from code that runs while JAX traces: a jitted
    function's body runs once per trace, not once per call."""
    reg = obs.default_registry()
    reg.inc("kernel.wavefront.dispatches")
    if work["rows_per_step"] > SUBLANES:
        reg.inc("kernel.wavefront.wide_dispatches")
    if work["lanes_per_chain"] < LANES:
        reg.inc("kernel.wavefront.chained_dispatches")
    if work["cost_tile"]:
        reg.inc("kernel.wavefront.cost_tile_dispatches")
    for key, name in _WORK_COUNTERS:
        reg.inc(name, work[key])


@functools.partial(jax.jit, static_argnames=("spec", "segment_width",
                                             "compute_dtype"))
def family_extras_ref(spec: DPSpec, reference, *, segment_width,
                      compute_dtype=jnp.float32) -> tuple:
    """The reference-derived family operands: twed's shifted reference
    ``r[j-1]`` (``r[-1] = 0`` convention), erp's gap-cost prefix
    ``bt[j] = cumsum d(r_k, gap)`` — both swizzled like the reference
    layout.  Depend only on (reference, segment_width): an
    :class:`repro.Aligner` session computes them ONCE next to its
    cached layout, as closed-over constants (bit-identical to the
    one-shot path — this standalone jit is the single compilation of
    the prefix arithmetic)."""
    if spec.family == "twed":
        r = jnp.asarray(reference).astype(compute_dtype)
        r_prev = jnp.concatenate([jnp.zeros((1,), r.dtype), r[:-1]])
        return (swizzle_reference(r_prev, segment_width),)
    if spec.family == "erp":
        r = jnp.asarray(reference).astype(compute_dtype)
        bt = jnp.cumsum(spec.cell_cost(r, spec.gap))
        return (swizzle_reference(bt, segment_width),)
    return ()


@functools.partial(jax.jit, static_argnames=("spec", "compute_dtype"))
def family_extras_query(spec: DPSpec, queries, *,
                        compute_dtype=jnp.float32) -> tuple:
    """The query-derived family operands: erp's gap-cost prefix
    ``bl[i] = cumsum d(q_k, gap)``, packed like the prepared queries."""
    if spec.family == "erp":
        q = jnp.asarray(queries).astype(compute_dtype)
        bl = jnp.cumsum(spec.cell_cost(q, spec.gap), axis=-1)
        return (prepare_queries(bl),)
    return ()


def family_extras(spec: DPSpec, queries, reference, *, segment_width,
                  compute_dtype=jnp.float32) -> tuple:
    """The family's extra kernel operands (``plan.extra_inputs`` order),
    packed for :func:`sdtw_wavefront_prepped` — empty for sdtw/local.

    All prefix arithmetic runs in the kernel's f32, through the same
    two jitted helpers every caller uses, so the prefix-peeled
    boundaries match the engine grid bit-for-bit.
    """
    return (family_extras_ref(spec, reference, segment_width=segment_width,
                              compute_dtype=compute_dtype)
            + family_extras_query(spec, queries,
                                  compute_dtype=compute_dtype))


@functools.partial(jax.jit, static_argnames=("segment_width", "compute_dtype"))
def _prep(queries, reference, *, segment_width, compute_dtype):
    return (prepare_queries(queries.astype(compute_dtype)),
            swizzle_reference(reference.astype(compute_dtype), segment_width))


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _dispatch(q_prepped, r_layout, extras=(), *, plan, interpret):
    out = wavefront_call(plan, q_prepped, r_layout, *extras,
                         interpret=interpret)
    return tuple(x.reshape(-1) for x in out)


def sdtw_wavefront_prepped(q_prepped: jnp.ndarray, r_layout: jnp.ndarray, *,
                           batch: int, m: int, n: int,
                           segment_width: int = 8,
                           compute_dtype=jnp.float32,
                           interpret: bool | None = None,
                           spec: DPSpec | None = None,
                           return_window: bool = False,
                           extras: tuple = ()):
    """Dispatch the wavefront kernel on pre-packed operands.

    q_prepped: (G, SUBLANES, query_pack_len(m, D)) from
               :func:`prepare_queries`
    r_layout:  (R, w, LANES) from :func:`swizzle_reference`, or
               (R, D, w, LANES) for multivariate inputs of D features
    extras:    the spec family's packed extra operands from
               :func:`family_extras` (required iff the plan's
               ``extra_inputs`` is non-empty; sdtw/local take none).
               Families ride the SAME single pallas_call — the plan
               only adds operands and swaps the stream fold.
    batch:     true (un-padded) query count; m: query length; n: true
               reference length (pre-swizzle-padding).
    interpret: None = auto (:func:`default_interpret`).
    spec:      recurrence spec; None = squared-Euclidean hard-min
               unbanded (the kernel's capability set is declared in
               ``repro.backends.builtin``).
    return_window: also return matched-window start columns — the start
               pointers ride the same wavefront carries (ONE
               pallas_call either way, see kernels.sdtw_wavefront).
               A band blocking every REAL bottom-row cell
               (``m - 1 - band > n - 1``) is detected statically here
               and short-circuits to the engine/ref answer — +inf
               costs, end 0, NO_WINDOW starts — instead of letting
               paths through PAD_VALUE padding columns report a
               pad-dominated finite cost (the kernel's former
               blocked-band semantics, which diverged from every other
               backend and would have leaked through device-aware
               auto-selection on TPU).
    Returns (costs (batch,) f32, end_indices (batch,) i32) — or
    (costs, starts, ends) when ``return_window`` — with indices clamped
    to ``n - 1`` so padded reference columns can never leak out as
    match positions.

    ``batch`` and ``n`` only trim the padded rows and clamp the end
    indices, OUTSIDE the jit: the compile cache is keyed by the padded
    operand shapes alone, so a serving batcher emitting the same shape
    grid with varying real-row counts (or references whose lengths
    differ but pad to the same layout) reuses one executable.

    Soft-min specs run the soft carry channel (running logsumexp fold,
    see ``repro.kernels.wavefront``); Sakoe–Chiba specs automatically
    execute the band-skip plan — fewer grid steps, identical outputs
    (``kernel_plan(...)`` exposes the geometry); an operand of two or
    more query groups runs two groups a serial step, and one that
    fills more lane chains runs them (:func:`plan_rows`), again with
    identical outputs.
    """
    validate_prepped(q_prepped, r_layout, m=m, n=n,
                     segment_width=segment_width)
    sp = DEFAULT_SPEC if spec is None else spec
    if band_blocks_all(sp, m, n):
        # no alignment exists: answer without touching the kernel —
        # engine parity (+inf, end 0, NO_WINDOW start)
        costs = jnp.full((batch,), jnp.inf, jnp.float32)
        ends = jnp.zeros((batch,), jnp.int32)
        if return_window:
            return (costs, jnp.full((batch,), NO_WINDOW, jnp.int32),
                    ends)
        return costs, ends
    plan = plan_rows(build_plan(
        sp, m=m, segment_width=segment_width,
        num_ref_blocks=r_layout.shape[0], compute_dtype=compute_dtype,
        with_window=return_window, n=n if sp.family != "sdtw" else None,
        features=layout_features(r_layout)),
        q_prepped.shape[0] * SUBLANES)
    out = _dispatch(q_prepped, r_layout, tuple(extras), plan=plan,
                    interpret=_resolve_interpret(interpret))
    if return_window:
        costs, starts, ends = out
        # clamp padded-column starts like the ends, but keep the
        # NO_WINDOW "no window" sentinel (blocked alignments) intact
        return (costs[:batch], jnp.clip(starts[:batch], NO_WINDOW, n - 1),
                jnp.minimum(ends[:batch], n - 1))
    costs, ends = out
    return costs[:batch], jnp.minimum(ends[:batch], n - 1)


def sdtw_wavefront(queries: jnp.ndarray, reference: jnp.ndarray, *,
                   segment_width: int = 8,
                   compute_dtype=jnp.float32,
                   interpret: bool | None = None,
                   spec: DPSpec | None = None,
                   return_window: bool = False):
    """Batched subsequence DTW via the Pallas wavefront kernel.

    queries: (B, M) float; reference: (N,) float — or multivariate
    (B, M, D) queries against an (N, D) reference.
    interpret: None = auto (compiled on TPU, interpreted elsewhere).
    Returns (costs (B,) f32, end_indices (B,) i32), or
    (costs, starts, ends) when ``return_window``.

    A call on concrete arrays counts its dispatch
    (:func:`count_wavefront`); a call while JAX traces counts nothing.
    """
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    B, M = queries.shape[:2]
    N = reference.shape[0]
    features = queries.shape[2] if queries.ndim == 3 else 1
    qk, rk = _prep(queries, reference, segment_width=segment_width,
                   compute_dtype=compute_dtype)
    sp = DEFAULT_SPEC if spec is None else spec
    extras = family_extras(sp, queries, reference,
                           segment_width=segment_width,
                           compute_dtype=compute_dtype)
    out = sdtw_wavefront_prepped(
        qk, rk, batch=B, m=M, n=N, segment_width=segment_width,
        compute_dtype=compute_dtype, interpret=interpret, spec=spec,
        return_window=return_window, extras=extras)
    if not isinstance(qk, jax.core.Tracer):      # not while tracing
        work = wavefront_work(sp, batch=B, m=M, n=N,
                              segment_width=segment_width,
                              features=features)
        if work is not None:
            count_wavefront(work)
    return out


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _normalize_padded(x, *, n: int, interpret: bool):
    B, L = x.shape
    b_pad = ceil_to(B, SUBLANES)
    l_pad = ceil_to(L, LANES)
    xp = jnp.pad(x, ((0, b_pad - B), (0, l_pad - L)))
    xp = xp.reshape(-1, SUBLANES, l_pad)
    out = normalizer_pallas(xp, n=n, interpret=interpret)
    return out.reshape(b_pad, l_pad)[:B, :L]


def normalize(x: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """Batch z-normalization via the Pallas kernel, over time. x: (B, L)
    -> (B, L); a multivariate (B, L, D) batch normalizes each feature
    over time, as D x B univariate rows of the same kernel.
    interpret: None = auto (compiled on TPU, interpreted elsewhere)."""
    x = jnp.asarray(x)
    interp = _resolve_interpret(interpret)
    if x.ndim == 3:
        B, L, D = x.shape
        rows = x.transpose(0, 2, 1).reshape(B * D, L)
        out = _normalize_padded(rows, n=L, interpret=interp)
        return out.reshape(B, D, L).transpose(0, 2, 1)
    return _normalize_padded(x, n=x.shape[1], interpret=interp)
