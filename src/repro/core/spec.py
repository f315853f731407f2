"""DPSpec — ONE declarative recurrence specification shared by every
sDTW backend.

The paper's contribution is a single DP recurrence

    D[i, j] = cost(q[i], r[j]) + reduce(D[i-1, j], D[i, j-1], D[i-1, j-1])

executed through progressively lower-level machinery (scan oracle →
anti-diagonal XLA engine → Pallas wavefront kernel → mesh pipeline).
Before this module each implementation hard-coded squared-Euclidean
cost, hard-min and a private infinity sentinel; ``DPSpec`` makes the
recurrence a *value* that every backend consumes:

  * ``distance``   — the per-cell cost: ``sqeuclidean`` (the paper's),
                     ``abs`` (Manhattan / L1), or ``cosine``;
  * ``reduction``  — ``hardmin`` (the paper), or ``softmin`` with
                     temperature ``gamma`` (Cuturi & Blondel 2017),
                     which makes the whole map differentiable;
  * ``band``       — optional Sakoe–Chiba radius: cell (i, j) is valid
                     iff ``|i - j| <= band`` on the (query-row,
                     reference-column) grid.  ``None`` disables banding
                     (and compiles the exact same graph as before the
                     spec existed).  Note the mask is *static* in (i, j),
                     so for subsequence matching it constrains how far
                     from the main diagonal an alignment may wander —
                     useful when queries are anchored near a known
                     reference offset; ``band >= M + N`` is equivalent
                     to unbanded;
  * ``accum_dtype``— the accumulator dtype of the DP sweep.

Backends declare which corners of this space they support via
``repro.backends.registry.Capabilities``; ``repro.core.api.sdtw``
resolves a spec, asks the registry for a capable backend, and executes.

The helpers here (``cell_cost``, ``reduce3``, ``cell_update``,
``band_valid``) are written so that the default spec reproduces each
backend's pre-spec computation graph bit-for-bit: hard-min keeps the
``min(min(left, up), upleft)`` operand order, squared-Euclidean keeps
the ``(q - r)**2`` form, and band/softmin branches are *Python-level*
(spec fields are static under ``jax.jit``), so an unbanded hard-min
spec adds zero ops to the sweep.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

DISTANCES = ("sqeuclidean", "abs", "cosine")
REDUCTIONS = ("hardmin", "softmin")
FAMILIES = ("sdtw", "twed", "erp", "local")

# ----------------------------------------------------------- sentinels
# The one home of every "effectively infinite" constant in the repo.
# Each value is chosen for the dtype and differentiation regime of the
# path that uses it:
#
INF = jnp.inf
#   Hard-min accumulators (engine, ref, distributed) in f32/f64: +inf is
#   the true identity of ``min`` and these paths are never
#   differentiated, so inf - inf NaNs cannot reach a gradient; masked
#   cells are overwritten with ``where`` before any read.
#
SOFT_BIG = 1e30
#   Soft-min accumulators: must stay FINITE so that
#   ``exp(-SOFT_BIG / gamma)`` underflows to exactly 0.0 without an
#   ``inf - inf = NaN`` appearing inside the logsumexp *gradient*.
#   1e30 leaves ~8 orders of magnitude of headroom below the f32 max
#   (~3.4e38), so ``cost + SOFT_BIG`` and ``SOFT_BIG / gamma`` for any
#   sane gamma cannot overflow to inf.
#
KERNEL_BIG = 3.0e38
#   Pallas wavefront kernel (hard-min, configurable compute dtype):
#   the largest round value representable in BOTH f32 and bf16 (bf16
#   max ≈ 3.39e38).  The kernel casts its carries to ``compute_dtype``,
#   so the sentinel must survive an f32 -> bf16 round trip without
#   becoming inf (inf arithmetic differs between interpret and compiled
#   modes).  Kept as a Python float so tracing never captures a traced
#   constant.
#
PAD_VALUE = 1.0e6
#   Reference PADDING columns in the kernel layout: ``(q - 1e6)**2 =
#   1e12`` dominates any real z-normalized cost yet stays far from f32
#   overflow even accumulated over long paths; ``|q - 1e6| ≈ 1e6`` does
#   the same for the ``abs`` distance.  NOT safe for ``cosine`` — the
#   cosine cost of a huge pad value is still O(1) — which is one reason
#   the kernel backend declines cosine (see repro.backends.builtin).
#
# ------------------------------------------------ soft-min exp / log
# The soft-min's float32 exp and log, accurate to a few ulps on a TPU
# as elsewhere.  A TPU v5e's own f32 log is off by up to ~1e-4 (absolute, on
# [1, 3]) and its exp runs ~1e-6 low on average; summed along a path of
# hundreds of cells that moved soft-min costs by ~1e-5 relative and the
# fused backward's gradients by ~5e-4.  Other backends and dtypes use
# jnp's own, which are accurate there.
_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125          # few mantissa bits: n * hi is exact
_LN2_LO = 1.4286068202862268e-06
_EXP_TAYLOR = (1 / 5040, 1 / 720, 1 / 120, 1 / 24, 1 / 6, 1 / 2, 1.0, 1.0)


def _tpu_math() -> bool:
    """Trace for a TPU?  Decided at trace time, like the Pallas
    ``interpret`` default, so a kernel body and the XLA programs around
    it agree."""
    return jax.default_backend() == "tpu"


def soft_exp(x):
    """exp(x): on a TPU in float32, :func:`_exp_f32`; else jnp's own."""
    if x.dtype == jnp.float32 and _tpu_math():
        return _exp_f32(x)
    return jnp.exp(x)


def soft_log(s):
    """log(s) for s > 0: on a TPU in float32, :func:`_log_f32`; else
    jnp's own."""
    if s.dtype == jnp.float32 and _tpu_math():
        return _log_f32(s)
    return jnp.log(s)


def _exp_f32(x):
    """exp(x): Cody–Waite reduction to r in [-ln2/2, ln2/2], a degree-7
    Taylor polynomial for exp(r), and 2**n built in the exponent bits.
    Underflows to exactly 0 below -87 (so exp(-inf) == 0)."""
    xc = jnp.clip(x, -87.0, 88.0)
    n = jnp.floor(xc * _LOG2E + 0.5)
    r = (xc - n * _LN2_HI) - n * _LN2_LO
    p = jnp.full_like(r, _EXP_TAYLOR[0])
    for c in _EXP_TAYLOR[1:]:
        p = p * r + c
    scale = lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    return jnp.where(x < -87.0, jnp.zeros_like(p), p * scale)


def _log_f32(s):
    """log(s) for s > 0: the backend's log refined by one Newton step on
    :func:`_exp_f32` (the step squares the starting error)."""
    y = jnp.log(s)
    return y + (s * _exp_f32(-y) - 1.0)


NO_WINDOW = -1
#   The int32 argmin / start-pointer sentinel: "no window found".  A
#   start (or end) index of -1 means no in-band alignment ever reached
#   the bottom row — it survives the streaming argmin folds untouched
#   because every real reference column is >= 0.  Shared by the engine
#   and ref start lanes, the Pallas kernel's int32 carry channel
#   (``repro.kernels.wavefront``), the backtrack oracle
#   (``repro.align.oracle``) and the search service, so "no window"
#   compares equal across every layer.


# ---------------------------------------------------------- recurrences
@dataclasses.dataclass(frozen=True)
class RecurrenceSpec:
    """The declarative shape of one banded-DP recurrence family.

    ``repro.dp``'s algebra axis: every family the executors serve is a
    frozen value of this class, describing WHICH recurrence sweeps —
    boundary conditions, per-predecessor transition costs, objective —
    while ``DPSpec`` keeps the orthogonal knobs (distance, reduction,
    band, dtype) and the family's numeric parameters.  The executors
    (``core.ref``, ``core.engine``, ``kernels.wavefront``) branch on
    these *static* flags, never on family names, so a new family is a
    new table entry plus a ``DPSpec.transition3`` case — not a new
    sweep.

    Fields:

    * ``objective``  — ``"min"`` (distances: sdtw/twed/erp) or ``"max"``
      (similarities: local alignment).  Max-objective families run
      NEGATED in min-space — every executor still minimizes, and the
      reported cost is the negated similarity score — so one fold
      machinery serves both;
    * ``free_start`` / ``free_end`` — subsequence boundary freedom: a
      free start zeroes virtual row -1, a free end folds the bottom row
      instead of the corner;
    * ``local_floor`` — Smith–Waterman restart: the cell value is
      floored at 0 (in min-space: ``min(value, 0)``) and the fold runs
      over EVERY valid cell, not a row or corner;
    * ``uses_transitions`` — the recurrence adds per-predecessor
      transition costs (``DPSpec.transition3``) instead of one local
      cell cost;
    * ``needs_shifted`` — cells read the PREVIOUS sample of each series
      (TWED's ``d(q_i, q_{i-1})`` / ``d(r_j, r_{j-1})`` terms), so the
      kernel plan carries a shifted reference layout;
    * ``needs_prefix`` — boundary rows/columns are gap-cost prefix sums
      (ERP), carried as extra swizzled operands.
    """

    name: str
    objective: str = "min"
    free_start: bool = False
    free_end: bool = False
    local_floor: bool = False
    uses_transitions: bool = False
    needs_shifted: bool = False
    needs_prefix: bool = False

    @property
    def fold(self) -> str:
        """Where the answer lives: ``row`` (free end: fold the bottom
        row), ``cells`` (local floor: fold every valid cell) or
        ``corner`` (global: the single cell (m-1, n-1))."""
        if self.local_floor:
            return "cells"
        return "row" if self.free_end else "corner"


FAMILY_RECURRENCES = {
    "sdtw": RecurrenceSpec(name="sdtw", free_start=True, free_end=True),
    "twed": RecurrenceSpec(name="twed", uses_transitions=True,
                           needs_shifted=True),
    "erp": RecurrenceSpec(name="erp", uses_transitions=True,
                          needs_prefix=True),
    "local": RecurrenceSpec(name="local", objective="max",
                            free_start=True, free_end=True,
                            local_floor=True, uses_transitions=True),
}


def recurrence(family: str) -> RecurrenceSpec:
    """The frozen :class:`RecurrenceSpec` of a family name."""
    try:
        return FAMILY_RECURRENCES[family]
    except KeyError:
        raise ValueError(f"unknown recurrence family {family!r}; "
                         f"choose from {FAMILIES}") from None


@dataclasses.dataclass(frozen=True)
class DPSpec:
    """Frozen, hashable recurrence spec — safe as a jit static argument."""

    distance: str = "sqeuclidean"
    reduction: str = "hardmin"
    gamma: float = 1.0           # softmin temperature (static; > 0)
    band: int | None = None      # Sakoe–Chiba radius, None = unbanded
    accum_dtype: str = "float32"
    # ------------------------------------------------ recurrence family
    family: str = "sdtw"         # one of FAMILIES
    nu: float = 1.0              # TWED stiffness (>= 0)
    lam: float = 1.0             # TWED deletion penalty (>= 0)
    gap: float = 0.0             # ERP gap value g (cost of deleting x
    #                              is d(x, g))
    gap_penalty: float = 1.0     # local alignment gap penalty (> 0)
    match_reward: float = 1.0    # local alignment match reward mu (> 0):
    #                              cell similarity is mu - d(q_i, r_j)

    def __post_init__(self):
        if self.distance not in DISTANCES:
            raise ValueError(f"unknown distance {self.distance!r}; "
                             f"choose from {DISTANCES}")
        if self.reduction not in REDUCTIONS:
            raise ValueError(f"unknown reduction {self.reduction!r}; "
                             f"choose from {REDUCTIONS}")
        if self.reduction == "softmin" and not self.gamma > 0:
            raise ValueError(f"softmin needs gamma > 0, got {self.gamma}")
        if self.band is not None and self.band < 0:
            raise ValueError(f"band must be >= 0 or None, got {self.band}")
        if self.family not in FAMILIES:
            raise ValueError(f"unknown recurrence family {self.family!r}; "
                             f"choose from {FAMILIES}")
        if self.family == "twed" and (self.nu < 0 or self.lam < 0):
            raise ValueError(f"twed needs nu >= 0 and lam >= 0, got "
                             f"nu={self.nu}, lam={self.lam}")
        if self.family == "local":
            if not self.gap_penalty > 0:
                raise ValueError(f"local alignment needs gap_penalty > 0, "
                                 f"got {self.gap_penalty}")
            if not self.match_reward > 0:
                raise ValueError(f"local alignment needs match_reward > 0, "
                                 f"got {self.match_reward}")
        jnp.dtype(self.accum_dtype)   # fail fast on bogus dtype strings

    # ------------------------------------------------------- properties
    @property
    def accum(self):
        return jnp.dtype(self.accum_dtype)

    @property
    def soft(self) -> bool:
        return self.reduction == "softmin"

    @property
    def differentiable(self) -> bool:
        """Soft-min specs yield NaN-free gradients end to end."""
        return self.soft

    @property
    def big(self) -> float:
        """The masked/initial-cell sentinel for this reduction (see the
        sentinel notes above)."""
        return SOFT_BIG if self.soft else INF

    @property
    def recurrence(self) -> RecurrenceSpec:
        """The frozen :class:`RecurrenceSpec` of this spec's family."""
        return FAMILY_RECURRENCES[self.family]

    def family_describe(self) -> str:
        """The family component of :meth:`describe` — the family name
        plus its live numeric parameters (``sdtw`` has none)."""
        if self.family == "twed":
            return f"twed(nu={self.nu:g},lam={self.lam:g})"
        if self.family == "erp":
            return f"erp(gap={self.gap:g})"
        if self.family == "local":
            return (f"local(gap={self.gap_penalty:g},"
                    f"match={self.match_reward:g})")
        return "sdtw"

    def describe(self) -> str:
        # the default family is deliberately silent so every pre-family
        # sdtw description (tune cache keys, logs, test ids) is
        # byte-identical to what earlier releases produced
        parts = [self.distance, self.reduction]
        if self.family != "sdtw":
            parts.insert(0, self.family_describe())
        if self.soft:
            parts.append(f"gamma={self.gamma:g}")
        if self.band is not None:
            parts.append(f"band={self.band}")
        return "/".join(parts)

    # ---------------------------------------------------- cell helpers
    def cell_cost(self, q, r):
        """Elementwise local cost. Broadcasts like ``q - r``."""
        if self.distance == "sqeuclidean":
            return (q - r) ** 2
        if self.distance == "abs":
            return jnp.abs(q - r)
        # cosine on scalar samples: 1 - qr/(|q||r|) ∈ [0, 2] (0 when the
        # signs agree). Degenerate but well-defined; eps guards 0-values.
        return 1.0 - (q * r) / (jnp.abs(q) * jnp.abs(r) + 1e-8)

    def feature_cost(self, q, r):
        """Local cost of feature vectors, whose last axis is the
        feature axis: the per-feature :meth:`cell_cost` terms added in
        feature order, so every executor that adds them the same way
        agrees bit for bit.  Broadcasts like ``q - r`` over the other
        axes.  Cosine is a cost of scalar samples here and is not
        defined for vectors."""
        if self.distance == "cosine":
            raise ValueError("distance 'cosine' has no multivariate "
                             "form here: use sqeuclidean or abs for "
                             "(B, M, D) inputs")
        total = self.cell_cost(q[..., 0], r[..., 0])
        for d in range(1, q.shape[-1]):
            total = total + self.cell_cost(q[..., d], r[..., d])
        return total

    def reduce3(self, left, up, upleft):
        """The 3-way predecessor reduction. Hard-min keeps the operand
        order min(min(left, up), upleft) every pre-spec backend used.

        Soft-min is the logsumexp fold ``-γ·logsumexp(-x/γ)`` written
        in min-shifted form: shifting by the hard min makes every
        exponent <= 0 *by construction*, so no intermediate can
        overflow and no ``isfinite`` guard is needed — unlike
        ``jax.nn.logsumexp``, whose internal max-guard ``where`` can
        manufacture NaNs under XLA fusion inside Pallas kernel bodies
        (observed on the interpret path; the de-optimized graph was
        clean).  Mathematically identical to the stacked logsumexp, and
        the shift contributes zero gradient (∂f/∂shift ≡ 0), so the
        fold stays NaN-free under ``jax.grad`` as well.
        """
        if not self.soft:
            return jnp.minimum(jnp.minimum(left, up), upleft)
        mn = jnp.minimum(jnp.minimum(left, up), upleft)
        s = (soft_exp(-(left - mn) / self.gamma)
             + soft_exp(-(up - mn) / self.gamma)
             + soft_exp(-(upleft - mn) / self.gamma))
        return mn - self.gamma * soft_log(s)

    def cell_update(self, cost, left, up, upleft, *, free_start=None):
        """One DP cell: ``cost + reduce3(...)``.

        ``free_start`` (bool mask, True where the cell sits in query row
        0) implements the subsequence boundary ``D[-1, j] = 0``: the
        reduced predecessor is replaced by exactly 0 there, for hard and
        soft reductions alike.
        """
        prev = self.reduce3(left, up, upleft)
        if free_start is not None:
            prev = jnp.where(free_start, jnp.zeros_like(prev), prev)
        return cost + prev

    def reduce2(self, a, b):
        """Two-way companion of :meth:`reduce3` — same hard/soft split,
        same min-shifted logsumexp form.  The local-alignment restart
        floor ``min(value, 0)`` runs through this so the soft local
        objective stays differentiable."""
        if not self.soft:
            return jnp.minimum(a, b)
        mn = jnp.minimum(a, b)
        s = (soft_exp(-(a - mn) / self.gamma)
             + soft_exp(-(b - mn) / self.gamma))
        return mn - self.gamma * soft_log(s)

    def transition3(self, qv, rv, *, q_prev=None, r_prev=None,
                    i=None, j=None):
        """Per-predecessor transition costs ``(t_left, t_up, t_diag)``
        of the non-sdtw families, added to the (left, up, upleft)
        predecessors before :meth:`reduce3`.

        * TWED (Marteau 2009, anti-diagonal form of arxiv 2007.16135),
          with the ``q[-1] = r[-1] = 0`` padding convention:
          delete-in-r (left) pays ``d(r_j, r_{j-1}) + nu + lam``,
          delete-in-q (up) pays ``d(q_i, q_{i-1}) + nu + lam``, and
          match (diag) pays ``d(q_i, r_j) + d(q_{i-1}, r_{j-1})
          + 2·nu·|i - j|``;
        * ERP (Chen & Ng 2004): gap moves pay the distance to the gap
          value ``g`` (``d(r_j, g)`` / ``d(q_i, g)``), the diagonal
          pays ``d(q_i, r_j)``;
        * local (Smith–Waterman in min-space): gap moves pay
          ``gap_penalty``, the diagonal pays ``d(q_i, r_j) -
          match_reward`` (the NEGATED similarity score).

        Every executor calls this with the same operand order, so f32
        sweeps agree bit-for-bit across ref / engine / kernel.
        """
        if self.family == "twed":
            nl = self.nu + self.lam
            t_left = self.cell_cost(rv, r_prev) + nl
            t_up = self.cell_cost(qv, q_prev) + nl
            t_diag = (self.cell_cost(qv, rv)
                      + self.cell_cost(q_prev, r_prev)
                      + (2.0 * self.nu) * jnp.abs(i - j))
            return t_left, t_up, t_diag
        if self.family == "erp":
            return (self.cell_cost(rv, self.gap),
                    self.cell_cost(qv, self.gap),
                    self.cell_cost(qv, rv))
        if self.family == "local":
            gp = self.gap_penalty
            return gp, gp, self.cell_cost(qv, rv) - self.match_reward
        raise ValueError(f"family {self.family!r} has no transition "
                         f"costs (sdtw uses cell_update)")

    def family_cell(self, qv, rv, left, up, upleft, *, i, j,
                    is_row0, is_col0, q_prev=None, r_prev=None,
                    top_boundary=None, left_boundary=None, big=None):
        """One non-sdtw DP cell — the single definition the rowscan
        ref, the anti-diagonal engine AND the Pallas kernel all execute,
        so their f32 grids agree bit-for-bit.

        ``left``/``up``/``upleft`` are the raw neighbor reads (garbage
        on grid edges — e.g. wrap-around rolls); the family's boundary
        conditions are injected HERE via ``is_row0``/``is_col0`` masks:

        * TWED (global): virtual row/col -1 are unreachable (``big``)
          except the origin corner ``D[-1,-1] = 0``;
        * ERP (global): virtual row -1 holds the reference gap-cost
          prefix ``top_boundary[j] = Σ_{k<=j} d(r_k, g)`` and virtual
          col -1 the query prefix ``left_boundary[i]``; the diagonal
          boundary is recovered by peeling one gap cost off the prefix
          (``B[j-1] = B[j] - d(r_j, g)`` — computed in exactly this
          form by every executor AND the oracle, so f32 rounding
          agrees);
        * local: virtual boundaries are 0 (a fresh alignment may start
          anywhere) and the restart floor ``reduce2(value, 0)`` caps
          the cell.

        ``big`` overrides the masked-cell sentinel (the kernel passes
        its finite ``KERNEL_BIG``).  Band masking stays with the
        caller.
        """
        if big is None:
            big = self.big
        t_left, t_up, t_diag = self.transition3(
            qv, rv, q_prev=q_prev, r_prev=r_prev, i=i, j=j)
        if self.family == "twed":
            up_b = jnp.where(is_row0, big, up)
            left_b = jnp.where(is_col0, big, left)
            upleft_b = jnp.where(
                is_row0 | is_col0,
                jnp.where(is_row0 & is_col0, jnp.zeros_like(upleft), big),
                upleft)
        elif self.family == "erp":
            up_b = jnp.where(is_row0, top_boundary, up)
            left_b = jnp.where(is_col0, left_boundary, left)
            upleft_b = jnp.where(
                is_row0, top_boundary - self.cell_cost(rv, self.gap),
                jnp.where(is_col0,
                          left_boundary - self.cell_cost(qv, self.gap),
                          upleft))
        elif self.family == "local":
            up_b = jnp.where(is_row0, jnp.zeros_like(up), up)
            left_b = jnp.where(is_col0, jnp.zeros_like(left), left)
            upleft_b = jnp.where(is_row0 | is_col0,
                                 jnp.zeros_like(upleft), upleft)
        else:
            raise ValueError("family_cell serves non-sdtw families only; "
                             "sdtw cells go through cell_update")
        val = self.reduce3(left_b + t_left, up_b + t_up, upleft_b + t_diag)
        if self.family == "local":
            val = self.reduce2(val, jnp.zeros_like(val))
        return val

    def band_valid(self, i, j):
        """Sakoe–Chiba validity mask ``|i - j| <= band`` (None when
        unbanded, so callers can skip the op entirely)."""
        if self.band is None:
            return None
        return jnp.abs(i - j) <= self.band

    def start3(self, left, up, upleft, s_left, s_up, s_upleft):
        """Start-pointer propagation companion of :meth:`reduce3`:
        the start index of the predecessor the hard-min picks.

        The tie-break mirrors ``min(min(left, up), upleft)`` exactly —
        on a tie ``left`` beats ``up`` and the inner min beats
        ``upleft`` (strict ``<`` flips the winner) — so every backend
        and the full-matrix backtrack oracle (``repro.align.oracle``)
        agree on WHICH optimal path they report, not just on its cost.
        Hard-min only: soft-min windows are ill-defined (use
        ``repro.align.soft`` for the expected alignment instead).
        """
        if self.soft:
            raise ValueError("start3 is hard-min only: soft-min specs "
                             "have no argmin path (see repro.align.soft)")
        s = jnp.where(up < left, s_up, s_left)
        s = jnp.where(upleft < jnp.minimum(left, up), s_upleft, s)
        return s


DEFAULT_SPEC = DPSpec()


def resolve_spec(spec: DPSpec | None = None, *, distance: str | None = None,
                 reduction: str | None = None, gamma: float | None = None,
                 band: int | None = None,
                 accum_dtype: str | None = None,
                 family: str | None = None, nu: float | None = None,
                 lam: float | None = None, gap: float | None = None,
                 gap_penalty: float | None = None,
                 match_reward: float | None = None) -> DPSpec:
    """Merge convenience kwargs over an optional base spec.

    ``resolve_spec()`` is the default spec; kwargs override individual
    fields (``gamma`` implies ``reduction="softmin"`` unless reduction
    is given explicitly).
    """
    base = spec if spec is not None else DEFAULT_SPEC
    if gamma is not None and reduction is None and not base.soft:
        reduction = "softmin"
    updates = {k: v for k, v in [("distance", distance),
                                 ("reduction", reduction),
                                 ("gamma", gamma), ("band", band),
                                 ("accum_dtype", accum_dtype),
                                 ("family", family), ("nu", nu),
                                 ("lam", lam), ("gap", gap),
                                 ("gap_penalty", gap_penalty),
                                 ("match_reward", match_reward)]
               if v is not None}
    return dataclasses.replace(base, **updates) if updates else base


# --------------------------------------------------- shared validation
# One home for the input checks that used to be duplicated between
# ``core.api.sdtw``, ``core.engine`` and ``search.SearchService``.

def validate_batch_inputs(queries, reference, *, segment_width=None):
    """The public batch contract: univariate queries (B, M) against a
    reference (N,), or multivariate queries (B, M, D) against a
    reference (N, D) with the same D features, shared across the
    batch, non-empty everywhere.  (Per-query (B, N) references are a
    backend capability — engine/ref accept them when called directly,
    as the search service's pair sweeps do — but the public ``sdtw``
    contract shares one reference.)"""
    if queries.ndim not in (2, 3):
        raise ValueError(
            f"queries must be 2-D (batch, length) or 3-D (batch, length, "
            f"features), got shape {queries.shape}")
    if reference.ndim != queries.ndim - 1:
        want = ("1-D (length,)" if queries.ndim == 2
                else "2-D (length, features)")
        raise ValueError(
            f"reference must be {want} for {queries.ndim}-D queries, got "
            f"shape {reference.shape}")
    if queries.shape[0] == 0:
        raise ValueError("empty query batch (queries.shape[0] == 0)")
    if queries.shape[1] == 0:
        raise ValueError("zero-length queries (queries.shape[1] == 0)")
    if reference.shape[0] == 0:
        raise ValueError("empty reference (reference.shape[0] == 0)")
    if queries.ndim == 3:
        if queries.shape[2] == 0:
            raise ValueError("zero features (queries.shape[2] == 0)")
        if reference.shape[1] != queries.shape[2]:
            raise ValueError(
                f"queries have {queries.shape[2]} features, the reference "
                f"{reference.shape[1]}")
    if segment_width is not None and segment_width < 1:
        raise ValueError(f"segment_width must be >= 1, got {segment_width}")


def univariate(queries, reference):
    """Univariate views of a batch with one feature: (B, M, 1) and
    (N, 1) become (B, M) and (N,), so that one feature runs exactly
    the univariate path.  Anything else is returned as it is."""
    if queries.ndim == 3 and queries.shape[2] == 1:
        return queries[..., 0], reference[..., 0]
    return queries, reference


def require_univariate(series, what: str) -> None:
    """The search and serving contract: 1-D series only.  Multivariate
    (length, features) series run on ``repro.sdtw`` and
    ``repro.Aligner``; search and serving are univariate."""
    if series.ndim != 1:
        raise ValueError(
            f"{what} must be 1-D, got shape {series.shape}"
            + (": multivariate (length, features) series run on "
               "repro.sdtw and repro.Aligner only; search and serving "
               "are univariate" if series.ndim == 2 else ""))


def validate_query_list(queries) -> None:
    """The search-service contract: a non-empty list of 1-D queries."""
    if len(queries) == 0:
        raise ValueError("empty query batch")
    for q in queries:
        require_univariate(q, "each query")
