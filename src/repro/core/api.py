"""Public sDTW API — ONE front door.

The paper's end-to-end flow (§5):

    normalize(reference); normalize(batch of queries); runSDTW(batch)

is a single request/result call here:

    result = repro.sdtw(queries, reference, outputs=("cost", "end"))
    result.cost, result.end                     # requested fields
    result.start is None                        # unrequested -> None

``outputs`` may name any of ``cost / end / start / path /
soft_alignment`` (``repro.core.result.ALL_OUTPUTS``); the return value
is a typed :class:`~repro.core.result.SDTWResult` pytree.  The
recurrence is a declarative ``DPSpec`` (distance × reduction × band ×
accum dtype) and the execution backend is looked up in
``repro.backends.registry``, which validates the spec AND the requested
outputs against the backend's declared Capabilities:

  * ``"ref"``         — trusted scan oracle (slow, for validation)
  * ``"engine"``      — anti-diagonal XLA engine (default; hard+soft)
  * ``"kernel"``      — Pallas TPU wavefront kernel (auto-interpreted
                        off-TPU; hard+soft, non-cosine)
  * ``"quantized"``   — uint8 codebook sDTW (approximate; paper §8)
  * ``"distributed"`` — shard_map pipeline (needs options={"mesh": ...})
  * ``"soft"``        — alias: engine with reduction="softmin"

Asking an incapable combination fails loudly ("backend 'quantized'
does not support output(s) ['start'] ...: use one of ['engine', ...]")
instead of silently computing the wrong thing; ``backend=None`` lets
the registry pick the first capable backend for the spec + outputs.

The sweep-level outputs (cost, end, start) all come from a SINGLE
fused sweep — requesting windows never runs a second pass after a cost
pass.  ``path`` is derived above the sweep (Hirschberg traceback over
the matched window).  ``soft_alignment`` is ``jax.grad`` through the
cost-matrix engine sweep — except on the kernel backend, where it
comes from the fused forward+reverse wavefront pair
(``repro.kernels.backward``) in the same dispatch as cost/end.

Serving many batches against one reference?  Use
:class:`repro.Aligner` (``repro.core.session``) — the precompiled
session form of this call: the reference is normalized once, kernel
layouts are cached, and jitted executables are memoized per
(batch shape, outputs) so warm calls are dispatch-only.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.backends import registry
from repro.core.normalize import normalize_batch, normalize_reference
from repro.core.result import (ALL_OUTPUTS, DEFAULT_OUTPUTS,  # noqa: F401
                               SDTWResult, normalize_outputs,
                               sweep_outputs)
from repro.core.spec import (DPSpec, resolve_spec, univariate,
                             validate_batch_inputs)


def _derive_outputs(res: SDTWResult, req: frozenset, queries, reference,
                    spec: DPSpec) -> SDTWResult:
    """Fill the above-the-sweep result fields (``path`` /
    ``soft_alignment``) from already-normalized operands.

    Shared by the one-shot front door and ``Aligner`` sessions: the
    sweep-level fields (cost/end/start) must already be present on
    ``res`` (one fused sweep), paths are recovered per query by the
    Hirschberg traceback pinned to the matched window, and the expected
    alignment runs ``jax.grad`` through the cost-matrix engine sweep.
    """
    if "path" in req:
        from repro.align.traceback import warping_path
        # (np.asarray first: asking jax for a float64 view would warn
        # and truncate under the default x64-disabled config)
        q64 = np.asarray(queries).astype(np.float64)
        r64 = np.asarray(reference).astype(np.float64)
        paths = [
            # a NO_WINDOW start means no in-band alignment exists (a
            # band blocked the whole bottom row): no path either
            (None if int(s) < 0 else
             warping_path(q64[b], r64, spec=spec, normalize=False,
                          window=(int(s), int(e))))
            for b, (s, e) in enumerate(zip(np.asarray(res.start),
                                           np.asarray(res.end)))]
        res = res.replace(path=paths)
    if "soft_alignment" in req and res.soft_alignment is None:
        # the kernel backend's fused dispatch already filled this in;
        # everything else differentiates the engine's cost matrix
        from repro.align.soft import _expected_alignment_jit, cost_matrix
        C = cost_matrix(queries, reference, spec).astype(spec.accum)
        res = res.replace(
            soft_alignment=_expected_alignment_jit(C, spec=spec))
    return res


def _auto_width(backend_impl, spec: DPSpec, req: frozenset, reference,
                workload: tuple, *, pinned: bool,
                interpret: bool | None):
    """Resolve ``segment_width="auto"`` through ``repro.tune``.

    Returns ``(width, backend)``: the tuned width, plus (when the
    caller did NOT pin a backend) the measured winner between kernel
    and engine — a cold call pays the one-time tuning trials, a warm
    cache answers with zero measurements.  A pinned non-kernel backend
    ignores width anyway, so "auto" resolves to the default with zero
    trials; a verdict never overrides capability checks (the swap only
    happens when the winner supports the request).
    """
    from repro.kernels.ops import DEFAULT_SEGMENT_WIDTH
    if not (req - {"soft_alignment"}):      # no backend sweep at all
        return DEFAULT_SEGMENT_WIDTH, backend_impl
    if pinned and backend_impl.name != "kernel":
        return DEFAULT_SEGMENT_WIDTH, backend_impl
    if not pinned and backend_impl.name not in ("kernel", "engine"):
        return DEFAULT_SEGMENT_WIDTH, backend_impl
    from repro import tune
    m, n, batch = workload
    res = tune.autotune(np.asarray(reference), m=m, batch=batch,
                        spec=spec, outputs=sweep_outputs(req),
                        backends=("kernel",) if pinned else None,
                        interpret=interpret)
    if (not pinned and res.backend != backend_impl.name
            and (res.from_cache or res.trials > 0)
            and registry.supports(res.backend, spec, outputs=req)):
        backend_impl = registry.get(res.backend)
    return res.segment_width, backend_impl


def sdtw(queries, reference, *,
         outputs=DEFAULT_OUTPUTS,
         normalize: bool = True,
         backend: str | None = None,
         spec: DPSpec | None = None,
         distance: str | None = None,
         reduction: str | None = None,
         gamma: float | None = None,
         band: int | None = None,
         family: str | None = None,
         nu: float | None = None,
         lam: float | None = None,
         gap: float | None = None,
         gap_penalty: float | None = None,
         match_reward: float | None = None,
         segment_width: int | str = 8,
         interpret: bool | None = None,
         options: dict | None = None) -> SDTWResult:
    """Align a batch of queries against one reference.

    queries: (B, M); reference: (N,) — or multivariate queries
    (B, M, D) against a reference (N, D) of the same D features, whose
    cell cost adds the per-feature costs (sdtw family; the backends
    that take them, and which outputs, are in the registry's
    ``multivariate_outputs``).  One feature, (B, M, 1) against (N, 1),
    runs exactly the univariate path.  Returns an
    :class:`~repro.core.result.SDTWResult` carrying exactly the
    requested ``outputs`` (everything else ``None``):

      * ``cost`` (B,)            — best subsequence alignment costs;
      * ``end`` (B,) int32       — where each best alignment ends;
      * ``start`` (B,) int32     — where it starts (hard-min specs on
                                   window-capable backends; same sweep);
      * ``path``                 — per-query (P, 2) warping paths
                                   (hard-min specs);
      * ``soft_alignment`` (B, M, N) — expected alignments (soft-min
                                   specs).

    Mirrors the paper's pipeline: optional z-normalization of both
    inputs over time, feature by feature for multivariate inputs
    (§5.1), then the batched subsequence-DTW sweep (§5.2) under
    the resolved spec.  ``spec`` carries the recurrence; the
    ``distance`` / ``reduction`` / ``gamma`` / ``band`` kwargs are
    per-call overrides of its fields (``gamma`` alone implies
    ``reduction="softmin"``).  ``family`` picks the recurrence family
    (``repro.dp``: ``"sdtw"`` default / ``"twed"`` / ``"erp"`` /
    ``"local"``) with its parameters ``nu``/``lam`` (twed), ``gap``
    (erp), ``gap_penalty``/``match_reward`` (local); plain sdtw calls
    are byte-identical to before the family axis existed.  ``backend=None`` (the default) asks the
    registry for the first backend capable of the spec AND the
    requested outputs; naming an incapable backend raises the
    registry's loud who-can-instead error.  ``interpret=None``
    auto-selects the Pallas mode from ``jax.default_backend()``.
    ``segment_width="auto"`` asks ``repro.tune`` for the measured
    fastest plan for this (machine, spec, shapes, outputs) workload —
    tuned once, then answered from the persistent cache (see the
    README "Autotuning" section); results are bit-identical to any
    pinned width.  ``options`` passes backend extras (e.g.
    ``{"mesh": ...}`` for ``backend="distributed"``).
    """
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    auto_width = isinstance(segment_width, str)
    if auto_width and segment_width != "auto":
        raise ValueError(f"segment_width must be an int >= 1 or 'auto', "
                         f"got {segment_width!r}")
    validate_batch_inputs(queries, reference,
                          segment_width=None if auto_width
                          else segment_width)
    queries, reference = univariate(queries, reference)
    features = queries.shape[2] if queries.ndim == 3 else 1
    if auto_width and features > 1:
        raise ValueError("segment_width='auto' tunes univariate "
                         "workloads: pin a width for multivariate inputs")
    resolved = resolve_spec(spec, distance=distance, reduction=reduction,
                            gamma=gamma, band=band, family=family,
                            nu=nu, lam=lam, gap=gap,
                            gap_penalty=gap_penalty,
                            match_reward=match_reward)
    req = normalize_outputs(outputs)
    workload = (int(queries.shape[1]), int(reference.shape[0]),
                int(queries.shape[0]))
    if backend is None:
        backend_impl, resolved = registry.select(resolved, outputs=req,
                                                 workload=workload,
                                                 features=features)
    else:
        backend_impl, resolved = registry.resolve(backend, resolved,
                                                  outputs=req,
                                                  features=features)
    if auto_width:
        segment_width, backend_impl = _auto_width(
            backend_impl, resolved, req, reference, workload,
            pinned=backend is not None, interpret=interpret)
    if normalize:
        queries = normalize_batch(queries)
        reference = normalize_reference(reference)
    fused_soft = (backend_impl.name == "kernel" and resolved.soft
                  and "soft_alignment" in req)
    if fused_soft:
        # one fused forward+reverse dispatch fills cost, end AND the
        # expected alignment — no engine cost matrix, no second sweep
        from repro.kernels.backward import soft_alignment_fused
        cost, end, E = soft_alignment_fused(
            queries, reference, spec=resolved,
            segment_width=segment_width, interpret=interpret)
        res = SDTWResult(cost=cost, end=end, soft_alignment=E)
    elif req - {"soft_alignment"}:
        plan = registry.ExecutionPlan(
            queries=queries, reference=reference,
            segment_width=segment_width, interpret=interpret,
            outputs=sweep_outputs(req), options=options)
        res = backend_impl.execute(resolved, plan)
    else:
        # a soft_alignment-only request needs no backend sweep: the
        # expected alignment is its own (differentiated) forward pass
        res = SDTWResult()
    res = _derive_outputs(res, req, queries, reference, resolved)
    return res.restrict(req)
