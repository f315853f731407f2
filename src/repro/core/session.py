"""Aligner — a precompiled sDTW session for one reference.

``repro.sdtw`` re-normalizes the reference, re-swizzles the kernel
layout, and re-enters jit dispatch machinery on every call.  That is
the right shape for one-shot use; a serving path that aligns every
incoming query batch against the same reference (the ROADMAP's
millions-of-users regime, and exactly the paper's §5 session: normalize
the reference once, then stream query batches) should pay those costs
once:

    aligner = repro.Aligner(reference, band=128)        # cold: prep
    res = aligner(queries)                              # compile once
    res = aligner(queries2)                             # warm: dispatch
    res = aligner(queries, outputs=("cost", "start", "end"))

An ``Aligner`` is constructed once per (reference, spec, backend) and

  * z-normalizes the reference ONCE at construction (queries are still
    normalized per call, inside the compiled executable);
  * caches the swizzled ``(R, w, LANES)`` kernel layout from
    ``kernels/ops.py`` prep, so the kernel backend's offline reference
    layout optimization (paper §3) is actually offline; the reference
    normalization and the swizzle run inside ``aligner.layout`` spans;
  * memoizes one compiled executable per (batch shape, dtype,
    outputs) request, traced and compiled inside the
    ``aligner.build`` span — warm calls are cache-lookup + dispatch,
    zero retraces (``Aligner.stats`` counts traces/compiles/hits; the
    tier-1 suite asserts the zero).  The reference (its kernel layout
    and the families' reference operands) is an ARGUMENT of the
    compiled call, never a constant: on the kernel backend one
    program, shared by the whole process, serves every reference of
    a shape;
  * counts the work of each wavefront kernel dispatch into the
    process-wide ``kernel.wavefront.*`` counters
    (``kernels.ops.count_wavefront``), from a work count made once per
    executable.

Each call runs inside an ``aligner.call`` span, which holds
``aligner.build`` (cold calls) and ``aligner.dispatch``.

The reference is (N,), or (N, D) for multivariate (B, M, D) query
batches of D features (an (N, 1) reference is the univariate (N,)).

Results are typed :class:`~repro.core.result.SDTWResult` pytrees, same
as ``repro.sdtw``; capability validation (spec × backend × outputs)
uses the same registry errors, raised at executable-build time.

The distributed backend is the one exception to the outer jit: its
shard_map pipeline is already built and cached per (mesh, spec,
layout) by the backend adapter, so the session just pins the
pre-normalized reference and dispatches.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.backends import registry
from repro.core.normalize import normalize_batch, normalize_reference
from repro.core.api import _derive_outputs
from repro.core.result import (DEFAULT_OUTPUTS, SDTWResult, from_sweep,
                               normalize_outputs, sweep_outputs)
from repro.core.spec import DPSpec, resolve_spec, validate_batch_inputs

log = logging.getLogger(__name__)

_TRACING = threading.local()
#   ``session``: the Aligner whose build is lowering on this thread —
#   the one a traced function body counts its trace against


def _count_trace() -> None:
    """Called from every traced executable body: a Python side effect,
    so it runs only while JAX traces, and counts the trace against the
    session whose build is lowering on this thread."""
    session = getattr(_TRACING, "session", None)
    if session is not None:
        session.stats.traces += 1
        session._metrics.inc("aligner.traces")


@functools.partial(jax.jit, static_argnames=(
    "spec", "n", "segment_width", "interpret", "sweep", "normalize"))
def _kernel_program(q, r_layout, extras_ref, *, spec, n, segment_width,
                    interpret, sweep, normalize):
    """The kernel backend's whole call: normalize the queries, pack
    them, and run the one wavefront dispatch against the reference
    layout and the family's reference operands — both arguments, so
    every session over a reference of the same shape shares one
    program."""
    from repro.kernels import ops as _ops
    _count_trace()
    if normalize:
        q = normalize_batch(q)
    q32 = q.astype(jnp.float32)
    qk = _ops.prepare_queries(q32)
    extras = tuple(extras_ref) + _ops.family_extras_query(spec, q32)
    out = _ops.sdtw_wavefront_prepped(
        qk, r_layout, batch=q.shape[0], m=q.shape[1], n=n,
        segment_width=segment_width, interpret=interpret, spec=spec,
        return_window="start" in sweep, extras=extras)
    return from_sweep(out, sweep)


@dataclasses.dataclass
class AlignerStats:
    """Session accounting — the cache-behavior contract, testable.

    ``traces`` counts executions of a traced function body (a Python
    side effect inside the jitted closure, so it only ticks while JAX
    is tracing); a warm call leaves it unchanged, and so does the
    build of a kernel program another session over a reference of the
    same shape already traced.  ``compiles`` counts
    jitted executables successfully brought to their first dispatch:
    the build traces and compiles, and the counter ticks only AFTER
    the first dispatch returns — a build or first dispatch that raises
    leaves ``compiles`` (and the executable cache) untouched, and
    eager strategies (distributed) never tick it.
    ``calls``/``cache_hits`` count dispatches; ``evictions`` counts
    executables dropped by the ``max_executables`` LRU bound.

    Every field is mirrored into the session's
    :class:`~repro.obs.MetricsRegistry` under ``aligner.*``, so
    cross-session aggregates live in ``repro.obs`` while this dataclass
    stays the per-session view.
    """
    calls: int = 0
    cache_hits: int = 0
    compiles: int = 0
    traces: int = 0
    evictions: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class _Bound:
    """A jitted function with its static arguments bound; ``lower``
    goes through the jitted function itself, so every binding of the
    same static arguments shares its compiled programs."""
    jitted: Callable
    static: dict

    def lower(self, *args):
        return self.jitted.lower(*args, **self.static)


@dataclasses.dataclass(frozen=True)
class _Executable:
    """One cached executable of a session.

    ``run`` is what a call dispatches, as ``run(queries, *args)``: the
    compiled program, or the eager strategy itself.  ``jitted`` is the
    jitted function the program was compiled from (None for eager
    strategies), kept for :meth:`Aligner.hlo_texts`.  ``args`` are the
    session's reference operands every call passes after the queries.
    ``work`` holds the :meth:`~repro.kernels.wavefront.KernelPlan.work`
    of each wavefront kernel dispatch that one call makes."""
    run: Callable
    jitted: Callable | None
    args: tuple = ()
    work: tuple = ()


class Aligner:
    """A session: one reference, one spec, one backend, many batches.

    Parameters mirror :func:`repro.sdtw`: ``spec`` (or the
    ``distance`` / ``reduction`` / ``gamma`` / ``band`` field
    overrides), ``backend`` (None auto-selects for the spec; per-call
    output requests re-validate against its capabilities), ``outputs``
    (an optional hint naming the outputs this session will serve, so
    auto-selection lands on a backend that can fulfill them),
    ``normalize`` (applied to the reference here, ONCE, and to each
    query batch inside the compiled call), ``segment_width`` /
    ``interpret`` (kernel backend), ``options`` (backend extras, e.g.
    ``{"mesh": ...}``).

    ``segment_width="auto"`` defers the width to ``repro.tune``: the
    first executable build for each (query length, batch bucket,
    outputs) key tunes (or answers from the persistent tuning cache —
    a warm machine measures nothing) and every executable dispatches
    the tuned width; results are bit-identical to any pinned width.
    ``tune_options`` forwards extras to :func:`repro.tune.autotune`
    (``budget=``, ``cache=``, ``candidates=``, ``timer=``).

    ``max_executables`` bounds the per-(batch shape, dtype, outputs)
    executable cache with an LRU: a long-lived session fed many
    distinct shapes stops growing without bound, evictions tick
    ``stats.evictions`` / the ``aligner.evictions`` counter, and an
    evicted key simply recompiles on next use.

    ``layout_cache`` shares a pre-existing swizzled-layout dict (keyed
    ``(segment_width, dtype_name)`` like ``ReferenceIndex`` entries),
    so index-backed sessions reuse the index's offline prep instead of
    re-swizzling.

    Pool-safety: the executable LRU is lock-guarded, so one session may
    be dispatched from several serve-pool worker threads concurrently
    (``repro.serve.pool``); the per-session ``stats`` counters stay
    consistent, and racing cold builds of the same key are wasteful but
    correct.
    """

    def __init__(self, reference, *, spec: DPSpec | None = None,
                 backend: str | None = None,
                 normalize: bool = True,
                 distance: str | None = None,
                 reduction: str | None = None,
                 gamma: float | None = None,
                 band: int | None = None,
                 outputs=None,
                 segment_width: int | str = 8,
                 interpret: bool | None = None,
                 options: dict | None = None,
                 layout_cache: dict | None = None,
                 max_executables: int = 64,
                 tune_options: dict | None = None,
                 metrics: obs.MetricsRegistry | None = None,
                 tracer: obs.Tracer | None = None):
        reference = jnp.asarray(reference)
        if reference.ndim not in (1, 2):
            raise ValueError(
                f"reference must be 1-D (length,) or 2-D (length, "
                f"features), got {reference.shape}")
        if reference.shape[0] == 0:
            raise ValueError("empty reference (reference.shape[0] == 0)")
        if reference.ndim == 2 and reference.shape[1] == 0:
            raise ValueError("zero features (reference.shape[1] == 0)")
        if reference.ndim == 2 and reference.shape[1] == 1:
            reference = reference[:, 0]       # one feature: univariate
        self.features = 1 if reference.ndim == 1 else int(reference.shape[1])
        if self.features > 1 and isinstance(segment_width, str):
            raise ValueError("segment_width='auto' tunes univariate "
                             "workloads: pin a width for a multivariate "
                             "reference")
        resolved = resolve_spec(spec, distance=distance,
                                reduction=reduction, gamma=gamma,
                                band=band)
        # ``outputs`` is a selection HINT: with backend=None it steers
        # auto-selection toward a backend that can fulfill the outputs
        # this session will be asked for (matching repro.sdtw's
        # auto-fallback — e.g. path requests skip window-less
        # backends).  Per-call requests still re-validate in _build.
        hint = None if outputs is None else normalize_outputs(outputs)
        if backend is None:
            self.backend, self.spec = registry.select(
                resolved, outputs=hint, features=self.features)
        else:
            self.backend, self.spec = registry.resolve(
                backend, resolved, outputs=hint, features=self.features)
        self._metrics = obs.default_registry() if metrics is None else \
            metrics
        self._tracer = obs.default_tracer() if tracer is None else tracer
        self.normalize = normalize
        if normalize:
            with self._tracer.span("aligner.layout", step="normalize",
                                   shape=list(reference.shape)) as sp:
                reference = normalize_reference(reference)
                sp.sync(reference)
        self.reference = reference
        self.length = int(reference.shape[0])
        self._auto_width = isinstance(segment_width, str)
        if self._auto_width and segment_width != "auto":
            raise ValueError(f"segment_width must be an int >= 1 or "
                             f"'auto', got {segment_width!r}")
        self.segment_width = segment_width
        self.interpret = interpret
        self.options = options
        self.tune_options = dict(tune_options) if tune_options else {}
        self._tuned_widths: dict = {}   # (m, bucket, sweep-req) -> width
        if max_executables < 1:
            raise ValueError(f"max_executables must be >= 1, got "
                             f"{max_executables}")
        self.max_executables = max_executables
        self._layouts: dict = {} if layout_cache is None else layout_cache
        self._layouts_verified: set = set()
        # pool-safety: the executable LRU is the only structure a
        # session mutates per call, so guarding it (lookup / insert /
        # evict as short critical sections — the sweep itself runs
        # unlocked) makes one Aligner safely shareable across
        # serve-pool worker threads.  Two threads racing the same cold
        # key may both build; last insert wins, which is wasteful but
        # correct (jit executables for the same key are interchangeable)
        self._fns_lock = threading.RLock()
        self._fns: OrderedDict = OrderedDict()
        self.stats = AlignerStats()
        log.debug("Aligner(n=%d, backend=%s, spec=%s)", self.length,
                  self.backend.name, self.spec.describe())

    # ----------------------------------------------------------- prep
    def resolved_width(self, batch_shape, outputs=DEFAULT_OUTPUTS) -> int:
        """The segment width this session dispatches for a (B, M)
        batch shape and output request.

        A pinned-width session returns it verbatim.  An
        ``segment_width="auto"`` session on the kernel backend asks
        ``repro.tune`` — memoized per (query length, batch bucket,
        sweep outputs) key, so the tuner (or its persistent cache) is
        consulted once per workload; non-kernel backends ignore width
        and get the default.
        """
        from repro.kernels import ops as _ops
        if not self._auto_width:
            return self.segment_width
        if self.backend.name != "kernel":
            return _ops.DEFAULT_SEGMENT_WIDTH
        from repro import tune
        B, m = batch_shape
        req = sweep_outputs(normalize_outputs(outputs))
        key = (int(m), tune.batch_bucket(int(B)), req)
        w = self._tuned_widths.get(key)
        if w is None:
            res = tune.autotune(
                np.asarray(self.reference), m=int(m), batch=int(B),
                spec=self.spec, outputs=req, backends=("kernel",),
                interpret=self.interpret, metrics=self._metrics,
                tracer=self._tracer, **self.tune_options)
            w = self._tuned_widths[key] = res.segment_width
        return w

    def layout(self, compute_dtype=jnp.float32,
               segment_width: int | None = None):
        """The cached swizzled kernel layout of this session's
        (already normalized) reference — computed at most once per
        (segment_width, dtype).

        A pre-populated ``layout_cache`` entry is verified (once per
        key) to actually unswizzle back to THIS reference: the cache
        dict is per-reference (a ``ReferenceIndex`` entry's), and a
        dict accidentally shared across references must fail loudly
        instead of sweeping against the wrong series.
        """
        from repro.kernels import ops as _ops
        if segment_width is None:
            if self._auto_width:
                raise ValueError(
                    "segment_width='auto' sessions have no single "
                    "layout; pass layout(dtype, segment_width=...) "
                    "with a width from resolved_width()")
            segment_width = self.segment_width
        key = (segment_width, jnp.dtype(compute_dtype).name)
        cached = self._layouts.get(key)
        if cached is None:
            with self._tracer.span("aligner.layout", step="swizzle",
                                   segment_width=segment_width) as sp:
                self._layouts[key] = _ops.swizzle_reference(
                    self.reference.astype(compute_dtype), segment_width)
                sp.sync(self._layouts[key])
            self._layouts_verified.add(key)
        elif key not in self._layouts_verified:
            want = np.asarray(self.reference.astype(compute_dtype))
            got = np.asarray(_ops.unswizzle_reference(cached))
            if got.shape[0] < self.length or \
                    not np.array_equal(got[:self.length], want):
                raise ValueError(
                    f"layout_cache entry {key} does not unswizzle to "
                    f"this session's reference (n={self.length}): "
                    f"layout_cache dicts are per-reference — do not "
                    f"share one across Aligners over different "
                    f"references")
            self._layouts_verified.add(key)
        return self._layouts[key]

    # ------------------------------------------------------ executable
    def _build(self, batch_shape, dtype, req: frozenset):
        """One executable for one (batch shape, dtype, outputs) key.

        Capability validation happens here (loud registry errors);
        the returned ``(fn, jitted, args, work)`` runs normalize-queries
        + the fused sweep as ONE traced computation, called as
        ``fn(queries, *args)`` and returning the sweep-level
        ``SDTWResult``.  ``args`` are the session's reference operands:
        arguments, never constants of the program.  ``jitted=False``
        marks the eager strategies (distributed), whose dispatches must
        not tick the trace/compile counters — nothing is traced or
        built.  ``work`` is the wavefront kernel work of each dispatch
        that one call makes (empty off the kernel backend).
        """
        # re-validate with the requested outputs: an Aligner built for
        # a capable (spec, backend) pair can still be asked for an
        # output the backend cannot fulfill
        registry.resolve(self.backend.name, self.spec, outputs=req,
                         features=self.features)
        sweep = sweep_outputs(req)
        fused = self._fused(req)
        # derived requests (path / soft_alignment) get their queries
        # normalized ONCE, eagerly, in align() — both the sweep and the
        # derivation consume the same batch, so the closure must not
        # normalize again.  The kernel's FUSED soft_alignment is not
        # derived — it is its own executable, normalizing inside.
        pre_normalized = bool(req & {"path", "soft_alignment"}) \
            and not fused

        if fused:
            # soft_alignment on the kernel backend: ONE memoized
            # executable runs the checkpointed forward+reverse pair
            # (repro.kernels.backward) and fills cost/end/E together —
            # no engine cost matrix, no derivation pass
            from repro.kernels import backward
            from repro.kernels import ops as _ops
            w = self.resolved_width(batch_shape, req)
            interp, spec = self.interpret, self.spec
            norm = self.normalize
            work = _ops.wavefront_work(spec, batch=batch_shape[0],
                                       m=batch_shape[1], n=self.length,
                                       segment_width=w, checkpoint=True)
            # the checkpointed forward and the reverse sweep execute
            # the same blocks over the same real columns
            works = () if work is None else (work, work)

            def run_fused(q, reference):
                _count_trace()
                if norm:
                    q = normalize_batch(q)
                cost, end, E = backward.soft_alignment_fused(
                    q, reference, spec=spec, segment_width=w,
                    interpret=interp)
                return SDTWResult(cost=cost, end=end, soft_alignment=E)

            return jax.jit(run_fused), True, (self.reference,), works

        if self.backend.name == "kernel":
            # the session's whole point on the kernel path: the layout
            # prep (pad + swizzle, paper §3) is done once, here, and
            # handed to the shared program as an argument
            from repro.kernels import ops as _ops
            B, m = batch_shape[:2]
            w = self.resolved_width(batch_shape, req)
            r_layout = self.layout(jnp.float32, segment_width=w)
            # non-sdtw families ride extra operands through the same
            # pallas_call; the reference-derived ones (twed's shifted
            # layout, erp's bt prefix) are computed ONCE here — eagerly,
            # by the same standalone jit every path uses, so the
            # session's grids stay bit-identical to the one-shot call
            extras_ref = _ops.family_extras_ref(self.spec, self.reference,
                                                segment_width=w)
            work = _ops.wavefront_work(self.spec, batch=B, m=m,
                                       n=self.length, segment_width=w,
                                       features=self.features)
            fn = _Bound(_kernel_program, dict(
                spec=self.spec, n=self.length, segment_width=w,
                interpret=self.interpret, sweep=sweep,
                normalize=self.normalize and not pre_normalized))
            return (fn, True, (r_layout, extras_ref),
                    () if work is None else (work,))

        backend, spec = self.backend, self.spec
        norm = self.normalize and not pre_normalized
        opts = self.options
        seg = self.resolved_width(batch_shape, req)
        interp = self.interpret

        def run(q, reference):
            _count_trace()
            if norm:
                q = normalize_batch(q)
            plan = registry.ExecutionPlan(
                queries=q, reference=reference, segment_width=seg,
                interpret=interp, outputs=sweep, options=opts)
            return backend.execute(spec, plan)

        if backend.name == "distributed":
            # shard_map pipelines carry their own jit + per-mesh cache
            # (backends.builtin); wrapping them again buys nothing and
            # this session builds no executable of its own
            return run, False, (self.reference,), ()
        return jax.jit(run), True, (self.reference,), ()

    def _fused(self, req: frozenset) -> bool:
        """Does this request dispatch the kernel's fused forward+reverse
        soft-alignment executable (vs deriving E above the sweep)?"""
        return (self.backend.name == "kernel" and self.spec.soft
                and "soft_alignment" in req)

    # -------------------------------------------------------- serving
    def align(self, queries, *, outputs=DEFAULT_OUTPUTS) -> SDTWResult:
        """Align one query batch. queries: (B, M), or (B, M, D) against
        a reference of D features.

        Returns an :class:`SDTWResult` restricted to ``outputs``.  The
        first call for a given (batch shape, dtype, outputs) traces and
        compiles; every later call with the same key is dispatch-only.
        """
        with self._tracer.span("aligner.call"):
            return self._align(queries, outputs)

    def _align(self, queries, outputs) -> SDTWResult:
        queries = jnp.asarray(queries)
        if queries.ndim == 3 and queries.shape[2] == 1:
            queries = queries[..., 0]         # one feature: univariate
        validate_batch_inputs(queries, self.reference,
                              segment_width=None if self._auto_width
                              else self.segment_width)
        req = normalize_outputs(outputs)
        self.stats.calls += 1
        m = self._metrics
        m.inc("aligner.calls")
        fused = self._fused(req)
        derived = bool(req & {"path", "soft_alignment"}) and not fused
        if derived and self.normalize:
            # normalize ONCE for both the sweep and the derivation
            # (the executable for a derived request skips its fused
            # normalize — see _build's pre_normalized)
            queries = normalize_batch(queries)
        if (req - {"soft_alignment"}) or fused:
            key = (queries.shape, jnp.dtype(queries.dtype).name, req)
            with self._fns_lock:
                entry = self._fns.get(key)
                cold = entry is None
                if not cold:
                    self.stats.cache_hits += 1
                    m.inc("aligner.cache_hits")
                    self._fns.move_to_end(key)      # LRU touch
            if cold:
                with self._tracer.span("aligner.build",
                                       backend=self.backend.name,
                                       batch=list(queries.shape),
                                       outputs=sorted(req)):
                    fn, jitted, args, work = self._build(
                        queries.shape, queries.dtype, req)
                    # jax.jit would trace and compile lazily, inside
                    # the first dispatch: do both here, so this span
                    # holds them and the dispatch span only runs
                    _TRACING.session = self
                    try:
                        run = (fn.lower(queries, *args).compile()
                               if jitted else fn)
                    finally:
                        _TRACING.session = None
                    entry = _Executable(
                        run=run, jitted=fn if jitted else None,
                        args=args, work=work)
                log.debug("built executable key=%s backend=%s",
                          key, self.backend.name)
            with self._tracer.span("aligner.dispatch",
                                   backend=self.backend.name,
                                   batch=list(queries.shape),
                                   cold=cold) as sp:
                res = entry.run(queries, *entry.args)
                sp.sync(res)
            if entry.work:
                from repro.kernels import ops as _ops
                for work in entry.work:
                    _ops.count_wavefront(work)
            if cold:
                # cache + count only now: an executable (and its
                # ``compiles`` tick) exists exactly when its first
                # dispatch succeeded — eager strategies (jitted None)
                # build none and tick nothing
                with self._fns_lock:
                    self._fns[key] = entry
                    if entry.jitted is not None:
                        self.stats.compiles += 1
                        m.inc("aligner.compiles")
                    while len(self._fns) > self.max_executables:
                        old_key, _ = self._fns.popitem(last=False)
                        self.stats.evictions += 1
                        m.inc("aligner.evictions")
                        log.debug("evicted executable key=%s (LRU, "
                                  "max_executables=%d)", old_key,
                                  self.max_executables)
        else:
            # soft_alignment-only: no sweep to run — validate the
            # request against the backend, then derive directly
            registry.resolve(self.backend.name, self.spec, outputs=req)
            res = SDTWResult()
        if derived:
            res = _derive_outputs(res, req, queries, self.reference,
                                  self.spec)
        return res.restrict(req)

    __call__ = align

    def executables(self) -> int:
        """How many distinct jitted executables this session holds."""
        with self._fns_lock:
            return sum(1 for e in self._fns.values()
                       if e.jitted is not None)

    def hlo_texts(self) -> list[str]:
        """Compiled HLO of every jitted executable this session holds:
        what the device runs.  On a TPU a kernel session's text holds
        the Pallas kernel as a ``tpu_custom_call``.  Each text is
        lowered and compiled again from the kept jitted function."""
        with self._fns_lock:
            held = [(key, e.jitted, e.args) for key, e in self._fns.items()
                    if e.jitted is not None]
        return [fn.lower(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
                         *args).compile().as_text()
                for (shape, dtype, _), fn, args in held]

    def __repr__(self):
        return (f"Aligner(n={self.length}, backend={self.backend.name!r}, "
                f"spec={self.spec.describe()}, "
                f"executables={self.executables()})")
