"""Builtin backend registrations — imported lazily by the registry.

Each entry pairs a Capabilities declaration with an execute(spec, plan)
adapter onto the underlying implementation.  The raw modules
(core.ref / core.engine / core.quantized / core.distributed /
kernels.ops) keep their tuple-level contracts; the adapters here are
where tuples become typed :class:`~repro.core.result.SDTWResult`
pytrees — every backend returns the same result type, whatever sweep
outputs the plan requested (``"start" in plan.outputs`` threads the
matched-window start pointers through the same fused sweep).

Heavy imports (Pallas, shard_map) stay inside the execute functions so
registry queries and the XLA-only backends never pay for them.
"""

from __future__ import annotations

from repro.backends.registry import (Backend, Capabilities, register,
                                     register_alias)
from repro.core.result import from_sweep

_ALL = frozenset({"sqeuclidean", "abs", "cosine"})
_HARD = frozenset({"hardmin"})
_BOTH = frozenset({"hardmin", "softmin"})

# outputs tiers: every backend fulfills cost/end requests; window-capable
# backends add start (+path, whose traceback is pinned by the window);
# differentiable backends also serve soft_alignment (jax.grad through
# the cost-matrix engine sweep in repro.align.soft, or the fused
# reverse-sweep pair in repro.kernels.backward on the kernel backend).
_COST_END = frozenset({"cost", "end"})
_WINDOWED = _COST_END | {"start", "path"}
_FULL = _WINDOWED | {"soft_alignment"}

# recurrence families (repro.dp): the three exact executors run every
# family through the shared DPSpec.family_cell definition; the
# approximate/sharded backends stay sdtw-only (the registry default),
# so a family request can never silently downgrade onto them.
_ALL_FAMILIES = frozenset({"sdtw", "twed", "erp", "local"})
_GLOBAL_WINDOWS = frozenset({"sdtw", "twed", "erp"})   # start output

# multivariate (B, M, D) inputs: the sweep outputs of the sdtw family on
# the executors that add per-feature costs (ref, kernel).  Paths and
# expected alignments are derived by univariate code above the sweep.
_SWEEP = frozenset({"cost", "end", "start"})


# ------------------------------------------------------------------ ref
def _exec_ref(spec, plan):
    from repro.core import ref
    return from_sweep(
        ref.sdtw_ref(plan.queries, plan.reference, spec=spec,
                     return_window="start" in plan.outputs),
        plan.outputs)


register(Backend(
    name="ref",
    capabilities=Capabilities(
        distances=_ALL, reductions=_BOTH, banding=True,
        differentiable=True, per_query_reference=True, exact=True,
        outputs=_FULL, families=_ALL_FAMILIES,
        window_families=_GLOBAL_WINDOWS, multivariate_outputs=_SWEEP,
        multivariate_reductions=_BOTH, device="any",
        notes="trusted row-scan oracle; slow, for validation"),
    execute=_exec_ref,
))


# --------------------------------------------------------------- engine
def _exec_engine(spec, plan):
    from repro.core import engine
    return from_sweep(
        engine.sdtw_engine(plan.queries, plan.reference, spec=spec,
                           return_window="start" in plan.outputs),
        plan.outputs)


register(Backend(
    name="engine",
    capabilities=Capabilities(
        distances=_ALL, reductions=_BOTH, banding=True,
        differentiable=True, per_query_reference=True, exact=True,
        outputs=_FULL, families=_ALL_FAMILIES,
        window_families=_GLOBAL_WINDOWS, device="any",
        notes="anti-diagonal XLA wavefront; the default"),
    execute=_exec_engine,
))

# soft == engine with the reduction forced to soft-min (the former
# core.softdtw fork, collapsed into a spec override).
register_alias("soft", "engine", reduction="softmin")


# --------------------------------------------------------------- kernel
def _exec_kernel(spec, plan):
    from repro.kernels import ops
    width = plan.segment_width
    if isinstance(width, str):
        # a plan built with segment_width="auto" that reached dispatch
        # unresolved (core.api resolves it earlier on the normal path):
        # ask the tuner, which answers from its cache when warm
        from repro import tune
        width = tune.autotune(
            plan.reference, m=int(plan.queries.shape[1]),
            batch=int(plan.queries.shape[0]), spec=spec,
            outputs=plan.outputs, backends=("kernel",),
            interpret=plan.interpret).segment_width
    if spec.soft and "start" not in plan.outputs \
            and spec.family == "sdtw":
        # soft specs dispatch through the fused custom_vjp so jax.grad
        # of the returned cost routes into the reverse-sweep backward
        # instead of failing on the opaque pallas_call
        from repro.kernels import backward
        return from_sweep(
            backward.sdtw_soft_fused(
                plan.queries, plan.reference, spec=spec,
                segment_width=width, interpret=plan.interpret),
            plan.outputs)
    return from_sweep(
        ops.sdtw_wavefront(
            plan.queries, plan.reference,
            segment_width=width, interpret=plan.interpret,
            spec=spec, return_window="start" in plan.outputs),
        plan.outputs)


register(Backend(
    name="kernel",
    capabilities=Capabilities(
        # no cosine: PAD_VALUE reference padding only dominates costs
        # that grow with |q - r| (see the sentinel notes in core.spec).
        # soft-min runs the carry-channel executor's running-logsumexp
        # fold (repro.kernels.wavefront.SoftMinFold); gradients and
        # soft_alignment route through the fused reverse-sweep
        # custom_vjp (repro.kernels.backward) — checkpointed forward +
        # reverse wavefronts, never an O(M*N) buffer on the grad path.
        distances=frozenset({"sqeuclidean", "abs"}), reductions=_BOTH,
        banding=True, differentiable=True, per_query_reference=False,
        exact=True, outputs=_FULL, families=_ALL_FAMILIES,
        # multivariate inputs: the feature cost is summed inside the
        # same pallas_call; hard-min only, since the fused backward
        # that makes soft-min differentiable is univariate
        multivariate_outputs=_SWEEP, multivariate_reductions=_HARD,
        device="tpu (interpret=True elsewhere)",
        notes="Pallas wavefront kernel (hard+soft, band-skip grids, "
              "fused reverse-sweep backward); one shared reference, "
              "(N,) or (N, D)"),
    execute=_exec_kernel,
))


# ------------------------------------------------------------ quantized
def _exec_quantized(spec, plan):
    from repro.core.quantized import sdtw_quantized
    return from_sweep(
        sdtw_quantized(
            plan.queries, plan.reference, normalize=False, spec=spec,
            n_levels=plan.option("n_levels", 256)),
        plan.outputs)


register(Backend(
    name="quantized",
    capabilities=Capabilities(
        distances=_ALL, reductions=_BOTH, banding=True,
        differentiable=False, per_query_reference=False,
        exact=False,   # uint8 codebook: ~10% cost error on CBF data
        outputs=_COST_END, device="any",
        notes="uint8 codebook encode -> engine on decoded centroids"),
    execute=_exec_quantized,
))


# ---------------------------------------------------------- distributed
_DISTRIBUTED_CACHE: dict = {}
_DISTRIBUTED_CACHE_MAX = 8     # bounded: entries pin Mesh objects and
#                                compiled shard_map pipelines


def _exec_distributed(spec, plan):
    from repro.core.distributed import make_sdtw_distributed
    mesh = plan.option("mesh")
    if mesh is None:
        raise ValueError(
            "distributed backend needs a mesh: pass "
            "options={'mesh': Mesh(...)} (and optionally 'row_block', "
            "'batch_axes', 'ref_axis') to repro.sdtw")
    batch_axes = tuple(plan.option("batch_axes", ("data",)))
    ref_axis = plan.option("ref_axis", "model")
    row_block = plan.option("row_block", 64)
    # cache the built shard_map per (mesh, spec, layout): a SearchService
    # routing every sweep round through one mesh must not rebuild (and
    # re-trace) the pipeline per dispatch
    key = (mesh, spec, batch_axes, ref_axis, row_block)
    fn = _DISTRIBUTED_CACHE.get(key)
    if fn is None:
        while len(_DISTRIBUTED_CACHE) >= _DISTRIBUTED_CACHE_MAX:
            _DISTRIBUTED_CACHE.pop(next(iter(_DISTRIBUTED_CACHE)))
        fn = _DISTRIBUTED_CACHE[key] = make_sdtw_distributed(
            mesh, spec=spec, batch_axes=batch_axes, ref_axis=ref_axis,
            row_block=row_block)
    return from_sweep(fn(plan.queries, plan.reference), plan.outputs)


register(Backend(
    name="distributed",
    capabilities=Capabilities(
        distances=_ALL, reductions=_HARD, banding=True,
        differentiable=False, per_query_reference=False, exact=True,
        outputs=_COST_END, device="multi-device mesh",
        notes="shard_map ppermute pipeline; needs options={'mesh': ...}"),
    execute=_exec_distributed,
))
