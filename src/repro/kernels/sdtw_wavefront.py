"""Compatibility shim over the carry-channel wavefront executor.

The monolithic per-variant kernel that used to live here (one hand-
written ``fori_loop`` body with a ``with_window`` if-forest duplicating
every carry) is gone: ``repro.kernels.wavefront`` now expresses the
wavefront ONCE as typed :class:`~repro.kernels.wavefront.CarryChannel`s
plus a stream fold (``MinArgminFold`` / ``SoftMinFold``), and every
variant (distance-only, +start-pointer window lanes, soft-min) is a
:class:`~repro.kernels.wavefront.KernelPlan` executed by
:func:`~repro.kernels.wavefront.wavefront_call`.

This module keeps the historical entry point and constants so
``repro.kernels.ops`` callers and prepped layouts are unchanged.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.spec import DEFAULT_SPEC, KERNEL_BIG, NO_WINDOW, DPSpec
from repro.kernels.wavefront import (LANES, SUBLANES,  # noqa: F401
                                     KernelPlan, band_grid_blocks,
                                     build_plan, wavefront_call)

NEG = NO_WINDOW    # historical alias; the sentinel lives in core.spec
BIG = KERNEL_BIG   # likewise (value + dtype rationale in core/spec.py)


def sdtw_wavefront_pallas(q_rev_pad: jnp.ndarray,
                          r_layout: jnp.ndarray,
                          *extras: jnp.ndarray,
                          m: int, segment_width: int,
                          compute_dtype=jnp.float32,
                          interpret: bool | None = None,
                          spec: DPSpec = DEFAULT_SPEC,
                          with_window: bool = False,
                          n: int | None = None):
    """Raw pallas_call wrapper. Use ``repro.kernels.ops.sdtw_wavefront``.

    q_rev_pad: (G, SUBLANES, Mp) reversed queries, Mp = query_pack_len(m)
    r_layout:  (R, w, LANES) pre-swizzled reference blocks
    returns (costs (G, SUBLANES) f32, ends (G, SUBLANES) i32), plus
    starts (G, SUBLANES) i32 in the middle when ``with_window`` —
    computed by the SAME pallas_call (the start pointers ride the
    wavefront carries as an int32 channel), never a second sweep.

    Capability floor (``repro.backends`` enforces this for API callers;
    direct callers get the same error from the plan): hard- and
    soft-min reductions with padding-safe distances — cosine is out
    because the PAD_VALUE reference padding would not lose the argmin.
    Sakoe–Chiba specs automatically run the band-skip plan (trailing
    fully-out-of-band reference blocks are dropped from the grid;
    outputs identical to the masked full grid).
    """
    plan = build_plan(spec, m=m, segment_width=segment_width,
                      num_ref_blocks=r_layout.shape[0],
                      compute_dtype=compute_dtype,
                      with_window=with_window,
                      n=n if spec.family != "sdtw" else None)
    return wavefront_call(plan, q_rev_pad, r_layout, *extras,
                          interpret=interpret)
