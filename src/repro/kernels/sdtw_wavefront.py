"""Compatibility shim over the carry-channel wavefront executor.

The monolithic per-variant kernel that used to live here (one hand-
written ``fori_loop`` body with a ``with_window`` if-forest duplicating
every carry) is gone: ``repro.kernels.wavefront`` now expresses the
wavefront ONCE as typed :class:`~repro.kernels.wavefront.CarryChannel`s
plus a stream fold (``MinArgminFold`` / ``SoftMinFold``), and every
variant (distance-only, +start-pointer window lanes, soft-min) is a
:class:`~repro.kernels.wavefront.KernelPlan` executed by
:func:`~repro.kernels.wavefront.wavefront_call`, which
``repro.kernels.ops`` dispatches.

This module keeps the historical constants so callers and prepped
layouts are unchanged.
"""

from __future__ import annotations

from repro.core.spec import KERNEL_BIG, NO_WINDOW
from repro.kernels.wavefront import LANES, SUBLANES  # noqa: F401

NEG = NO_WINDOW    # historical alias; the sentinel lives in core.spec
BIG = KERNEL_BIG   # likewise (value + dtype rationale in core/spec.py)
