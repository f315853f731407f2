"""The soft-min sDTW training loss: ``make_sdtw_loss`` builds a
differentiable batch objective against one reference series."""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp


def make_sdtw_loss(reference, *, spec=None, gamma: float = 1.0,
                   band: int | None = None,
                   backend: str | None = None,
                   segment_width: int = 8,
                   interpret: bool | None = None,
                   normalize: bool = True,
                   reduce: str = "mean") -> Callable:
    """-> loss(pred (B, M)) — the batch's soft-min sDTW cost against
    one reference series, usable directly under ``jax.grad`` /
    ``jax.value_and_grad`` as a training objective.

    The spec is promoted to soft-min (``gamma``) if it is not already;
    ``backend="kernel"`` differentiates through the fused
    reverse-sweep custom_vjp (``repro.kernels.backward``) instead of
    unrolling ``jax.grad`` through the engine's O(M·N) cost matrix —
    same gradients, kernel speed.  ``reduce``: "mean" | "sum" | "none".
    """
    from repro.core.api import sdtw
    from repro.core.spec import resolve_spec
    if reduce not in ("mean", "sum", "none"):
        raise ValueError(f"reduce must be 'mean', 'sum' or 'none', "
                         f"got {reduce!r}")
    resolved = resolve_spec(spec, gamma=gamma, band=band)
    if not resolved.soft:
        resolved = resolve_spec(resolved, reduction="softmin")
    reference = jnp.asarray(reference)

    def loss(pred):
        cost = sdtw(pred, reference, outputs=("cost",),
                    normalize=normalize, backend=backend, spec=resolved,
                    segment_width=segment_width,
                    interpret=interpret).cost
        if reduce == "mean":
            return cost.mean()
        if reduce == "sum":
            return cost.sum()
        return cost

    return loss
