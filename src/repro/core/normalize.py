"""Batch z-normalization (the paper's normalizer, §5.1).

Standardizes each series to mean 0 / std 1 using the cuDTW++ moment
formulation the paper adopts::

    sum   /= n
    sumSq  = sumSq/n - sum*sum      # biased variance via E[x^2] - E[x]^2

The Pallas kernel in ``repro.kernels.normalizer`` implements the same
computation with an explicit VMEM reduction; this module is the public
API and the pure-jnp reference.  Every series is normalized over time:
a univariate one along its last axis, a multivariate one feature by
feature.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normalize_batch(x: jnp.ndarray, *, eps: float = 1e-12,
                    accum_dtype=jnp.float32) -> jnp.ndarray:
    """Z-normalize each series over time.  x: (..., L) univariate
    series, normalized along the last axis; or a (B, L, D) batch of
    multivariate series, each feature normalized over time (axis -2):
    per-utterance mean and variance normalization."""
    axis = -2 if x.ndim == 3 else -1
    xf = x.astype(accum_dtype)
    n = x.shape[axis]
    s = jnp.sum(xf, axis=axis, keepdims=True) / n
    sq = jnp.sum(xf * xf, axis=axis, keepdims=True) / n - s * s
    # clamp tiny negative variance from the E[x^2]-E[x]^2 formulation
    std = jnp.sqrt(jnp.maximum(sq, eps))
    return ((xf - s) / std).astype(x.dtype)


def normalize_reference(reference: jnp.ndarray) -> jnp.ndarray:
    """Z-normalize one reference over time: (N,), or (N, D) with each
    feature normalized over time."""
    if reference.ndim == 2:
        return normalize_batch(reference[None])[0]
    return normalize_batch(reference)


normalize = jax.jit(normalize_batch)
