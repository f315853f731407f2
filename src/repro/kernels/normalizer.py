"""Pallas TPU batch z-normalizer — the paper's normalizer kernel (§5.1).

Paper mechanism -> TPU mapping:
  * one thread block per query            -> one grid step per group of
    SUBLANES queries (a (8, L) VMEM tile).
  * thread coarsening (<=2 elems/thread)  -> each VPU op covers an
    (8, 128) tile; a lane owns ceil(L/128) elements (coarsening is
    structural on TPU).
  * shared-memory parallel reduction for sum / sumSq -> a VREG tree
    reduction emitted by ``jnp.sum`` over the VMEM tile.
  * first thread computing mean/std, broadcast via shared memory ->
    scalar broadcast from the reduced value (no explicit sync needed:
    the VPU is a single instruction stream).

Moments use the cuDTW++ formulation the paper adopts:
``var = sumSq/n - mean**2`` (biased), matching ``core.normalize``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8


def _kernel(x_ref, o_ref, *, n: int, eps: float):
    x = x_ref[0].astype(jnp.float32)          # (S, Lp)
    # padded tail (if any) contributes zeros to sum and sumSq but must not
    # change n; n is the true length, baked in statically.
    s = jnp.sum(x, axis=1, keepdims=True) / n
    sq = jnp.sum(x * x, axis=1, keepdims=True) / n - s * s
    std = jnp.sqrt(jnp.maximum(sq, eps))
    o_ref[0] = ((x - s) / std).astype(o_ref.dtype)


def normalizer_pallas(x: jnp.ndarray, *, n: int, eps: float = 1e-12,
                      interpret: bool | None = None) -> jnp.ndarray:
    """x: (G, SUBLANES, Lp) with the true (unpadded) length ``n``.
    Padding columns (>= n) must be zero; their output is garbage and is
    sliced off by the ops.py wrapper.  interpret: None =
    ``ops.default_interpret()`` (compiled on TPU)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret  # imports us
        interpret = default_interpret()
    G, S, Lp = x.shape
    assert S == SUBLANES
    kernel = functools.partial(_kernel, n=n, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, S, Lp), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((1, S, Lp), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, S, Lp), x.dtype),
        interpret=interpret,
        name="sdtw_normalizer",
    )(x)
