"""Measure the VPU's float32 elementwise ceiling of a TPU with a Pallas
microkernel.

Each grid step loads ``chains`` independent (8, 128) float32 tiles into
registers and runs ``iters`` rounds of ``x = min(x + a, b)`` on every
one of them: two VPU operations per element per round, with enough
independent chains to fill the issue slots.  The ceiling is the best
rate, in elementwise operations per second, over a few chain counts.
It bounds the min-plus recurrence of sDTW, which never uses the MXU and
for which no peak is published.  ``peaks.py`` holds the measured number.

  python3 -m chipbench.vpu_ceiling        # on a TPU; prints JSON
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES, LANES = 8, 128
OPS_PER_ROUND = 2                        # one add and one min
CHAIN_COUNTS = (8, 16, 24, 32)
UNROLL = 8


def _kernel(x_ref, a_ref, b_ref, o_ref, *, chains: int, iters: int):
    a = a_ref[...]
    b = b_ref[...]

    def body(_, xs):
        for _ in range(UNROLL):           # Mosaic loops do not unroll
            xs = tuple(jnp.minimum(x + a, b) for x in xs)
        return xs

    xs = tuple(x_ref[0, c] for c in range(chains))
    xs = jax.lax.fori_loop(0, iters // UNROLL, body, xs)
    acc = xs[0]
    for x in xs[1:]:
        acc = acc + x
    o_ref[0] = acc


def ceiling_call(*, steps: int, chains: int, iters: int,
                 interpret: bool = False):
    """The jitted microkernel: (steps, chains, 8, 128) tiles -> (steps,
    8, 128).  It performs ``ops(steps, chains, iters)`` VPU operations;
    ``iters`` is a multiple of ``UNROLL``."""
    if iters % UNROLL:
        raise ValueError(f"iters={iters} is not a multiple of {UNROLL}")
    kernel = functools.partial(_kernel, chains=chains, iters=iters)
    tile = pl.BlockSpec((SUBLANES, LANES), lambda s: (0, 0))
    call = pl.pallas_call(
        kernel, grid=(steps,),
        in_specs=[pl.BlockSpec((1, chains, SUBLANES, LANES),
                               lambda s: (s, 0, 0, 0)), tile, tile],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, SUBLANES, LANES),
                                       jnp.float32),
        interpret=interpret)
    return jax.jit(call)


def ops(steps: int, chains: int, iters: int) -> int:
    """Elementwise VPU operations one call performs."""
    return steps * chains * iters * OPS_PER_ROUND * SUBLANES * LANES


def measure(*, steps: int = 32, iters: int = 2_000_000, repeats: int = 5):
    """Best rate (ops/s) per chain count, timed on the host clock over
    calls of several hundred milliseconds each."""
    rates = {}
    for chains in CHAIN_COUNTS:
        fn = ceiling_call(steps=steps, chains=chains, iters=iters)
        x = jnp.zeros((steps, chains, SUBLANES, LANES), jnp.float32)
        a = jnp.full((SUBLANES, LANES), 1e-3, jnp.float32)
        b = jnp.full((SUBLANES, LANES), 1e6, jnp.float32)
        fn(x, a, b).block_until_ready()                  # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn(x, a, b).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        rates[chains] = {"seconds": best,
                         "ops_per_s": ops(steps, chains, iters) / best}
    return rates


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"vpu_ceiling: no TPU (JAX sees {dev.platform!r})")
    rates = measure()
    print(json.dumps({"device_kind": dev.device_kind, "rates": rates,
                      "ceiling_ops_per_s": max(
                          r["ops_per_s"] for r in rates.values())}))


if __name__ == "__main__":
    main()
