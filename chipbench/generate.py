"""The one traffic generator: every mix under ``traffic/`` is data read
here, and every array is made on the device from the seed.

A configuration's ``references`` block states the reference set::

    {"count": 1, "length": 100000, "process": "random_walk", "seed": 1}

``seed`` fixes the set for the deployment, as an index or a genome is
fixed; without it the run's seed draws the set too.
``process`` is ``random_walk``: the cumulative sum of standard normal
steps.

A traffic file states the query batches::

    {"batch": 32, "query_len": 2000, "pool": 16,
     "queries": [{"kind": "excerpt", "share": 1.0,
                  "resample": [0.9, 1.1], "noise": 0.1}]}

``pool`` batches are made before the window and the window cycles
through them.  Each kind gets a fixed number of rows in every batch
(its ``share`` of ``batch``, largest remainders first), so every seed
does the same work; the seed draws the rows and their order.  Kinds:

  fresh    a new series of the configuration's reference process,
           which no reference holds;
  excerpt  a stretch of a reference chosen uniformly, resampled by a
           factor drawn from ``resample``, z-normalized, plus Gaussian
           noise of standard deviation ``noise``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int):
    """A PRNG key from any non-negative whole number (more than 32
    bits are folded in, not dropped)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _series(key, shape, process: str):
    steps = jax.random.normal(key, shape, jnp.float32)
    if process == "random_walk":
        return jnp.cumsum(steps, axis=-1)
    raise ValueError(f"unknown process {process!r}")


@functools.partial(jax.jit, static_argnames=("count", "length", "process"))
def _references(key, *, count, length, process):
    return _series(key, (count, length), process)


def references(key, spec: dict):
    """(count, length) float32 reference set on the device.  A ``seed``
    in ``spec`` fixes the set for the configuration; ``key`` then goes
    unused."""
    if "seed" in spec:
        key = key_of(int(spec["seed"]))
    return _references(key, count=int(spec["count"]),
                       length=int(spec["length"]),
                       process=spec["process"])


def rows_per_kind(batch: int, kinds: list) -> list[int]:
    """Rows of each kind in one batch: shares of ``batch``, largest
    remainders first, summing to ``batch``."""
    shares = np.array([float(k["share"]) for k in kinds])
    if (shares <= 0).any():
        raise ValueError("every query kind needs a share > 0")
    exact = batch * shares / shares.sum()
    rows = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - rows), kind="stable")[:batch - rows.sum()]:
        rows[i] += 1
    return [int(r) for r in rows]


def _excerpts(key, refs, *, shape, m, resample, noise):
    """Resampled, z-normalized, noisy stretches of random references."""
    R, N = refs.shape
    lo, hi = resample
    kr, kf, ko, kn = jax.random.split(key, 4)
    ref = jax.random.randint(kr, shape, 0, R)
    f = jax.random.uniform(kf, shape, jnp.float32, lo, hi)
    span = int(np.ceil((m - 1) * hi)) + 2
    if span > N:
        raise ValueError(f"excerpts of {m} samples resampled up to {hi}x "
                         f"need references longer than {span}")
    off = jax.random.randint(ko, shape, 0, N - span + 1)
    pos = off[..., None] + jnp.arange(m, dtype=jnp.float32) * f[..., None]
    i0 = jnp.floor(pos).astype(jnp.int32)
    frac = pos - i0
    flat = refs.reshape(-1)
    base = (ref * N)[..., None]
    x = flat[base + i0] * (1 - frac) + flat[base + i0 + 1] * frac
    x = x - x.mean(axis=-1, keepdims=True)
    x = x / jnp.maximum(x.std(axis=-1, keepdims=True), 1e-12)
    return x + noise * jax.random.normal(kn, x.shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("plan", "pool", "m",
                                             "process"))
def _queries(key, refs, *, plan, pool, m, process):
    parts = []
    keys = jax.random.split(key, len(plan) + 1)
    for k, (kind, rows, params) in zip(keys[1:], plan):
        shape = (pool, rows)
        if kind == "fresh":
            parts.append(_series(k, shape + (m,), process))
        elif kind == "excerpt":
            p = dict(params)
            parts.append(_excerpts(k, refs, shape=shape, m=m,
                                   resample=p["resample"],
                                   noise=p["noise"]))
        else:
            raise ValueError(f"unknown query kind {kind!r}")
    batches = jnp.concatenate(parts, axis=1)
    order = jax.vmap(lambda k: jax.random.permutation(k, batches.shape[1]))(
        jax.random.split(keys[0], pool))
    return jnp.take_along_axis(batches, order[..., None], axis=1)


def queries(key, traffic: dict, refs, ref_spec: dict):
    """(pool, batch, query_len) float32 query batches on the device."""
    kinds = traffic["queries"]
    rows = rows_per_kind(int(traffic["batch"]), kinds)
    plan = tuple(
        (k["kind"], r, tuple(sorted(
            (p, tuple(v) if isinstance(v, list) else v)
            for p, v in k.items() if p not in ("kind", "share"))))
        for k, r in zip(kinds, rows) if r)
    return _queries(key, refs, plan=plan, pool=int(traffic["pool"]),
                    m=int(traffic["query_len"]),
                    process=ref_spec["process"])
