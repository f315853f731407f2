"""Aligner session contract: precompiled executables, zero warm
retraces, correct cache keying, and parity with the one-shot front
door.

The trace counter is a Python side effect inside the jitted closure,
so it only ticks while JAX is tracing — a warm (same shape, same
outputs) call that left it unchanged provably did not retrace.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.api import sdtw
from repro.core.normalize import normalize_batch, normalize_reference
from repro.core.spec import DPSpec
from repro.data.cbf import make_cylinder_bell_funnel

B, M, N = 4, 16, 120


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    q = jnp.asarray(make_cylinder_bell_funnel(rng, B, M))
    r = jnp.asarray(make_cylinder_bell_funnel(rng, 1, N)[0])
    return q, r


# --------------------------------------------------------- trace count
@pytest.mark.parametrize("backend", ["engine", "kernel"])
def test_warm_calls_do_not_retrace(data, backend):
    """Acceptance: the second same-shape call is dispatch-only (zero
    retraces) on both the engine and kernel backends; a new batch shape
    or outputs set compiles exactly ONE new executable."""
    q, r = data
    a = repro.Aligner(r, backend=backend, segment_width=2)
    a(q)
    assert (a.stats.calls, a.stats.compiles, a.stats.traces,
            a.stats.cache_hits) == (1, 1, 1, 0)
    res = a(q)                                  # warm: NO retrace
    assert (a.stats.calls, a.stats.compiles, a.stats.traces,
            a.stats.cache_hits) == (2, 1, 1, 1)
    a(q)                                        # still warm
    assert a.stats.traces == 1 and a.stats.compiles == 1
    a(q[:2])                                    # new batch shape
    assert (a.stats.compiles, a.stats.traces) == (2, 2)
    a(q, outputs=("cost", "start", "end"))      # new outputs set
    assert (a.stats.compiles, a.stats.traces) == (3, 3)
    a(q, outputs=("cost", "start", "end"))      # warm again
    a(q[:2])
    assert (a.stats.compiles, a.stats.traces) == (3, 3)
    assert a.executables() == 3
    assert res.present == frozenset({"cost", "end"})


def test_outputs_hint_steers_auto_selection(data, monkeypatch):
    """On TPU auto-selection prefers the kernel; an outputs hint the
    preferred backend cannot serve must steer a backend=None session
    to one that can — and the kernel's fused reverse-sweep backward
    means soft_alignment is no longer such a hint."""
    from repro.backends import registry
    _, r = data
    monkeypatch.setattr(registry, "_device_default", lambda: "tpu")
    plain = repro.Aligner(r, gamma=0.5)
    assert plain.backend.name == "kernel"
    # soft_alignment stays on the kernel: the fused forward+reverse
    # pair serves it directly
    hinted = repro.Aligner(r, gamma=0.5, outputs=("cost",
                                                  "soft_alignment"))
    assert hinted.backend.name == "kernel"
    # a hint the kernel genuinely cannot serve (cosine distance) still
    # steers; a named backend + impossible hint fails at construction
    steered = repro.Aligner(r, distance="cosine")
    assert steered.backend.name == "engine"
    with pytest.raises(ValueError, match="start"):
        repro.Aligner(r, backend="quantized",
                      outputs=("cost", "start", "end"))


def test_outputs_key_is_order_insensitive(data):
    q, r = data
    a = repro.Aligner(r, backend="engine")
    a(q, outputs=("cost", "end", "start"))
    a(q, outputs=("start", "cost", "end"))      # same frozenset -> warm
    assert a.stats.compiles == 1 and a.stats.cache_hits == 1


# ------------------------------------------------------------- parity
def _batch(batch, features, seed=3):
    """``batch`` queries and one reference; feature inputs are (B, M, D)
    and (N, D) random walks."""
    rng = np.random.default_rng(seed)
    if features == 1:
        return (jnp.asarray(make_cylinder_bell_funnel(rng, batch, M)),
                jnp.asarray(make_cylinder_bell_funnel(rng, 1, N)[0]))
    walk = lambda shape: jnp.asarray(np.cumsum(
        rng.normal(size=shape), axis=-2).astype(np.float32))
    return walk((batch, M, features)), walk((N, features))


@pytest.mark.parametrize("backend, batch, features", [
    pytest.param("ref", B, 1, id="ref"),
    pytest.param("engine", B, 1, id="engine"),
    pytest.param("kernel", B, 1, id="kernel"),
    # the batches where ops.plan_rows changes its rows or chains:
    # 8 rows; 16 rows; 2, 2 and 4 chains of 16 rows
    *[pytest.param("kernel", b, 1, id=f"kernel-batch{b}")
      for b in (2, 16, 24, 32, 64)],
    *[pytest.param(be, B, d, id=f"{be}-features{d}")
      for be in ("ref", "kernel") for d in (2, 39)],
    pytest.param("kernel", 24, 39, id="kernel-batch24-features39"),
])
def test_session_equals_front_door_bit_for_bit(data, backend, batch,
                                               features):
    """A normalize=False session contains exactly the sweep, so its
    numbers equal the eager dispatch path bit for bit — the session's
    own kernel program and the registry's kernel route alike, at every
    rows-and-chains plan and on feature inputs."""
    q, r = data if (batch, features) == (B, 1) else _batch(batch, features)
    qn = normalize_batch(q)
    rn = normalize_reference(r)
    a = repro.Aligner(rn, backend=backend, normalize=False,
                      segment_width=2)
    res = a(qn, outputs=("cost", "start", "end"))
    want = sdtw(q, r, backend=backend, outputs=("cost", "start", "end"),
                segment_width=2)
    for name in ("cost", "start", "end"):
        np.testing.assert_array_equal(np.asarray(getattr(res, name)),
                                      np.asarray(getattr(want, name)))


def test_normalizing_session_close_to_front_door(data):
    """normalize=True sessions fuse query normalization into the
    executable — same math, fusion may differ in the last ulp."""
    q, r = data
    a = repro.Aligner(r, backend="kernel", segment_width=2)
    res = a(q)
    want = sdtw(q, r, backend="kernel", segment_width=2)
    np.testing.assert_allclose(np.asarray(res.cost),
                               np.asarray(want.cost), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(res.end),
                                  np.asarray(want.end))


def test_quantized_session(data):
    q, r = data
    a = repro.Aligner(r, backend="quantized")
    res = a(q)
    want = sdtw(q, r, backend="quantized")
    np.testing.assert_allclose(np.asarray(res.cost),
                               np.asarray(want.cost), rtol=1e-5)


# ------------------------------------------------- derived + validation
def test_session_derived_outputs(data):
    q, r = data
    a = repro.Aligner(r, backend="engine")
    res = a(q, outputs=("cost", "path"))
    assert len(res.path) == B and res.start is None
    want = sdtw(q, r, backend="engine", outputs=("path",))
    for got, exp in zip(res.path, want.path):
        np.testing.assert_array_equal(got, exp)

    soft = repro.Aligner(r, spec=DPSpec(reduction="softmin", gamma=0.5),
                         backend="engine")
    rs = soft(q, outputs=("cost", "soft_alignment"))
    ws = sdtw(q, r, backend="engine",
              spec=DPSpec(reduction="softmin", gamma=0.5),
              outputs=("cost", "soft_alignment"))
    np.testing.assert_allclose(np.asarray(rs.soft_alignment),
                               np.asarray(ws.soft_alignment),
                               rtol=1e-5, atol=1e-7)
    # soft_alignment-only session requests skip the sweep (no
    # executable is built) but still validate + derive
    only = soft(q, outputs=("soft_alignment",))
    assert only.present == frozenset({"soft_alignment"})
    assert soft.executables() == 1      # just the ("cost", ...) sweep
    np.testing.assert_array_equal(np.asarray(only.soft_alignment),
                                  np.asarray(rs.soft_alignment))


def test_session_capability_errors(data):
    q, r = data
    a = repro.Aligner(r, backend="quantized")
    with pytest.raises(ValueError, match=r"output\(s\) \['start'\]"):
        a(q, outputs=("cost", "start"))
    soft = repro.Aligner(r, spec=DPSpec(reduction="softmin"))
    with pytest.raises(ValueError, match="soft-min"):
        soft(q, outputs=("start",))
    with pytest.raises(ValueError, match="unknown output"):
        a(q, outputs=("cost", "bogus"))
    with pytest.raises(ValueError, match="1-D"):
        repro.Aligner(np.zeros((2, 8, 3), np.float32))
    with pytest.raises(ValueError, match="empty"):
        repro.Aligner(np.zeros((0,), np.float32))


def test_distributed_session_stats_stay_eager(data):
    """The distributed strategy dispatches to the backend's own cached
    shard_map pipeline — the session builds no executable, so its
    trace/compile counters must stay at zero (the AlignerStats
    contract) while calls/hits still count."""
    import jax
    from jax.sharding import Mesh
    q, r = data
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    a = repro.Aligner(r, backend="distributed",
                      options={"mesh": mesh, "row_block": 8})
    res = a(q)
    res2 = a(q)
    assert (a.stats.calls, a.stats.cache_hits) == (2, 1)
    assert (a.stats.compiles, a.stats.traces) == (0, 0)
    assert a.executables() == 0
    want = sdtw(q, r, backend="distributed",
                options={"mesh": mesh, "row_block": 8})
    np.testing.assert_array_equal(np.asarray(res.cost),
                                  np.asarray(want.cost))
    np.testing.assert_array_equal(np.asarray(res2.end),
                                  np.asarray(want.end))


def test_executable_cache_is_lru_bounded(data):
    """Past ``max_executables`` the oldest executable is evicted —
    stats.evictions and the aligner.evictions counter tick — and the
    evicted key recompiles on its next use."""
    from repro import obs
    q, r = data
    metrics = obs.MetricsRegistry()
    a = repro.Aligner(r, backend="engine", max_executables=2,
                      metrics=metrics)
    a(q)                                        # key A
    a(q[:3])                                    # key B
    assert a.executables() == 2 and a.stats.evictions == 0
    a(q[:2])                                    # key C evicts A
    assert a.executables() == 2 and a.stats.evictions == 1
    assert metrics.snapshot()["aligner.evictions"]["value"] == 1
    # B and C are resident (warm), A was evicted and recompiles
    compiles = a.stats.compiles
    a(q[:3])
    a(q[:2])
    assert a.stats.compiles == compiles
    a(q)                                        # A again: cold
    assert a.stats.compiles == compiles + 1
    assert a.stats.evictions == 2               # ... evicting B

    # a warm hit refreshes recency: touching C then adding a new key
    # must evict A (least recently used), not C
    a(q[:2])                                    # refresh C
    a(q[:1])                                    # new key D evicts A
    evs = a.stats.evictions
    compiles = a.stats.compiles
    a(q[:2])                                    # C still resident
    assert a.stats.compiles == compiles and a.stats.evictions == evs

    with pytest.raises(ValueError, match="max_executables"):
        repro.Aligner(r, max_executables=0)


def test_layout_cache_shared(data):
    """The kernel session reuses a caller-provided swizzled-layout dict
    (the ReferenceIndex integration) instead of re-swizzling."""
    from repro.kernels import ops as _ops
    q, r = data
    rn = normalize_batch(r)
    cache = {}
    a = repro.Aligner(rn, backend="kernel", normalize=False,
                      segment_width=2, layout_cache=cache)
    a(normalize_batch(q))
    key = (2, "float32")
    assert key in cache
    np.testing.assert_array_equal(
        np.asarray(cache[key]),
        np.asarray(_ops.swizzle_reference(rn.astype(jnp.float32), 2)))
    # second session over the same cache does not re-swizzle
    marker = cache[key]
    b = repro.Aligner(rn, backend="kernel", normalize=False,
                      segment_width=2, layout_cache=cache)
    b(normalize_batch(q))
    assert cache[key] is marker
    # a cache accidentally shared across DIFFERENT references must
    # fail loudly, not sweep against the wrong series
    other = normalize_batch(jnp.asarray(
        np.random.default_rng(3).normal(size=(N,)).astype(np.float32)))
    wrong = repro.Aligner(other, backend="kernel", normalize=False,
                          segment_width=2, layout_cache=cache)
    with pytest.raises(ValueError, match="per-reference"):
        wrong(normalize_batch(q))
