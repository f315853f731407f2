"""Fused reverse-sweep soft-DTW backward (repro.kernels.backward).

The acceptance contract of the tentpole: the kernel backend's
custom_vjp cost gradients and E-matrix must match the engine oracle
(``jax.grad`` straight through the cost-matrix sweep) across
gamma x band x multi-block N, the reverse sweep's own cost readout
must reproduce the forward cost, E must converge to the hard path
indicator as gamma -> 0, the training-loss helper must give identical
gradients on both backends — and the fused gradient path must never
materialize an O(M*N) buffer (checked on the jaxpr itself).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.align.oracle import oracle_path
from repro.align.soft import _expected_alignment_jit, cost_matrix
from repro.core.engine import sdtw_engine
from repro.core.spec import DPSpec
from repro.kernels import backward as kb

B, M, N = 3, 20, 600          # w=2 -> W=256: N spans 3 kernel blocks
SEG = 2


def _spec(gamma, band=None):
    return DPSpec(reduction="softmin", gamma=gamma, band=band)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, M)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    return q, r


# gamma x band x (multi-block N): the satellite's parity matrix.
# band=40 keeps only the first kernel block alive (band-skip exercises
# the reverse grid's leading-block offset); band=None runs all three.
MATRIX = [(g, band) for g in (0.01, 0.1, 1.0) for band in (None, 40)]


@pytest.mark.parametrize("gamma,band", MATRIX,
                         ids=[f"g{g}-band{b}" for g, b in MATRIX])
def test_grad_and_e_parity(data, gamma, band):
    q, r = data
    spec = _spec(gamma, band)

    def loss_fused(qq, rr):
        return kb.sdtw_soft_fused(qq, rr, spec=spec, segment_width=SEG,
                                  interpret=True)[0].sum()

    def loss_engine(qq, rr):
        return sdtw_engine(qq, rr, spec=spec, return_end=False).sum()

    cf, ce = loss_fused(q, r), loss_engine(q, r)
    np.testing.assert_allclose(float(cf), float(ce), rtol=1e-5, atol=1e-5)
    gq_f, gr_f = jax.grad(loss_fused, argnums=(0, 1))(q, r)
    gq_e, gr_e = jax.grad(loss_engine, argnums=(0, 1))(q, r)
    np.testing.assert_allclose(np.asarray(gq_f), np.asarray(gq_e),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gr_f), np.asarray(gr_e),
                               rtol=1e-4, atol=1e-4)

    _, _, E = kb.soft_alignment_fused(q, r, spec=spec, segment_width=SEG,
                                      interpret=True)
    E_oracle = _expected_alignment_jit(cost_matrix(q, r, spec), spec=spec)
    np.testing.assert_allclose(np.asarray(E), np.asarray(E_oracle),
                               rtol=1e-4, atol=1e-4)


def test_reverse_sweep_cost_parity(data):
    """The reverse recurrence's own bottom-row readout recomputes the
    total soft cost — the free consistency check on the B matrix."""
    q, r = data
    for gamma, band in ((1.0, None), (0.1, 40)):
        cost, _, rcost, _, _ = kb._checkpoint_sweeps(
            q, r, spec=_spec(gamma, band), segment_width=SEG,
            interpret=True)
        np.testing.assert_allclose(np.asarray(cost[:B]),
                                   np.asarray(rcost[:B]),
                                   rtol=1e-5, atol=1e-5)


def test_e_converges_to_hard_path(data):
    """gamma -> 0: the fused E concentrates on the hard optimal path."""
    q, r = data
    _, _, E = kb.soft_alignment_fused(q, r, spec=_spec(1e-3),
                                      segment_width=SEG, interpret=True)
    E = np.asarray(E)
    for b in range(B):
        path = oracle_path(np.asarray(q)[b], np.asarray(r))
        assert (E[b][path[:, 0], path[:, 1]] > 0.9).all()


def test_statically_blocked_band_zero_grads(data):
    """M - 1 - band > N - 1: no alignment exists — inf cost, zero
    gradients, zero E, no kernel dispatch."""
    q = jnp.asarray(np.random.default_rng(0).normal(size=(2, 20)),
                    jnp.float32)
    r = jnp.asarray(np.random.default_rng(1).normal(size=(8,)),
                    jnp.float32)
    spec = _spec(0.5, band=4)
    cost, end = kb.sdtw_soft_fused(q, r, spec=spec, segment_width=SEG,
                                   interpret=True)
    assert np.isinf(np.asarray(cost)).all()
    g = jax.grad(lambda qq: kb.sdtw_soft_fused(
        qq, r, spec=spec, segment_width=SEG, interpret=True)[0].sum())(q)
    assert (np.asarray(g) == 0).all()
    _, _, E = kb.soft_alignment_fused(q, r, spec=spec, segment_width=SEG,
                                      interpret=True)
    assert E.shape == (2, 20, 8) and (np.asarray(E) == 0).all()


@pytest.mark.parametrize("band", [None, 8])
@pytest.mark.parametrize("reduce", ["mean", "sum", "none"])
@pytest.mark.parametrize("backend", ["ref", "engine", "kernel"])
def test_train_loss_grad_equivalence(data, backend, reduce, band):
    """make_sdtw_loss promotes its spec to soft-min, reduces as asked,
    and differentiates identically on every backend — through the fused
    kernel backward on the kernel — against the engine's soft-min cost
    through the front door, normalization chain included."""
    import repro
    from repro.train import make_sdtw_loss
    q, r = data
    kw = {"segment_width": SEG, "interpret": True} \
        if backend == "kernel" else {}
    loss = make_sdtw_loss(r, gamma=0.5, band=band, backend=backend,
                          reduce=reduce, **kw)
    reducer = {"mean": jnp.mean, "sum": jnp.sum,
               "none": lambda c: c}[reduce]

    def want(x):
        return reducer(repro.sdtw(x, r, spec=_spec(0.5, band=band),
                                  backend="engine",
                                  outputs=("cost",)).cost)

    got = loss(q)
    assert got.shape == (() if reduce != "none" else (B,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want(q)),
                               rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda x: loss(x).sum())(q)
    ge = jax.grad(lambda x: want(x).sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ge),
                               rtol=1e-4, atol=1e-4)


def test_train_loss_rejects_unknown_reduce(data):
    from repro.train import make_sdtw_loss
    with pytest.raises(ValueError, match="reduce must be"):
        make_sdtw_loss(data[1], reduce="max")


# --------------------------------------------------- memory guarantee
def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for leaf in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(leaf, "jaxpr", leaf)
                if hasattr(inner, "eqns"):
                    yield from _iter_jaxprs(inner)


def _max_buffer_elems(fn, *args):
    """Largest intermediate buffer (in elements) anywhere in the traced
    computation, sub-jaxprs included."""
    closed = jax.make_jaxpr(fn)(*args)
    best = 0
    for jx in _iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                shape = getattr(getattr(v, "aval", None), "shape", None)
                if shape is not None:
                    best = max(best, int(np.prod(shape, dtype=int)))
    return best


def test_fused_grad_never_materializes_mn(data):
    """The tentpole's memory contract: the fused gradient path holds
    tiles and boundary strips only — no buffer reaches B*M*N elements —
    while the grad-through-engine oracle necessarily materializes one."""
    q, r = data
    spec = _spec(0.5)

    def grad_fused(qq):
        return jax.grad(lambda x: kb.sdtw_soft_fused(
            x, r, spec=spec, segment_width=SEG,
            interpret=True)[0].sum())(qq)

    def grad_engine(qq):
        C = cost_matrix(qq, r, spec)
        return jax.grad(lambda x: sdtw_engine(
            x, r, spec=spec, return_end=False).sum())(qq), C

    mn = B * M * N
    fused_peak = _max_buffer_elems(grad_fused, q)
    assert fused_peak < mn, (fused_peak, mn)
    engine_peak = _max_buffer_elems(lambda qq: grad_engine(qq)[1], q)
    assert engine_peak >= mn, (engine_peak, mn)


# ------------------------------------------- soft-min exp / log on TPU
@pytest.fixture
def tpu_math(monkeypatch):
    """Trace the soft-min's exp/log as for a TPU (the accurate float32
    pair), with no program traced otherwise left in JAX's caches."""
    from repro.core import spec
    monkeypatch.setattr(spec, "_tpu_math", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_tpu_exp_log_accuracy():
    from repro.core import spec
    for lo, hi in [(-87, -5), (-5, 0), (0, 5)]:
        x = np.linspace(lo, hi, 4096, dtype=np.float32)
        got = np.asarray(spec._exp_f32(jnp.asarray(x)), np.float64)
        want = np.exp(x.astype(np.float64))
        assert np.max(np.abs(got - want) / want) < 2e-7
    edge = spec._exp_f32(jnp.asarray([-np.inf, -100.0, 0.0], jnp.float32))
    assert np.asarray(edge).tolist() == [0.0, 0.0, 1.0]
    s = np.linspace(1, 3, 4096, dtype=np.float32)
    got = np.asarray(spec._log_f32(jnp.asarray(s)), np.float64)
    assert np.max(np.abs(got - np.log(s.astype(np.float64)))) < 3e-7


def test_tpu_math_grad_parity(data, tpu_math):
    """With the soft-min traced as for a TPU, the fused kernel gradient
    still matches the gradient through the engine."""
    q, r = data
    spec = _spec(0.5)
    gk = jax.grad(lambda q: kb.sdtw_soft_fused(
        q, r, spec=spec, segment_width=SEG, interpret=True)[0].sum())(q)
    ge = jax.grad(lambda q: sdtw_engine(q, r, spec=spec)[0].sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ge),
                               rtol=1e-4, atol=1e-4)
