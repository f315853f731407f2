"""Two query groups per serial wavefront step: a plan of 16 rows against
the plan of 8 rows of the same kind, bit-for-bit (kernel interpreted on
the CPU); the rule that picks the rows from the batch; and the fused
soft-DTW backward through the wider sweeps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec import DPSpec
from repro.kernels import backward, ops
from repro.kernels.wavefront import (LANES, SUBLANES, step_pack_len,
                                     wavefront_call)

KINDS = {
    "hardmin": (DPSpec(), False),
    "window": (DPSpec(), True),
    "softmin": (DPSpec(reduction="softmin", gamma=0.5), False),
    "band_skip": (DPSpec(band=40), False),
    "twed": (DPSpec(family="twed"), False),
    "erp": (DPSpec(family="erp"), False),
    "local": (DPSpec(family="local"), False),
}
M, W = 12, 2
N = LANES * W * 3 + 37          # four reference blocks, the last padded
BLOCKS = 4
BATCHES = (16, 24, 40, 64)      # 2, 3 (a pad group), 5 and 8 groups


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    q = rng.normal(size=(max(BATCHES), M)).astype(np.float32)
    r = rng.normal(size=N).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(r)


def _sweep(kind, q, r, rows):
    spec, window = KINDS[kind]
    plan = dataclasses.replace(
        ops.kernel_plan(spec, m=M, n=N, segment_width=W,
                        with_window=window), rows_per_step=rows)
    extras = ops.family_extras(spec, q, r, segment_width=W)
    return wavefront_call(plan, ops.prepare_queries(q),
                          ops.swizzle_reference(r, W), *extras,
                          interpret=True)


@pytest.fixture(scope="module")
def one_group(data):
    """Every kind's outputs at 8 rows a step, for the largest batch:
    each query's answer does not depend on the others in its batch."""
    q, r = data
    return {kind: _sweep(kind, q, r, SUBLANES) for kind in KINDS}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_two_groups_a_step_match_one_bit_for_bit(data, one_group, kind,
                                                 batch):
    q, r = data
    wide = _sweep(kind, q[:batch], r, 2 * SUBLANES)
    groups = batch // SUBLANES
    assert len(wide) == len(one_group[kind])
    for got, want in zip(wide, one_group[kind]):
        assert got.shape == (groups, SUBLANES)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want)[:groups])


def _pallas_grids(jaxpr):
    """(grid, query block shape) of every pallas_call in a jaxpr."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                block = gm.block_mappings[0].block_shape
                found.append((tuple(gm.grid), tuple(
                    getattr(b, "block_size", b) for b in block)))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(sub.jaxpr)
    walk(jaxpr)
    return found


@pytest.mark.parametrize("batch, rows, grid_batch, lanes", [
    (1, 8, 1, 128), (8, 8, 1, 128),        # one group: the 8-row program
    (9, 16, 1, 128),                       # two or more: two a step,
    (24, 16, 1, 64), (64, 16, 1, 32),      # in as many chains as fill
])
def test_the_batch_picks_the_rows(data, one_group, batch, rows,
                                  grid_batch, lanes):
    q, r = data
    plan = ops.kernel_plan(m=M, n=N, segment_width=W, batch=batch)
    assert (plan.rows_per_step, plan.lanes_per_chain) == (rows, lanes)
    qp = ops.prepare_queries(q[:batch])
    rl = ops.swizzle_reference(r, W)

    def sweep(qp, rl):
        return ops.sdtw_wavefront_prepped(qp, rl, batch=batch, m=M, n=N,
                                          segment_width=W, interpret=True)
    grids = _pallas_grids(jax.make_jaxpr(sweep)(qp, rl).jaxpr)
    assert grids == [((grid_batch, BLOCKS),
                      (1, rows, step_pack_len(M, 1, lanes)))]
    # the public path answers as the one-group sweep does
    costs, ends = sweep(qp, rl)
    want_c, want_e = (np.asarray(x).reshape(-1)[:batch]
                      for x in one_group["hardmin"])
    np.testing.assert_array_equal(np.asarray(costs), want_c)
    np.testing.assert_array_equal(np.asarray(ends), np.minimum(want_e,
                                                               N - 1))


def test_fused_soft_backward_unchanged_by_two_groups(data, monkeypatch):
    """The checkpointed forward and reverse sweeps take two groups a
    step at 16 queries; the cost and both gradients are those of the
    sweeps at one group a step, bit-for-bit."""
    q, r = data
    q = q[:16]
    spec = DPSpec(reduction="softmin", gamma=0.5)

    def loss(q, r):
        return backward.sdtw_soft_fused(q, r, spec=spec, segment_width=W,
                                        interpret=True)[0].sum()
    value_and_grad = jax.value_and_grad(loss, argnums=(0, 1))

    def run():
        jax.clear_caches()        # the sweeps' jit traces the rule anew
        grids = _pallas_grids(jax.make_jaxpr(value_and_grad)(q, r).jaxpr)
        return grids, value_and_grad(q, r)

    try:
        wide_grids, wide = run()
        with monkeypatch.context() as mp:
            mp.setattr(ops, "plan_rows", lambda plan, batch: plan)
            narrow_grids, narrow = run()
    finally:
        jax.clear_caches()
    assert [g for _, (_, g, _) in wide_grids] == [16, 16]
    assert [g for _, (_, g, _) in narrow_grids] == [8, 8]
    for got, want in zip(jax.tree.leaves(wide), jax.tree.leaves(narrow)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
