"""Independent lane chains in the wavefront step: plans of 2, 4 and 8
chains against the one-chain plan of the same kind, bit for bit (kernel
interpreted on the CPU), ties between columns that different chains'
sub-blocks hold included; the rule that picks the chains; and the
plans that must keep one chain."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec import DPSpec
from repro.kernels import ops
from repro.kernels.wavefront import (LANES, SUBLANES, KernelPlan,
                                     step_pack_len, wavefront_call)

KINDS = {
    "hardmin": (DPSpec(), False, 1),
    "window": (DPSpec(), True, 1),
    "softmin": (DPSpec(reduction="softmin", gamma=0.5), False, 1),
    "band_skip": (DPSpec(band=300), False, 1),
    "twed": (DPSpec(family="twed"), False, 1),
    "erp": (DPSpec(family="erp"), False, 1),
    "local": (DPSpec(family="local"), False, 1),
    "multivariate": (DPSpec(), True, 3),
}
B, M, N, W = 32, 12, 3_000, 2
# (rows a chain, lanes a chain): 32 queries in one grid step at 2 and 4
# chains; 8 chains take a step of 64, half of it pad queries
CHAINED = ((2 * SUBLANES, 64), (SUBLANES, 32), (SUBLANES, 16))
# planted copies of query 0's rows: columns 700 and 1,300 end exact
# matches (cost 0), in different sub-blocks of different blocks
TIE_ENDS = (700 + M - 1, 1_300 + M - 1)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(16)
    out = {}
    for d in (1, 3):
        shape = (B, M) if d == 1 else (B, M, d)
        q = rng.normal(size=shape).astype(np.float32)
        r = rng.normal(size=(N,) if d == 1 else (N, d)).astype(np.float32)
        for start in (s - M + 1 for s in TIE_ENDS):
            r[start:start + M] = q[0]
        # the same query in every chain of every chained plan, and in
        # both sublane halves: ties between chains' lanes
        for b in (8, 16, 24, 5):
            q[b] = q[0]
        out[d] = (jnp.asarray(q), jnp.asarray(r))
    return out


def _sweep(kind, data, rows, lanes):
    spec, window, d = KINDS[kind]
    q, r = data[d]
    plan = dataclasses.replace(
        ops.kernel_plan(spec, m=M, n=N, segment_width=W,
                        with_window=window, features=d),
        rows_per_step=rows, lanes_per_chain=lanes)
    extras = ops.family_extras(spec, q, r, segment_width=W)
    out = wavefront_call(plan, ops.prepare_queries(q),
                         ops.swizzle_reference(r, W), *extras,
                         interpret=True)
    return [np.asarray(x) for x in out]


@pytest.fixture(scope="module")
def one_chain(data):
    return {kind: _sweep(kind, data, 2 * SUBLANES, LANES) for kind in KINDS}


@pytest.mark.parametrize("rows, lanes", CHAINED)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_chains_match_one_chain_bit_for_bit(data, one_chain, kind, rows,
                                            lanes):
    got = _sweep(kind, data, rows, lanes)
    assert len(got) == len(one_chain[kind])
    for g, want in zip(got, one_chain[kind]):
        assert g.shape == (B // SUBLANES, SUBLANES)
        np.testing.assert_array_equal(g, want)


@pytest.mark.parametrize("kind", ["hardmin", "window", "multivariate"])
def test_ties_between_chains_keep_the_smallest_column(one_chain, kind):
    """Query 0 matches exactly at two end columns, which lanes of
    different sub-blocks hold; its copies sit in every chain.  Every
    copy reports cost 0 and the earlier column, as the one-chain plan
    does (the chained plans are held to it above)."""
    costs, ends = one_chain[kind][0].ravel(), one_chain[kind][-1].ravel()
    for b in (0, 5, 8, 16, 24):
        assert costs[b] == 0.0
        assert ends[b] == TIE_ENDS[0]
    if KINDS[kind][1]:                       # starts of the window plans
        starts = one_chain[kind][1].ravel()
        assert all(starts[b] == TIE_ENDS[0] - M + 1
                   for b in (0, 5, 8, 16, 24))


@pytest.mark.parametrize("batch, m, features, rows, lanes", [
    (512, 2_000, 1, 16, 16),      # the paper's batch: 8 chains of 16
    (32, 100, 39, 16, 64),        # sws2013_qbe's terms: 2 chains of 64
    (64, 60, 1, 16, 32),          # 4 chains fill 64 queries exactly
    (24, 20, 1, 16, 64),          # 24 rounds up to 32: 2 chains
    (16, 20, 1, 16, 128),         # 2 chains of 16 would pad 16 queries
    (9, 20, 1, 16, 128),
    (2, 20, 1, 8, 128),           # a search round's 2 rows: one chain
    (512, 2_700, 1, 16, 16),      # 8 chains cut the steps 4.0%
    (512, 2_800, 1, 16, 128),     # 3.98%: no chain pays for its step
    (512, 12_000, 1, 16, 128),    # 0.9%
    (256, 500, 39, 16, 32),       # 8 chains would hold 23 MB of VMEM
    (256, 2_000, 64, 16, 128),    # 2 chains would hold 38 MB
])
def test_the_batch_picks_the_chains(batch, m, features, rows, lanes):
    plan = ops.kernel_plan(m=m, n=100_000, segment_width=8, batch=batch,
                           features=features)
    assert (plan.rows_per_step, plan.lanes_per_chain) == (rows, lanes)
    assert plan.chains * plan.lanes_per_chain == LANES
    if plan.chains > 1:     # chains are picked before the cost tile
        vpu = dataclasses.replace(plan, cost_tile=False)
        assert vpu.vmem_bytes() <= ops.CHAIN_VMEM_BUDGET
    if plan.cost_tile:
        assert plan.vmem_bytes() <= ops.COST_TILE_VMEM_BUDGET


@pytest.mark.parametrize("m, features, lanes, want", [
    # one chain: 16 rows x 2,382 lanes of query pack, 8 x 128 of the
    # reference and 16 x 128 of each of 2 outputs, double-buffered;
    # then 16 x 16 tiles of strip and 16 x 128 of each of 2
    # accumulators
    (2_000, 1, LANES, 4 * (2 * (16 * 2_382 + 8 * 128 + 2 * 16 * 128)
                           + 16 * 2_048 + 2 * 16 * 128)),
    # 8 chains of 16 lanes: 128 tiles of query pack, 125 of strip, the
    # accumulators of 8 chains and the sub-block's reference tile
    (2_000, 1, 16, 4 * (2 * (16 * 16_384 + 8 * 128 + 2 * 16 * 128)
                        + 16 * 16_000 + 2 * 8 * 16 * 128 + 8 * 128)),
])
def test_vmem_bytes_by_hand(m, features, lanes, want):
    plan = dataclasses.replace(
        ops.kernel_plan(m=m, n=100_000, segment_width=8, features=features),
        rows_per_step=2 * SUBLANES, lanes_per_chain=lanes)
    assert plan.vmem_bytes() == want


def test_reverse_and_checkpoint_sweeps_keep_one_chain():
    spec = DPSpec(reduction="softmin", gamma=0.5)
    plan = ops.kernel_plan(spec, m=20, n=5_000, segment_width=2,
                           checkpoint=True, batch=512)
    assert (plan.rows_per_step, plan.lanes_per_chain) == (16, LANES)
    rev = ops.plan_rows(dataclasses.replace(plan, reverse=True), 512)
    assert rev.lanes_per_chain == LANES
    for flag in ("reverse", "checkpoint"):
        with pytest.raises(ValueError, match="one chain"):
            dataclasses.replace(plan, lanes_per_chain=32, **{flag: True})
    with pytest.raises(ValueError, match="lanes_per_chain"):
        dataclasses.replace(plan, checkpoint=False, lanes_per_chain=48)


@pytest.mark.parametrize("m, features, lanes, want", [
    (12, 1, 64, 4 * LANES),       # ceil((12 + 190) / 64) tiles
    (100, 39, 64, 39 * 5 * LANES),
    (2_000, 1, 16, 128 * LANES),  # ceil((2000 + 46) / 16) tiles
    (2_000, 1, LANES, 2_000 + 3 * LANES - 2),  # one chain: the prepared row
])
def test_step_pack_len(m, features, lanes, want):
    assert step_pack_len(m, features, lanes) == want
    plan = KernelPlan(spec=DPSpec(), m=m, segment_width=2,
                      num_ref_blocks=1, features=features,
                      lanes_per_chain=lanes)
    assert plan.queries_per_step == SUBLANES * LANES // lanes
