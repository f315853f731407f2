"""Compile every kernel plan of the main path for a TPU v5e that is
described, not attached, at the paper's shapes (512 queries x 2,000
samples against a 100,000-sample reference).

Interpret mode cannot see what the chip's compiler refuses (lane slices
not aligned to 128, blocks that break the (8, 128) tiling rule, gathers
Mosaic cannot lower, programs larger than the device memory).  These
compiles can, in a second or two each, and each must hold the Pallas
kernel as a ``tpu_custom_call`` under its stable name, which the
benchmark's trace reduction finds the kernel by.  Nothing runs: results are checked by
the interpret-mode suites and on the chip by ``chip_smoke.py``.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.spec import DPSpec
from repro.kernels import backward, ops
from repro.kernels.wavefront import (LANES, SUBLANES, query_pack_len,
                                     step_pack_len)

B, M, N = 512, 2_000, 100_000      # configs/paper_sdtw.PAPER
W = 8                              # default segment width

PLANS = {
    "hardmin": (DPSpec(), False),
    "window": (DPSpec(), True),
    "softmin": (DPSpec(reduction="softmin", gamma=0.5), False),
    "band_skip": (DPSpec(band=128), False),
    "twed": (DPSpec(family="twed"), False),
    "erp": (DPSpec(family="erp"), False),
    "local": (DPSpec(family="local"), False),
}


@pytest.fixture(scope="module")
def one_chip():
    """A single v5e chip of a described 2x2 topology.  The persistent
    compilation cache is off meanwhile: entries compiled for a described
    chip cannot be read back here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_math(monkeypatch):
    """Trace the soft-min's exp/log as the chip would (the check is made
    at trace time), with no such program left in JAX's caches after."""
    from repro.core import spec
    monkeypatch.setattr(spec, "_tpu_math", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{name}" in text
    return text


def _rows_per_step(text, name):
    """Queries per grid step of each ``name`` kernel in compiled HLO:
    the sublane extent of its first (steps, rows, LANES) output."""
    return re.findall(rf"%{name}(?:\.\d+)? = \(?f32\[\d+,(\d+),",
                      text)


@pytest.mark.parametrize("name", sorted(PLANS))
def test_wavefront_plan_compiles(one_chip, tpu_math, name):
    spec, window = PLANS[name]
    blocks = ops.ceil_to(N, LANES * W) // (LANES * W)
    q = _sds((B // SUBLANES, SUBLANES, query_pack_len(M)), one_chip)
    r = _sds((blocks, W, LANES), one_chip)
    extras = {"twed": (r,), "erp": (r, q)}.get(name, ())

    def sweep(q, r, *extras):
        return ops.sdtw_wavefront_prepped(
            q, r, batch=B, m=M, n=N, segment_width=W, interpret=False,
            spec=spec, return_window=window, extras=extras)
    text = _assert_kernel(jax.jit(sweep).lower(q, r, *extras).compile(),
                          "sdtw_wavefront")
    assert "sdtw_normalizer" not in text
    # 64 query groups: every kind takes two groups a step
    assert _rows_per_step(text, "sdtw_wavefront") == ["16"]


def test_fused_soft_backward_compiles(one_chip, tpu_math):
    """Forward+reverse sweeps and the tile fold of the soft-DTW
    gradient at 8 x 2,000 against 100,000 fit one chip."""
    spec = DPSpec(reduction="softmin", gamma=0.5)

    def loss(q, r):
        return backward.sdtw_soft_fused(q, r, spec=spec, segment_width=W,
                                        interpret=False)[0].sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _sds((8, M), one_chip), _sds((N,), one_chip)).compile()
    _assert_kernel(compiled, "sdtw_wavefront")
    text = _assert_kernel(compiled, "sdtw_wavefront_reverse")
    assert text.count("tpu_custom_call") >= 2
    # one group of 8 queries: both sweeps keep one group a step
    assert _rows_per_step(text, "sdtw_wavefront") == ["8"]
    assert _rows_per_step(text, "sdtw_wavefront_reverse") == ["8"]


def _compile_the_rules_plan(one_chip, batch, m, n, features, lanes,
                            cost_tile=False):
    """Compile a dispatch of these shapes as the rule plans it, which
    must be 16 rows in chains of ``lanes``: the kernel reads query rows
    packed for that many lanes a chain, ``rows x chains`` queries a
    grid step.  A cost-tile plan reads the MXU's bfloat16 query
    operand instead and asks the compiler for the VMEM it holds."""
    plan = ops.kernel_plan(m=m, n=n, segment_width=W, batch=batch,
                           features=features)
    assert (plan.rows_per_step, plan.lanes_per_chain, plan.cost_tile) == \
        (16, lanes, cost_tile)
    blocks = plan.num_ref_blocks
    q = _sds((batch // SUBLANES, SUBLANES, query_pack_len(m, features)),
             one_chip)
    r = _sds((blocks, W, LANES) if features == 1 else
             (blocks, features, W, LANES), one_chip)

    def sweep(q, r):
        return ops.sdtw_wavefront_prepped(q, r, batch=batch, m=m, n=n,
                                          segment_width=W, interpret=False)
    text = _assert_kernel(jax.jit(sweep).lower(q, r).compile(),
                          "sdtw_wavefront")
    steps = batch // plan.queries_per_step
    if not cost_tile:
        assert f"f32[{steps},16,{step_pack_len(m, features, lanes)}]" in text
        return
    depth, rows_t = plan.chains * plan.tile_depth, 16 * plan.tile_steps
    assert f"bf16[{steps},{depth},{rows_t}]" in text
    limit = plan.vmem_limit_bytes()
    assert plan.vmem_bytes() <= limit
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
              and "sdtw_wavefront/pallas_call" in ln]
    assert len(kernel) == 1 and f'"size":"{limit}"' in kernel[0]


@pytest.mark.parametrize("batch, m, n, features, lanes", [
    (B, M, N, 1, 16),                   # paper_batch: 8 chains of 16
    (32, 100, 7_200_000, 39, 64),       # sws2013_qbe: 2 chains of 64
])
def test_benchmark_shapes_compile_chained(one_chip, batch, m, n, features,
                                          lanes):
    """Both benchmark shapes compile as the chained plans the rule
    picks; sws2013_qbe's 39 features take the MXU's cost tile."""
    _compile_the_rules_plan(one_chip, batch, m, n, features, lanes,
                            cost_tile=features > 1)


@pytest.mark.parametrize("batch, m, features, lanes", [
    (B, 12_000, 1, LANES),      # long queries keep one chain
    (256, 500, 39, 32),         # 8 chains, or the cost tile, would outgrow
    #                             their VMEM budgets
])
def test_long_and_wide_batches_compile(one_chip, batch, m, features,
                                       lanes):
    """A long univariate batch and a wide multivariate one compile as
    the rule plans them."""
    _compile_the_rules_plan(one_chip, batch, m, N, features, lanes)


@pytest.mark.parametrize("shape", [(B, M), (1, N)])
def test_normalizer_compiles(one_chip, shape):
    compiled = jax.jit(lambda x: ops.normalize(x, interpret=False)).lower(
        _sds(shape, one_chip)).compile()
    text = _assert_kernel(compiled, "sdtw_normalizer")
    assert "wavefront" not in text


def test_multivariate_session_program_compiles(one_chip):
    """The session's whole kernel program of ``sws2013_qbe``: 32 queries
    of 100 frames x 39 features normalized, packed and swept against a
    7,200,000-frame archive, whose layout is an argument."""
    from repro.core.session import _kernel_program
    from repro.core.spec import DPSpec
    n, d = 7_200_000, 39
    blocks = ops.ceil_to(n, LANES * W) // (LANES * W)
    compiled = _kernel_program.lower(
        _sds((32, 100, d), one_chip), _sds((blocks, d, W, LANES), one_chip),
        (), spec=DPSpec(), n=n, segment_width=W, interpret=False,
        sweep=frozenset({"cost", "end"}), normalize=True).compile()
    text = _assert_kernel(compiled, "sdtw_wavefront")
    assert _rows_per_step(text, "sdtw_wavefront") == ["16"]
    # the 1.12 GB layout is a parameter, never a constant
    assert f"f32[{blocks},{d},{W},{LANES}]" in text
    assert "constant" not in "".join(
        ln for ln in text.splitlines() if f"f32[{blocks},{d}" in ln)
