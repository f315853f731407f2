"""Compile the VPU-ceiling microkernel for a TPU v5e that is described,
not attached, at the sizes ``vpu_ceiling.measure`` runs: the chip's
compiler refuses here what interpret mode cannot see."""
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import vpu_ceiling


@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip of a described 2x2 topology, with the persistent
    compilation cache off meanwhile (entries compiled for a described
    chip cannot be read back here)."""
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


@pytest.mark.parametrize("chains", vpu_ceiling.CHAIN_COUNTS)
def test_vpu_ceiling_compiles(one_chip, chains):
    fn = vpu_ceiling.ceiling_call(steps=32, chains=chains, iters=2_000_000)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = fn.lower(sds((32, chains, 8, 128)), sds((8, 128)),
                        sds((8, 128))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vpu_ceiling_counts_its_operations():
    fn = vpu_ceiling.ceiling_call(steps=2, chains=3, iters=16,
                                  interpret=True)
    x = jnp.zeros((2, 3, 8, 128))
    out = fn(x, jnp.full((8, 128), 0.25), jnp.full((8, 128), 2.5))
    # each chain climbs by 0.25 a round and stops at 2.5
    assert float(out[0, 0, 0]) == pytest.approx(3 * 2.5)
    assert vpu_ceiling.ops(2, 3, 16) == 2 * 3 * 16 * 2 * 1024
    with pytest.raises(ValueError):
        vpu_ceiling.ceiling_call(steps=1, chains=1, iters=12)
