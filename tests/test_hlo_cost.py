"""hlo_cost: loop-aware FLOP and collective-byte counts from compiled
HLO text.  The collective check needs two fake devices, so it runs in
a subprocess and the XLA device-count flag never leaks into other
tests."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax import lax

from repro.utils import hlo_cost

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_hlo_cost_scales_while_loops():
    A = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scan10(a, b):
        return lax.scan(lambda x, _: (x @ b, None), a, None, length=10)[0]

    c = jax.jit(scan10).lower(A, A).compile()
    got = hlo_cost.analyze(c.as_text())
    expect = 10 * 2 * 128 ** 3
    assert abs(got.flops - expect) / expect < 0.02, (got.flops, expect)


def test_hlo_cost_counts_collectives_inside_loops():
    # needs >= 2 fake devices -> subprocess
    code = r"""
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from repro.utils import hlo_cost
mesh = jax.make_mesh((2,), ("x",))
def f(a):
    def body(c, _):
        # carry must change or XLA hoists the loop-invariant psum
        return c + 1.0, lax.psum(c, "x")   # one all-reduce per iteration
    _, ys = lax.scan(body, a, None, length=5)
    return ys[-1]
g = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
c = jax.jit(g).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
got = hlo_cost.analyze(c.as_text())
# 5 iterations x (4*128 rows local) x 4B x2 (all-reduce) = 2*5*4*128*4
expect = 2 * 5 * 4 * 128 * 4
assert abs(got.coll_bytes - expect) / expect < 0.5, (got.coll_bytes, expect)
print("OK", got.coll_bytes)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
