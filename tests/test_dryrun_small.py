"""Dry-run machinery on a small fake-device mesh (subprocess so the
XLA device-count flag never leaks into other tests), plus hlo_cost
unit checks that run in-process."""

import json
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


def test_dryrun_cell_small_mesh():
    """End-to-end dry-run of one smoke-config cell on a 2x2 fake mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import dataclasses
from repro import configs
from repro.launch import specs as S
from repro.utils import roofline as R

mesh = jax.make_mesh((2, 2), ("data", "model"))

# monkeypatch the registry to the smoke config so this compiles fast
import repro.configs as C
smoke = C.get_smoke("gemma3_27b")
C._module("gemma3_27b").CONFIG = smoke

# shrink the shape too
C.SHAPES = dict(C.SHAPES)
C.SHAPES["train_4k"] = dataclasses.replace(
    C.SHAPES["train_4k"], seq_len=64, global_batch=4)

cell = S.build_cell("gemma3_27b", "train_4k", mesh)
fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
             out_shardings=cell.out_shardings)
compiled = fn.lower(*cell.args).compile()
r = R.from_compiled(compiled, arch="gemma3_27b", shape="train_4k",
                    mesh_desc="2x2", chips=4, model_flops=cell.model_flops)
assert r.hlo_flops > 0 and r.hlo_bytes > 0
assert r.bottleneck in ("compute", "memory", "collective")
print("OK", json.dumps({"flops": r.hlo_flops, "bn": r.bottleneck}))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
