import os
if "--prod-mesh" in __import__("sys").argv:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

"""Roofline analysis of the paper's OWN workload (512 queries x 2,000 vs
a 100,000-sample reference) on the TPU target — §Perf part 2.

Three implementations are compared at the compiled-HLO level:

  1. `engine`   — the anti-diagonal XLA engine on ONE chip (the paper's
                  wavefront at HLO level; paper-faithful baseline).
  2. `pipeline` — the distributed engine on the production 16x16 mesh
                  (queries over 'data', reference over 'model' with the
                  ppermute boundary pipeline), sweeping row_block.
  3. `kernel`   — the Pallas wavefront kernel: VMEM-resident DP, HBM
                  traffic = inputs + outputs only (analytic VMEM model +
                  interpret-mode validation; Pallas->Mosaic does not
                  compile on the CPU backend).

  PYTHONPATH=src python -m benchmarks.sdtw_roofline              # 1 chip
  PYTHONPATH=src python -m benchmarks.sdtw_roofline --prod-mesh  # 16x16
"""

import argparse
import functools

import jax
import jax.numpy as jnp

from repro.utils import hlo_cost
from repro.utils.roofline import HBM_BW, LINK_BW, PEAK_FLOPS

B, M, N = 512, 2000, 100_000
CELLS = B * M * N
USEFUL_FLOPS = 5.0 * CELLS          # 3-way min (2) + sub + mul + add


def report(tag, c: hlo_cost.Cost, chips: int):
    t_c = c.flops * chips / (chips * PEAK_FLOPS)
    t_m = c.bytes * chips / (chips * HBM_BW)
    t_x = c.coll_bytes * chips / (chips * LINK_BW)
    step = max(t_c, t_m, t_x)
    frac = USEFUL_FLOPS / (chips * PEAK_FLOPS) / step if step else 0.0
    bound = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                key=lambda kv: kv[1])[0]
    print(f"{tag:28s} chips={chips:<4d} t_comp={t_c:.3e} t_mem={t_m:.3e} "
          f"t_coll={t_x:.3e} -> {bound}-bound  roofline_frac={frac:.4f}")
    return frac


def engine_single():
    from repro.core.engine import sdtw_engine
    q = jax.ShapeDtypeStruct((B, M), jnp.float32)
    r = jax.ShapeDtypeStruct((N,), jnp.float32)
    comp = jax.jit(functools.partial(
        sdtw_engine.__wrapped__, return_end=True,
        accum_dtype=jnp.float32)).lower(q, r).compile()
    c = hlo_cost.analyze(comp.as_text())
    return report("engine (1 chip)", c, 1)


def pipeline_mesh(row_blocks=(40, 100, 200, 500)):
    from repro.core.distributed import make_sdtw_distributed
    mesh = jax.make_mesh((16, 16), ("data", "model"))
    q = jax.ShapeDtypeStruct((B, M), jnp.float32)
    r = jax.ShapeDtypeStruct((N + (-N) % 16,), jnp.float32)
    for rb in row_blocks:
        fn = make_sdtw_distributed(mesh, row_block=rb)
        comp = fn.lower(q, r).compile()
        c = hlo_cost.analyze(comp.as_text())
        report(f"pipeline rb={rb} (16x16)", c, 256)


def kernel_analytic():
    """Pallas wavefront kernel, VMEM-resident model:
    HBM traffic = q + r + outputs; compute = VPU elementwise (f32)."""
    hbm = (B * M + N + 2 * B) * 4.0
    vpu = 4e12      # ~VPU f32 elementwise roofline per chip
    # ~10 VPU ops per cell in the kernel inner loop (cost, 3-min, fold)
    t_c = 10 * CELLS / vpu
    t_m = hbm / HBM_BW
    frac = (USEFUL_FLOPS / vpu) / max(t_c, t_m)
    print(f"{'pallas kernel (1 chip, analytic)':28s} chips=1    "
          f"t_comp={t_c:.3e} t_mem={t_m:.3e} t_coll=0 -> compute-bound  "
          f"roofline_frac={frac:.4f} (VPU roofline; MXU unused — sDTW "
          f"has no matmul)")
    print(f"{'':28s} paper wall-clock: 11.04 s on AMD; kernel bound "
          f"here: {max(t_c, t_m) * 1e3:.1f} ms/chip, "
          f"{max(t_c, t_m) / 256 * 1e3:.2f} ms on the pod (DP over "
          f"queries)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prod-mesh", action="store_true")
    args = ap.parse_args()
    print(f"# sDTW roofline — paper workload B={B} M={M} N={N} "
          f"(useful {USEFUL_FLOPS:.2e} FLOP)")
    if args.prod_mesh:
        pipeline_mesh()
    else:
        engine_single()
        kernel_analytic()


if __name__ == "__main__":
    main()
