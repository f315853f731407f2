"""Band-skip plan: grid steps executed + wall-clock vs band width.

For Sakoe–Chiba specs the carry-channel executor trims the pallas grid
itself (``KernelPlan.grid_blocks``): reference blocks whose columns are
all beyond ``(m-1) + band`` are never visited, so a tight band costs
~O(N / band) fewer grid steps than the masked full grid — and the
outputs are bit-for-bit identical (asserted in --ci mode and in
tests/test_wavefront_plans.py).

  PYTHONPATH=src python -m benchmarks.band_skip
  PYTHONPATH=src python -m benchmarks.band_skip --ci   # tiny, asserts
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import time_fn


def run(*, full: bool = False, ci: bool = False, csv: list | None = None):
    import jax
    import jax.numpy as jnp
    from repro.core.spec import DPSpec
    from repro.kernels import ops
    from repro.kernels.wavefront import build_plan, wavefront_call

    if ci:
        B, M, N, w, reps = 4, 10, 128 * 2 * 3 + 40, 2, 1
        bands = (16, 64, None)
    elif full:
        B, M, N, w, reps = 32, 128, 65536, 8, 3
        bands = (64, 256, 1024, 4096, None)
    else:
        B, M, N, w, reps = 8, 32, 16384, 4, 3
        bands = (32, 128, 1024, None)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, M)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=(N,)).astype(np.float32))
    qp = ops.prepare_queries(q)
    rl = ops.swizzle_reference(r, w)
    groups, blocks = qp.shape[0], rl.shape[0]

    print(f"[band_skip] B={B} M={M} N={N} w={w} ref_blocks={blocks} "
          f"({'ci' if ci else 'full' if full else 'reduced'})")
    baseline = None
    for band in bands:
        spec = DPSpec(band=band)
        plan = build_plan(spec, m=M, segment_width=w,
                          num_ref_blocks=blocks)
        full_plan = build_plan(spec, m=M, segment_width=w,
                               num_ref_blocks=blocks, band_skip=False)

        def skip_fn():
            return jax.block_until_ready(
                wavefront_call(plan, qp, rl))

        def mask_fn():
            return jax.block_until_ready(
                wavefront_call(full_plan, qp, rl))

        t_skip = time_fn(skip_fn, warmup=1, runs=reps)
        t_mask = time_fn(mask_fn, warmup=1, runs=reps)
        if ci:
            for a, b in zip(skip_fn(), mask_fn()):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        run_steps = groups * plan.grid_blocks
        total_steps = groups * plan.num_ref_blocks
        speedup = t_mask / t_skip if t_skip > 0 else float("nan")
        label = "inf " if band is None else f"{band:<4d}"
        print(f"  band={label}: grid steps {run_steps:4d}/{total_steps:4d}"
              f"   masked {t_mask * 1e3:8.2f} ms   skip "
              f"{t_skip * 1e3:8.2f} ms   speedup {speedup:4.2f}x")
        if band is None:
            baseline = run_steps
        if csv is not None:
            csv.append({"bench": "band_skip", "band": band or -1,
                        "B": B, "M": M, "N": N, "w": w,
                        "grid_steps": run_steps,
                        "grid_steps_full": total_steps,
                        "ms_masked": round(t_mask * 1e3, 3),
                        "ms_skip": round(t_skip * 1e3, 3),
                        "speedup": round(speedup, 3)})
    if ci:
        tight = build_plan(DPSpec(band=bands[0]), m=M, segment_width=w,
                           num_ref_blocks=blocks)
        assert tight.grid_blocks < tight.num_ref_blocks, \
            (tight.grid_blocks, tight.num_ref_blocks)
        print("  band-skip == masked full grid on every band (ci assert), "
              f"tight band runs {tight.grid_blocks}/{tight.num_ref_blocks} "
            "blocks")
    assert baseline is not None


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ci", action="store_true")
    args = ap.parse_args()
    run(full=args.full, ci=args.ci)
