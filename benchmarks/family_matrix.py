"""Recurrence-family matrix benchmark (``repro.dp``).

For every family (sdtw / twed / erp / local) x reduction, times one
batched engine dispatch and one kernel dispatch over the same data and
hard-asserts the family contracts on every run:

  * engine == full-matrix float64 numpy oracle (``repro.dp.oracle``)
    to 1e-5 on a small slice of the batch;
  * kernel == engine bit-for-bit on hard-min, <= 1e-4 on soft-min,
    end columns always exact.

So a family regression (a fold drifting, an extra operand mis-swizzled,
an oracle mismatch) fails the benchmark — in CI on tiny shapes — and
the emitted ``BENCH_family_matrix.json`` metrics let
``launch/report.py --history/--plot`` trend per-family wall-clock.

  python -m benchmarks.family_matrix           # bench-sized shapes
  python -m benchmarks.family_matrix --ci      # tiny shapes + asserts
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import gsps, time_fn

FAMILY_KW = {
    "sdtw": {},
    "twed": {"nu": 0.5, "lam": 0.75},
    "erp": {"gap": 0.25},
    "local": {"gap_penalty": 0.6, "match_reward": 1.1},
}


def _assert_oracle(spec, q, r, cost, end, *, slice_b: int):
    """Engine vs the float64 full-matrix oracle on the first queries
    of the batch (the oracle is O(M*N) python per query)."""
    from repro.core.ref import sdtw_numpy
    from repro.dp.oracle import dp_oracle
    oracle = sdtw_numpy if spec.family == "sdtw" else dp_oracle
    for b in range(slice_b):
        want_c, want_e = oracle(np.asarray(q[b]), np.asarray(r), spec)
        assert np.isinf(cost[b]) == np.isinf(want_c), \
            (spec.describe(), b, cost[b], want_c)
        if np.isfinite(want_c):
            np.testing.assert_allclose(
                cost[b], want_c, rtol=1e-5, atol=1e-5,
                err_msg=f"{spec.describe()} engine != oracle (query {b})")
        assert int(end[b]) == int(want_e), \
            (spec.describe(), b, end[b], want_e)


def _assert_kernel(spec, eng_c, eng_e, ker_c, ker_e):
    if spec.soft:
        both_inf = np.isinf(eng_c) & np.isinf(ker_c)
        fin = ~both_inf
        np.testing.assert_allclose(
            ker_c[fin], eng_c[fin], rtol=1e-4, atol=1e-4,
            err_msg=f"{spec.describe()} kernel != engine (soft)")
    else:
        np.testing.assert_array_equal(
            ker_c, eng_c,
            err_msg=f"{spec.describe()} kernel != engine (hard)")
    np.testing.assert_array_equal(
        ker_e, eng_e, err_msg=f"{spec.describe()} kernel end != engine")


def run(full: bool = False, ci: bool = False,
        csv: list | None = None) -> dict:
    import jax.numpy as jnp
    from repro.core.api import sdtw
    from repro.core.spec import resolve_spec

    if ci:
        # tiny shapes; still one timed run per cell so the archived
        # BENCH metrics carry a trendable (if noisy) wall-clock
        B, M, N, runs = 4, 12, 40, 1
    elif full:
        B, M, N, runs = 128, 256, 4000, 5
    else:
        B, M, N, runs = 16, 64, 512, 3
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, M)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=(N,)).astype(np.float32))
    floats = B * M
    slice_b = B if ci else min(B, 2)

    print(f"# family matrix  B={B} M={M} N={N} "
          f"({'ci' if ci else 'full' if full else 'reduced'})")
    metrics: dict[str, float] = {}
    checked = 0
    for family, kw in FAMILY_KW.items():
        for reduction in ("hardmin", "softmin"):
            spec = resolve_spec(None, family=family, reduction=reduction,
                                gamma=0.7, **kw)
            tag = f"{family}/{reduction[:4]}"
            results = {}
            for backend in ("engine", "kernel"):
                def call(backend=backend):
                    res = sdtw(q, r, backend=backend, spec=spec,
                               normalize=False, segment_width=4)
                    return res.cost, res.end
                cost, end = call()
                dt = (float("nan") if runs == 0
                      else time_fn(call, warmup=1, runs=runs))
                results[backend] = (np.asarray(cost), np.asarray(end))
                rate = gsps(floats, dt) if dt == dt else float("nan")
                print(f"  {backend:7s} {tag:14s} {dt * 1e3:8.2f} ms  "
                      f"{rate:8.4f} Gsps")
                if dt == dt:
                    metrics[f"{family}_{reduction[:4]}_{backend}_ms"] = \
                        dt * 1e3
                if csv is not None:
                    csv.append({"bench": "family_matrix",
                                "family": family, "reduction": reduction,
                                "backend": backend, "B": B, "M": M,
                                "N": N, "sec": dt})
            eng_c, eng_e = results["engine"]
            _assert_oracle(spec, q, r, eng_c, eng_e, slice_b=slice_b)
            _assert_kernel(spec, eng_c, eng_e, *results["kernel"])
            checked += 1
    print(f"[family_matrix] {checked} family x reduction cells: "
          f"oracle + kernel parity OK")
    assert checked == 2 * len(FAMILY_KW)
    metrics["checked_cells"] = float(checked)
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ci", action="store_true",
                    help="tiny shapes, correctness asserts only")
    args = ap.parse_args(argv)
    run(full=args.full, ci=args.ci)


if __name__ == "__main__":
    main()
