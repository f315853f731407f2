"""Smoke run of the sDTW main path on a TPU, through the entry points a
user calls, at the paper's size (``configs/paper_sdtw.PAPER``: 512
queries x 2,000 samples against a 100,000-sample reference,
z-normalized, hard-min, data made from ``--seed``).

  A  batch alignment: ``repro.Aligner`` with the backend auto-selected
     (it must resolve to the compiled Pallas kernel), cost/start/end,
     checked against the XLA engine on the chip and, for 4 queries,
     against a float64 row sweep on the host;
  B  search: a ``ReferenceIndex`` of 8 references x 100,000 samples and
     ``SearchService(kernel, windows)`` top-1 for 64 queries of 2,000
     samples, checked against brute force;
  C  soft-DTW gradient: ``jax.grad`` of ``train.make_sdtw_loss`` on the
     kernel (gamma 0.5) at 8 x 2,000 against 100,000, which must be
     finite, and at 8 x 256 against 4,096, which must agree with the
     gradient through the engine.

  python chip_smoke.py              # phases A-C on one chip
  python chip_smoke.py --chips 4    # only the distributed backend over
                                    # a (data=1, model=4) mesh, against
                                    # the kernel on device 0

Seconds printed here are smoke timings of one run, not benchmark
metrics.  Any failed check raises, so the script exits nonzero; the
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed.  Without a TPU it exits nonzero before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro.configs.paper_sdtw import PAPER  # noqa: E402
from repro.core.normalize import normalize_batch  # noqa: E402
from repro.core.ref import sdtw_bottom_row  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.search import ReferenceIndex, SearchConfig, SearchService  # noqa: E402,E501
from repro.search.service import brute_force_topk  # noqa: E402
from repro.train import make_sdtw_loss  # noqa: E402

SEGMENT_WIDTH = 8               # pinned: no tuning verdict steers the run
OUTPUTS = ("cost", "start", "end")
GAMMA = 0.5
N_REFS, N_SEARCH_QUERIES = 8, 64
GRAD_BATCH = 8
GRAD_SMALL = (256, 4_096)       # (query length, reference length)
HOST_ROWS = 4
COST_RTOL = 1e-5                # kernel vs engine, both float32
HOST_RTOL = 1e-4                # float32 device vs float64 host
GRAD_TOL = 1e-4


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + "  ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def has_kernel(hlo_texts) -> bool:
    return any("tpu_custom_call" in t for t in hlo_texts)


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


# ----------------------------------------------------------------- data
def batch_data(rng, batch: int, m: int, n: int):
    """(reference (n,), queries (batch, m)): i.i.d. normal samples."""
    return (rng.standard_normal(n, dtype=np.float32),
            rng.standard_normal((batch, m), dtype=np.float32))


def search_data(rng, n_refs: int, n: int, n_queries: int, m: int):
    """Random-walk references, and queries cut from them at random
    offsets with noise added: each query has one planted best match."""
    refs = np.cumsum(rng.standard_normal((n_refs, n), dtype=np.float32),
                     axis=1)
    owner = rng.integers(0, n_refs, n_queries)
    start = rng.integers(0, n - m, n_queries)
    queries = np.stack([refs[o, s:s + m] for o, s in zip(owner, start)])
    queries += 0.1 * queries.std() * rng.standard_normal(
        queries.shape, dtype=np.float32)
    return {f"ref{i}": refs[i] for i in range(n_refs)}, queries


# --------------------------------------------------------------- phases
def phase_batch(reference, queries) -> dict:
    """A: one Aligner session, backend auto-selected."""
    report("A", queries=queries.shape, reference=reference.shape,
           outputs=OUTPUTS)
    kern = repro.Aligner(reference, outputs=OUTPUTS,
                         segment_width=SEGMENT_WIDTH)
    report("A", backend=kern.backend.name)
    if kern.backend.name != "kernel":
        raise AssertionError(f"auto-selection chose {kern.backend.name!r}, "
                             "not the kernel")
    res, first_s = timed(kern, queries, outputs=OUTPUTS)
    res, steady_s = timed(kern, queries, outputs=OUTPUTS)
    kernel_in_hlo = has_kernel(kern.hlo_texts())
    report("A", smoke_first_call_s=round(first_s, 3),
           smoke_steady_s=round(steady_s, 3), kernel_in_hlo=kernel_in_hlo)

    eng = repro.Aligner(reference, backend="engine")
    want, eng_first_s = timed(eng, queries, outputs=OUTPUTS)
    cost_rel = max_rel(res.cost, want.cost)
    ends_equal = bool(np.array_equal(res.end, want.end))
    starts_equal = bool(np.array_equal(res.start, want.start))
    report("A", engine_smoke_first_call_s=round(eng_first_s, 3),
           cost_max_rel_vs_engine=cost_rel, ends_equal=ends_equal,
           starts_equal=starts_equal)
    if not (ends_equal and starts_equal and cost_rel <= COST_RTOL):
        raise AssertionError("kernel and engine disagree")

    # the same normalized float32 inputs, swept in float64 on the host
    qn = np.asarray(normalize_batch(jnp.asarray(queries[:HOST_ROWS])))
    last = sdtw_bottom_row(qn, np.asarray(kern.reference))
    best = last.min(axis=1)
    ends = np.asarray(res.end)[:HOST_ROWS]
    host_rel = max_rel(np.asarray(res.cost)[:HOST_ROWS], best)
    # how far the kernel's end column is from the float64 optimum
    end_gap = max_rel(last[np.arange(HOST_ROWS), ends], best)
    report("A", host_rows=HOST_ROWS, cost_max_rel_vs_f64=host_rel,
           end_cost_gap_rel_vs_f64=end_gap,
           ends_equal_f64=int(np.sum(ends == last.argmin(axis=1))),
           peak_bytes_in_use=peak_bytes())
    if host_rel > HOST_RTOL or end_gap > HOST_RTOL:
        raise AssertionError("kernel disagrees with the float64 sweep")
    return {"kernel_in_hlo": kernel_in_hlo, "cost": np.asarray(res.cost),
            "end": np.asarray(res.end)}


def phase_search(refs: dict, queries) -> dict:
    """B: exact top-1 search over a reference index."""
    report("B", references=len(refs),
           reference=next(iter(refs.values())).shape, queries=queries.shape)
    index = ReferenceIndex()
    for name, series in refs.items():
        index.add(name, series)
    svc = SearchService(index, SearchConfig(
        backend="kernel", windows=True, segment_width=SEGMENT_WIDTH))
    t0 = time.perf_counter()
    hits = svc.topk(queries, k=1)          # host Match objects: synced
    first_s = time.perf_counter() - t0
    svc.reset_stats()
    t0 = time.perf_counter()
    hits = svc.topk(queries, k=1)
    steady_s = time.perf_counter() - t0
    st = svc.stats
    kernel_in_hlo = has_kernel(t for s in svc.sessions()
                               for t in s.hlo_texts())
    report("B", smoke_first_call_s=round(first_s, 3),
           smoke_steady_s=round(steady_s, 3),
           sweeps=f"{st.dp_pairs}/{st.pairs}", kernel_in_hlo=kernel_in_hlo)
    want = brute_force_topk(index, queries, k=1, backend="kernel",
                            windows=True, segment_width=SEGMENT_WIDTH)
    equal = hits == want
    report("B", equal_to_brute_force=equal, peak_bytes_in_use=peak_bytes())
    if not equal:
        raise AssertionError("search top-1 differs from brute force")
    return {"kernel_in_hlo": kernel_in_hlo}


def phase_grad(reference, pred, small_reference, small_pred) -> dict:
    """C: the soft-DTW loss gradient through the fused kernel backward."""
    report("C", pred=pred.shape, reference=reference.shape, gamma=GAMMA)
    loss = make_sdtw_loss(reference, backend="kernel", gamma=GAMMA,
                          segment_width=SEGMENT_WIDTH)
    t0 = time.perf_counter()
    grad = jax.jit(jax.grad(loss)).lower(jnp.asarray(pred)).compile()
    compile_s = time.perf_counter() - t0
    # compiled ahead of time, so one call is already a steady one
    g, steady_s = timed(grad, jnp.asarray(pred))
    finite = bool(np.isfinite(np.asarray(g)).all())
    kernel_in_hlo = has_kernel([grad.as_text()])
    report("C", smoke_compile_s=round(compile_s, 3),
           smoke_steady_s=round(steady_s, 3), grad_finite=finite,
           kernel_in_hlo=kernel_in_hlo, peak_bytes_in_use=peak_bytes())
    if not finite:
        raise AssertionError("kernel gradient is not finite")

    report("C", pred=small_pred.shape, reference=small_reference.shape,
           oracle="jax.grad through the engine")
    gk = jax.grad(make_sdtw_loss(small_reference, backend="kernel",
                                 gamma=GAMMA, segment_width=SEGMENT_WIDTH))(
        jnp.asarray(small_pred))
    ge = jax.grad(make_sdtw_loss(small_reference, backend="engine",
                                 gamma=GAMMA))(jnp.asarray(small_pred))
    err = float(np.max(np.abs(np.asarray(gk) - np.asarray(ge))))
    report("C", grad_max_abs_diff_vs_engine=err)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ge),
                               rtol=GRAD_TOL, atol=GRAD_TOL)
    return {"kernel_in_hlo": kernel_in_hlo}


def phase_distributed(reference, queries, devices, *, row_block: int):
    """The distributed backend over a (data=1, model=4) mesh, against
    the kernel on the first device."""
    mesh = jax.sharding.Mesh(np.asarray(devices).reshape(1, len(devices)),
                             ("data", "model"))
    report("D", queries=queries.shape, reference=reference.shape,
           mesh=dict(mesh.shape), row_block=row_block)
    kern = repro.Aligner(reference, backend="kernel",
                         segment_width=SEGMENT_WIDTH)
    want, kern_s = timed(kern, queries, outputs=("cost", "end"))

    def dist():
        return repro.sdtw(queries, reference, backend="distributed",
                          outputs=("cost", "end"),
                          options={"mesh": mesh, "row_block": row_block})
    res, first_s = timed(dist)
    res, steady_s = timed(dist)
    cost_rel = max_rel(res.cost, want.cost)
    ends_equal = bool(np.array_equal(res.end, want.end))
    report("D", kernel_smoke_first_call_s=round(kern_s, 3),
           smoke_first_call_s=round(first_s, 3),
           smoke_steady_s=round(steady_s, 3),
           cost_max_rel_vs_kernel=cost_rel, ends_equal=ends_equal,
           peak_bytes_in_use=peak_bytes())
    if not (ends_equal and cost_rel <= COST_RTOL):
        raise AssertionError("distributed and kernel disagree")


# ----------------------------------------------------------------- main
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed path (4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{devices[0].platform!r}); this run needs a TPU chip")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} TPU device(s)")
    os.environ["REPRO_TUNE_CACHE"] = "off"
    report("setup", compile_cache=enable_compile_cache(),
           device=devices[0].device_kind, count=len(devices))
    if ops.default_interpret():
        raise AssertionError("Pallas would run interpreted on this chip")

    rng = np.random.default_rng(args.seed)
    reference, queries = batch_data(rng, PAPER.batch, PAPER.query_len,
                                    PAPER.ref_len)
    if args.chips == 4:
        phase_distributed(reference, queries, devices[:4], row_block=100)
    else:
        checks = [phase_batch(reference, queries)]
        refs, search_queries = search_data(
            rng, N_REFS, PAPER.ref_len, N_SEARCH_QUERIES, PAPER.query_len)
        checks.append(phase_search(refs, search_queries))
        small_ref, small_pred = batch_data(rng, GRAD_BATCH, *GRAD_SMALL)
        checks.append(phase_grad(reference, queries[:GRAD_BATCH],
                                 small_ref, small_pred))
        if not all(c["kernel_in_hlo"] for c in checks):
            raise AssertionError("a phase ran without the compiled kernel")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
