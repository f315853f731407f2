"""The wavefront kernel's work counts: ``KernelPlan.work`` against
numbers worked out by hand, and the process-wide ``kernel.wavefront.*``
counters that a session and the one-shot call add at dispatch (kernel
interpreted on the CPU)."""
import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.core.spec import DPSpec
from repro.kernels import ops
from repro.kernels.wavefront import KernelPlan

KEYS = ("dispatches", "wide_dispatches", "chained_dispatches",
        "cost_tile_dispatches", "grid_steps", "loop_steps", "lane_cells",
        "cells_real", "feature_cells")


def counters() -> dict:
    reg = obs.default_registry()
    return {k: reg.value(f"kernel.wavefront.{k}") for k in KEYS}


def plus(before: dict, *works) -> dict:
    out = dict(before)
    for w in works:
        out["dispatches"] += 1
        out["wide_dispatches"] += w["rows_per_step"] == 16
        out["chained_dispatches"] += w["lanes_per_chain"] < 128
        out["cost_tile_dispatches"] += w["cost_tile"]
        for k, v in w.items():
            if k not in ("rows_per_step", "lanes_per_chain", "cost_tile"):
                out[k] += v
    return out


@pytest.mark.parametrize("spec, batch, m, n, w, want", [
    # the paper's batch: 64 groups x 98 blocks, 2,000 + 127 steps each
    (DPSpec(), 512, 2_000, 100_000, 8,
     {"rows_per_step": 8, "lanes_per_chain": 128, "grid_steps": 6_272, "loop_steps": 13_340_544,
      "lane_cells": 109_285_736_448, "cells_real": 102_400_000_000,
      "feature_cells": 102_400_000_000, "cost_tile": 0}),
    # 13 queries fill 2 groups; 1,000 columns pad to 2 blocks of 512
    (DPSpec(), 13, 20, 1_000, 4,
     {"rows_per_step": 8, "lanes_per_chain": 128, "grid_steps": 4,
      "loop_steps": 4 * 147,
      "lane_cells": 4 * 147 * 8 * 512, "cells_real": 13 * 20 * 1_000,
      "feature_cells": 13 * 20 * 1_000, "cost_tile": 0}),
    # band 100 at m = 200 keeps columns up to 298: 2 of 20 blocks of
    # 256 run, and only their 512 columns hold real cells
    (DPSpec(band=100), 8, 200, 5_000, 2,
     {"rows_per_step": 8, "lanes_per_chain": 128, "grid_steps": 2,
      "loop_steps": 2 * 327,
      "lane_cells": 2 * 327 * 8 * 256, "cells_real": 8 * 200 * 512,
      "feature_cells": 8 * 200 * 512, "cost_tile": 0}),
])
def test_plan_work_by_hand(spec, batch, m, n, w, want):
    plan = ops.kernel_plan(spec, m=m, n=n, segment_width=w)
    work = plan.work(batch, n)
    assert work == want
    assert all(type(v) is int for v in work.values())
    assert work["cells_real"] <= work["lane_cells"]


@pytest.mark.parametrize("spec, batch, m, n, w, want", [
    # the paper's batch two groups a step in each of 8 chains of 16
    # lanes: 4 steps along the batch x 98 blocks, 8 sub-blocks of
    # 2,000 + 15 steps each
    (DPSpec(), 512, 2_000, 100_000, 8,
     {"rows_per_step": 16, "lanes_per_chain": 16, "grid_steps": 392,
      "loop_steps": 6_319_040, "lane_cells": 103_531_151_360,
      "cells_real": 102_400_000_000, "feature_cells": 102_400_000_000,
      "cost_tile": 0}),
    # 24 queries round up to 32: 2 chains of 16 rows, one step along
    # the batch, its 8 pad queries' lane-cells counted
    (DPSpec(), 24, 20, 1_000, 4,
     {"rows_per_step": 16, "lanes_per_chain": 64, "grid_steps": 2,
      "loop_steps": 2 * 2 * 83, "lane_cells": 2 * 2 * 83 * 16 * 512,
      "cells_real": 24 * 20 * 1_000, "feature_cells": 24 * 20 * 1_000,
      "cost_tile": 0}),
    # 9 queries fill 2 groups, 7 rows of the second padding
    (DPSpec(band=100), 9, 200, 5_000, 2,
     {"rows_per_step": 16, "lanes_per_chain": 128, "grid_steps": 2,
      "loop_steps": 2 * 327, "lane_cells": 2 * 327 * 16 * 256,
      "cells_real": 9 * 200 * 512, "feature_cells": 9 * 200 * 512,
      "cost_tile": 0}),
    # a search round's 2 real rows: one group in one chain
    (DPSpec(), 2, 20, 1_000, 4,
     {"rows_per_step": 8, "lanes_per_chain": 128, "grid_steps": 2,
      "loop_steps": 2 * 147, "lane_cells": 2 * 147 * 8 * 512,
      "cells_real": 2 * 20 * 1_000, "feature_cells": 2 * 20 * 1_000,
      "cost_tile": 0}),
    # one group never takes the wide step
    (DPSpec(), 8, 20, 1_000, 4,
     {"rows_per_step": 8, "lanes_per_chain": 128, "grid_steps": 2,
      "loop_steps": 2 * 147, "lane_cells": 2 * 147 * 8 * 512,
      "cells_real": 8 * 20 * 1_000, "feature_cells": 8 * 20 * 1_000,
      "cost_tile": 0}),
    # 12,000-sample queries: chains would cut the steps under 1%, so
    # one chain, 32 steps along the batch x 98 blocks of 12,127 steps
    (DPSpec(), 512, 12_000, 100_000, 8,
     {"rows_per_step": 16, "lanes_per_chain": 128, "grid_steps": 3_136,
      "loop_steps": 3_136 * 12_127,
      "lane_cells": 3_136 * 12_127 * 16 * 1_024,
      "cells_real": 614_400_000_000, "feature_cells": 614_400_000_000,
      "cost_tile": 0}),
    # sws2013_qbe's terms: 32 queries of 100 frames x 39 features, 2
    # chains of 64 lanes, 7,032 blocks of 2 sub-blocks of 163 steps
    (DPSpec(), 32, 100, 7_200_000, 8,
     {"rows_per_step": 16, "lanes_per_chain": 64, "grid_steps": 7_032,
      "loop_steps": 2_292_432, "lane_cells": 37_559_205_888,
      "cells_real": 23_040_000_000, "feature_cells": 898_560_000_000,
      "cost_tile": 1}),
])
def test_batch_plan_work_by_hand(spec, batch, m, n, w, want):
    features = want["feature_cells"] // want["cells_real"]
    plan = ops.kernel_plan(spec, m=m, n=n, segment_width=w, batch=batch,
                           features=features)
    assert plan.rows_per_step == want["rows_per_step"]
    assert plan.lanes_per_chain == want["lanes_per_chain"]
    assert plan.work(batch, n) == want
    assert ops.wavefront_work(spec, batch=batch, m=m, n=n,
                              segment_width=w, features=features) == want


def test_banded_plan_skips_blocks_and_reverse_reads_the_same():
    spec = DPSpec(reduction="softmin", gamma=0.5, band=100)
    fwd = ops.kernel_plan(spec, m=200, n=5_000, segment_width=2)
    assert fwd.skipped_blocks == 18
    rev = KernelPlan(spec=spec, m=200, segment_width=2,
                     num_ref_blocks=fwd.num_ref_blocks, reverse=True,
                     checkpoint=True)
    assert rev.work(8, 5_000) == fwd.work(8, 5_000)


def test_plan_work_refuses_a_reference_the_plan_cannot_hold():
    plan = ops.kernel_plan(m=20, n=1_000, segment_width=4)
    with pytest.raises(ValueError, match="does not fit"):
        plan.work(8, 1_025)
    with pytest.raises(ValueError, match="does not fit"):
        plan.work(8, 0)


def _data(b=3, m=20, n=300, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(b, m)).astype(np.float32),
            rng.normal(size=n).astype(np.float32))


def test_session_counts_one_dispatch_per_call():
    q, r = _data()
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel",
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    work = ops.kernel_plan(m=20, n=300).work(3, 300)
    start = counters()
    aligner(q)                                   # the cold call counts
    assert counters() == plus(start, work)
    aligner(q)                                   # and every cache hit
    aligner(q + 1.0)
    assert aligner.stats.cache_hits == 2
    assert counters() == plus(start, work, work, work)


def test_session_window_plan_counts_its_own_work():
    q, r = _data(b=9, m=16, n=700)
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel", segment_width=2,
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    aligner(q, outputs=("cost", "start", "end"))
    work = ops.kernel_plan(m=16, n=700, segment_width=2, with_window=True,
                           batch=9).work(9, 700)
    assert work["rows_per_step"] == 16       # 9 queries fill two groups
    assert work["grid_steps"] == 1 * 3
    assert counters() == plus(dict.fromkeys(KEYS, 0), work)


def test_fused_soft_session_counts_both_sweeps():
    q, r = _data(b=2, m=12, n=200)
    spec = DPSpec(reduction="softmin", gamma=0.5)
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel", spec=spec,
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    aligner(q, outputs=("cost", "soft_alignment"))
    work = ops.kernel_plan(spec, m=12, n=200).work(2, 200)
    assert counters() == plus(dict.fromkeys(KEYS, 0), work, work)


def test_fused_soft_session_keeps_one_chain():
    """At 32 queries the forward sweep alone would take 2 chains; the
    fused soft pair's checkpointed sweeps keep one, and count so."""
    q, r = _data(b=32, m=12, n=200)
    spec = DPSpec(reduction="softmin", gamma=0.5)
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel", spec=spec,
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    aligner(q, outputs=("cost", "soft_alignment"))
    work = ops.kernel_plan(spec, m=12, n=200, checkpoint=True,
                           batch=32).work(32, 200)
    assert (work["rows_per_step"], work["lanes_per_chain"]) == (16, 128)
    assert counters() == plus(dict.fromkeys(KEYS, 0), work, work)
    assert counters()["chained_dispatches"] == 0


def test_blocked_band_dispatches_and_counts_nothing():
    q, r = _data(b=2, m=50, n=20)
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel", band=5,
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    res = aligner(q)
    assert np.all(np.isinf(np.asarray(res.cost)))
    assert counters() == dict.fromkeys(KEYS, 0)


def test_engine_session_counts_nothing():
    q, r = _data()
    obs.reset()
    aligner = repro.Aligner(r, backend="engine",
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    aligner(q)
    assert counters() == dict.fromkeys(KEYS, 0)


@pytest.mark.parametrize("batch, wide", [(3, 0), (8, 0), (9, 1), (24, 1)])
def test_wide_dispatches_count_only_two_group_plans(batch, wide):
    q, r = _data(b=batch)
    obs.reset()
    ops.sdtw_wavefront(q, r)
    work = ops.wavefront_work(batch=batch, m=20, n=300)
    assert work["rows_per_step"] == (16 if wide else 8)
    assert counters()["wide_dispatches"] == wide
    assert counters() == plus(dict.fromkeys(KEYS, 0), work)


def test_one_shot_counts_on_the_host_and_not_while_tracing():
    q, r = _data()
    obs.reset()
    ops.sdtw_wavefront(q, r)
    work = ops.kernel_plan(m=20, n=300).work(3, 300)
    assert counters() == plus(dict.fromkeys(KEYS, 0), work)
    jax.jit(lambda q, r: ops.sdtw_wavefront(q, r))(q, r)
    assert counters() == plus(dict.fromkeys(KEYS, 0), work)


@pytest.mark.parametrize("batch, chained", [(3, 0), (16, 0), (24, 1),
                                            (64, 1)])
def test_chained_dispatches_count_only_chained_plans(batch, chained):
    q, r = _data(b=batch)
    obs.reset()
    ops.sdtw_wavefront(q, r)
    ops.sdtw_wavefront(q, r)
    work = ops.wavefront_work(batch=batch, m=20, n=300)
    assert (work["lanes_per_chain"] < 128) == bool(chained)
    assert counters()["chained_dispatches"] == 2 * chained
    assert counters() == plus(dict.fromkeys(KEYS, 0), work, work)


def test_chained_session_counts_what_the_rule_runs():
    """A session's batch of 32 x 100 runs 2 chains of 64 lanes; the
    counters add that plan's loop steps and lane-cells each call."""
    q, r = _data(b=32, m=100, n=3_000)
    obs.reset()
    aligner = repro.Aligner(r, backend="kernel", segment_width=2,
                            metrics=obs.MetricsRegistry(),
                            tracer=obs.Tracer())
    aligner(q)
    work = ops.kernel_plan(m=100, n=3_000, segment_width=2,
                           batch=32).work(32, 3_000)
    assert (work["rows_per_step"], work["lanes_per_chain"]) == (16, 64)
    assert work["loop_steps"] == 12 * 2 * (100 + 63)
    assert counters() == plus(dict.fromkeys(KEYS, 0), work)
    assert counters()["chained_dispatches"] == 1
