"""Multivariate batch alignment through ``repro.Aligner``: one archive
of feature frames, a closed loop of query batches of feature frames,
each call waited for before the next is sent.

The archive (N, D) and the query batches (B, M, D) are random walks,
one per feature along time, made on the device from the seed: the
archive from the configuration's own seed, as a deployment's archive
is fixed, the queries from the run's.  Once the session holds the
normalized archive, the raw one is kept on the host for the check.
The timed call is the session's own ``Aligner.__call__`` on a batch
made before the window; it normalizes each query feature over time
and runs the sweep in one compiled program.

The program's ``kernel.wavefront.*`` counters are read after the
warm-up and again after the window; ``counters()`` gives what the
window added.

The check takes a sample of the window's answers, drawn from the seed,
and sweeps the same raw queries through ``reference_features.py``:

  cost_gap  |program cost - reference best| / reference best;
  end_gap   (reference bottom row at the program's end - best) / best:
            how far from optimal the reported end column is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro import obs

from chipbench import generate, reference_features
from chipbench.systems import common
from chipbench.systems.common import rel_gap

END_TO_END = "align_gcells_per_s"
WORD = 4                                  # float32 / int32 bytes
COUNTERS = ("dispatches", "wide_dispatches", "grid_steps", "loop_steps",
            "lane_cells", "cells_real", "feature_cells")


@functools.partial(jax.jit, static_argnames=("shape",))
def _walks(key, shape):
    """Random walks along axis -2 (time), one per feature (axis -1)."""
    return jnp.cumsum(jax.random.normal(key, shape, jnp.float32), axis=-2)


@functools.partial(jax.jit, static_argnames=("n", "d"))
def _archive(key, *, n, d):
    """(n, d) random walks along time, one per feature, made feature by
    feature along the lanes: an (n, d) array of few features pads its
    last axis to whole lane tiles on the device, so only the result
    takes that room."""
    return jnp.cumsum(jax.random.normal(key, (d, n), jnp.float32),
                      axis=1).T


def _program_counters() -> dict:
    reg = obs.default_registry()
    return {f"kernel.wavefront.{k}": reg.value(f"kernel.wavefront.{k}")
            for k in COUNTERS}


class System:
    def __init__(self, cell, seed: int, *, tracer):
        cfg, tr = cell.config, cell.traffic
        self.outputs = tuple(cfg["outputs"])
        self.limits = cfg["limits"]
        self.sample_rows = int(cfg["check_sample"])
        self.features = int(cfg["features"])
        ref = cfg["references"]
        if int(ref["count"]) != 1 or ref["process"] != "random_walk":
            raise ValueError("one random-walk archive is supported")
        self.n = int(ref["length"])
        self.batch, self.m = int(tr["batch"]), int(tr["query_len"])
        key_q = generate.key_of(seed)
        archive = _archive(generate.key_of(int(ref["seed"])), n=self.n,
                           d=self.features)
        pool = _walks(key_q, (int(tr["pool"]), self.batch, self.m,
                              self.features))
        self.batches = [pool[p] for p in range(pool.shape[0])]
        self.aligner = repro.Aligner(
            archive, backend=cfg.get("backend"), outputs=self.outputs,
            segment_width=int(cfg["segment_width"]),
            metrics=obs.MetricsRegistry(), tracer=tracer)
        # the session holds the normalized archive; the raw one is kept
        # on the host for the check, and leaves the device
        self.archive = np.asarray(archive)
        del archive
        if self.aligner.backend.name != cfg["expect_backend"]:
            raise RuntimeError(
                f"the Aligner chose backend {self.aligner.backend.name!r}, "
                f"not {cfg['expect_backend']!r}")
        self._run(self.batches[0])               # compile the one shape
        self.results: list[tuple] = []
        self._counters0 = _program_counters()
        self._archive_norm = None

    def _run(self, q):
        res = self.aligner(q, outputs=self.outputs)
        jax.block_until_ready((res.cost, res.end))
        return res

    def call(self, i: int) -> None:
        p = i % len(self.batches)
        res = self._run(self.batches[p])
        self.results.append((p, res.cost, res.end))

    # ------------------------------------------------------- accounting
    def attempted(self) -> int:
        return len(self.results) * self.batch

    def end_to_end(self, seconds: float) -> dict:
        return {END_TO_END: self.work()["cells"] / seconds / 1e9}

    def work(self) -> dict:
        calls = len(self.results)
        words = (self.batch * self.m * self.features
                 + self.n * self.features + self.batch * len(self.outputs))
        return {"cells": calls * self.batch * self.m * self.n,
                "bytes": calls * WORD * words}

    def counters(self) -> dict:
        now = _program_counters()
        return {"calls": len(self.results),
                **{k: now[k] - self._counters0[k] for k in now}}

    def release(self) -> None:
        """Free the program's state; the answers and inputs stay."""
        self.aligner = None

    # ----------------------------------------------------------- checks
    def sample(self, rng) -> dict:
        """Window answers to check, drawn from the seed: (call, row)."""
        return common.sample(self.results, self.batches, self.batch,
                             self.sample_rows, rng)

    def program_answers(self, s: dict) -> dict:
        cost = {c: np.asarray(self.results[c][1]) for c in set(s["calls"])}
        end = {c: np.asarray(self.results[c][2]) for c in set(s["calls"])}
        return {"cost": np.array([cost[c][r] for c, r in
                                  zip(s["calls"], s["rows"])]),
                "end": np.array([end[c][r] for c, r in
                                 zip(s["calls"], s["rows"])])}

    def _sweep(self, s, dtype, target=None):
        if self._archive_norm is None:
            self._archive_norm = reference_features.znorm_archive(
                self.archive)
        q = reference_features.znorm_time(s["queries"]).astype(np.float32)
        return reference_features.sweep(q, self._archive_norm, target,
                                        dtype=dtype)

    def control_answers(self, s: dict) -> dict:
        """The reference in bfloat16 in the program's place."""
        best, arg, _ = self._sweep(s, jnp.bfloat16)
        return {"cost": best, "end": arg}

    def compare(self, s: dict, answers: dict) -> dict:
        """{name: value} of each number compared, worst over the sample."""
        end = np.asarray(answers["end"], np.int64)
        ok = (end >= 0) & (end < self.n)
        best, _, at = self._sweep(s, jnp.float32,
                                  np.where(ok, end, -1).astype(np.int32))
        at = np.where(ok, at, np.inf)
        return {"cost_gap": float(np.max(rel_gap(answers["cost"], best))),
                "end_gap": float(np.max(rel_gap(at, best)))}
