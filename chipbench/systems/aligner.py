"""Batch alignment through ``repro.Aligner``: one reference, a closed
loop of query batches, each call waited for before the next is sent.

The timed call is the session's own ``Aligner.__call__`` on a batch
made before the window; it normalizes the queries and runs the sweep
in one compiled program.  The check takes a sample of the window's
answers, drawn from the seed, and sweeps the same raw queries through
``reference.py``:

  cost_gap  |program cost - reference best| / reference best;
  end_gap   (reference bottom row at the program's end - best) / best:
            how far from optimal the reported end column is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro import obs

from chipbench import counts, generate, reference
from chipbench.systems import common
from chipbench.systems.common import rel_gap

END_TO_END = "align_gcells_per_s"


class System:
    def __init__(self, cell, seed: int, *, tracer):
        cfg, tr = cell.config, cell.traffic
        self.outputs = tuple(cfg["outputs"])
        self.limits = cfg["limits"]
        self.sample_rows = int(cfg["check_sample"])
        key_ref, key_q = jax.random.split(generate.key_of(seed))
        self.refs = generate.references(key_ref, cfg["references"])
        pool = generate.queries(key_q, tr, self.refs, cfg["references"])
        self.batches = [pool[p] for p in range(pool.shape[0])]
        self.batch, self.m = map(int, pool.shape[1:])
        self.n = int(self.refs.shape[1])
        self.aligner = repro.Aligner(
            self.refs[0], backend=cfg.get("backend"), outputs=self.outputs,
            segment_width=int(cfg["segment_width"]),
            metrics=obs.MetricsRegistry(), tracer=tracer)
        if self.aligner.backend.name != cfg["expect_backend"]:
            raise RuntimeError(
                f"the Aligner chose backend {self.aligner.backend.name!r}, "
                f"not {cfg['expect_backend']!r}")
        self._run(self.batches[0])               # compile the one shape
        self.results: list[tuple] = []

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
        return {
            "cells": counts.cells(calls * self.batch, self.m, self.n),
            "bytes": calls * counts.sdtw_bytes(
                queries=self.batch, m=self.m, references=1, n=self.n,
                outputs=len(self.outputs)),
        }

    def counters(self) -> dict:
        return {"calls": len(self.results)}

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
        q = reference.znorm(s["queries"]).astype(np.float32)
        r = reference.znorm(np.asarray(self.refs)).astype(np.float32)
        return reference.sweep(q, r, np.zeros(len(q), np.int32), target,
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
