"""Whole runs of each cell at tiny sizes on the CPU (the kernel
interpreted), with the chip check skipped: the answers of a sound run
pass every limit; the bfloat16 control and answers broken where they
are produced fail one; and the command refuses to measure without a
TPU or without the program."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import repro

from chipbench import layout, run
from chipbench import trace as tr
from chipbench.tests import tiny

CELLS = sorted(tiny.OVERRIDES)
SEED = 2**31 + 11


def _run(workload, seed=SEED):
    return run.run_cell(workload, seed, 0.2, False, require_tpu=False,
                        overrides=tiny.overrides(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window"]["programs_built_in_window"] == 0
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_fails(workload):
    cell = layout.load_cell(workload)
    for part, extra in tiny.overrides(workload).items():
        getattr(cell, part).update(extra)
    run._prepare_environment(cache=False)
    from repro import obs
    system = cell.system().System(cell, SEED, tracer=obs.Tracer())
    for i in range(2):
        system.call(i)
    system.release()
    s = system.sample(np.random.default_rng(SEED))
    readings = system.compare(s, system.control_answers(s))
    assert any(v > system.limits[k] for k, v in readings.items()), readings


def _altered_aligner(monkeypatch, alter):
    align = repro.Aligner.align

    def broken(self, queries, *, outputs=("cost", "end")):
        return alter(align(self, queries, outputs=outputs))

    monkeypatch.setattr(repro.Aligner, "align", broken)
    monkeypatch.setattr(repro.Aligner, "__call__", broken)


BATCH_FAULTS = {
    "cost_off_by_a_percent": lambda r: r.replace(cost=r.cost * 1.01),
    "end_moved": lambda r: r.replace(end=(r.end + 500) % 1024),
    "half_the_batch_unanswered": lambda r: r.replace(
        cost=r.cost.at[r.cost.shape[0] // 2:].set(0.0)),
    "answers_of_other_rows": lambda r: r.replace(
        cost=r.cost[::-1], end=r.end[::-1]),
}


@pytest.mark.parametrize("fault", sorted(BATCH_FAULTS))
def test_batch_answers_broken_where_produced_fail(monkeypatch, fault):
    _altered_aligner(monkeypatch, BATCH_FAULTS[fault])
    res = _run("paper_batch.sweep")
    assert not res["correct"], res["checks"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "paper_batch.sweep", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_without_a_tpu():
    out = _command(layout.CHECKOUT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_the_command_refuses_without_the_program(tmp_path):
    shutil.copy(layout.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(layout.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "the program is not in this checkout" in out.stderr
    assert not out.stdout.strip()


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((layout.CHECKOUT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = layout.load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        assert cell.system().System
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
            assert m["moves"] in names
        for limit in cell.config["limits"].values():
            assert isinstance(limit, float) and limit > 0


FIXTURE = pathlib.Path(__file__).parent / "data" / "small_trace.xplane.pb"


def test_a_traced_run_reads_every_per_layer_metric(monkeypatch):
    """The traced path on the CPU.  The profiler writes no TPU plane
    here, so the reduction is handed the small trace recorded on the
    chip in its place; the work is the tiny run's, and the peaks are
    stand-ins, not a chip's."""
    recorded = tr.load(FIXTURE)
    monkeypatch.setattr(tr, "load", lambda path: recorded)
    res = run.run_cell("paper_batch.sweep", SEED, 0.2, True,
                       require_tpu=False,
                       overrides=tiny.overrides("paper_batch.sweep"),
                       peaks={"vpu_ops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e11})
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"wavefront_roofline.align",
                                   "device_idle_share.align"}
    assert 0 < res["metrics"]["device_idle_share.align"]["value"] < 100
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert list(res["breakdown"]) == ["device_ops", "idle_gaps"]
    assert res["breakdown"]["device_ops"][0][0] == "_dispatch"
    assert list(res)[-1] == "checks"


def test_readers_that_find_nothing_return_nothing():
    from chipbench import readers
    empty = tr.Summary(window_ns=10, busy_ns=10, kernel_ns={},
                       top_ops=[], idle_by_host=[], devices=1)
    ctx = types.SimpleNamespace(trace=empty, counters={}, peaks={},
                                work={"cells": 100, "bytes": 4})
    assert readers.wavefront_roofline(ctx) is None
    assert readers.device_idle_share(ctx) == 0.0
