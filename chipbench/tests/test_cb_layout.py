"""A later cell, configuration, traffic mix or metric is a new file:
the loader finds each by its name, with no list to edit."""
import json
import shutil
import types

import pytest

from chipbench import layout, run
from chipbench.tests import tiny

TOY_CONFIG = {
    "name": "toy", "system": "aligner",
    "references": {"count": 1, "length": 640, "process": "random_walk"},
    "outputs": ["cost", "end"], "backend": "kernel",
    "expect_backend": "kernel", "segment_width": 4, "check_sample": 8,
    "limits": {"cost_gap": 1e-4, "end_gap": 1e-4},
}
TOY_MIX = {"batch": 8, "query_len": 24, "pool": 2, "queries": [
    {"kind": "fresh", "share": 3},
    {"kind": "excerpt", "share": 1, "resample": [1.0, 1.2], "noise": 0.2}]}
TOY_READER = '''
def read(ctx):
    return float(ctx.counters["calls"])
'''


@pytest.fixture
def toy_bench(tmp_path):
    """A copy of the benchmark with a throwaway configuration, mix and
    metric added as new files and entries."""
    root = tmp_path / "chipbench"
    shutil.copytree(layout.HERE, root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (root / "traffic" / "toy_mix.json").write_text(json.dumps(TOY_MIX))
    (root / "metrics" / "toy.calls.py").write_text(TOY_READER)
    bench = json.loads((layout.CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "nowhere",
                             "file": "chipbench/configs/toy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.mix", "config": "toy",
                               "traffic": "toy_mix", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("toy.mix")
    bench["per_layer"].append({
        "name": "toy.calls", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "front door",
        "moves": bench["end_to_end"][0]["name"], "workloads": ["toy.mix"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path, root


def test_loader_finds_new_files_by_name(toy_bench):
    path, root = toy_bench
    cell = layout.load_cell("toy.mix", benchmark=path, root=root)
    assert cell.config == TOY_CONFIG and cell.traffic == TOY_MIX
    assert [m["name"] for m in cell.end_to_end] == [
        "align_gcells_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy.calls"]
    ctx = types.SimpleNamespace(counters={"calls": 3})
    assert cell.reader("toy.calls").read(ctx) == 3.0
    # the cells already there are untouched by the addition
    old = layout.load_cell("paper_batch.sweep", benchmark=path, root=root)
    assert "toy.calls" not in [m["name"] for m in old.per_layer]


def test_a_new_cell_runs_from_its_files(toy_bench):
    path, root = toy_bench
    res = run.run_cell("toy.mix", 5, 0.2, False, require_tpu=False,
                       benchmark=path, root=root)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"align_gcells_per_s", "setup_s"}
    assert res["attempted"] % 8 == 0


def test_unknown_names_are_errors(toy_bench):
    path, root = toy_bench
    with pytest.raises(KeyError, match="unknown workload"):
        layout.load_cell("toy.nothing", benchmark=path, root=root)
    cell = layout.load_cell("toy.mix", benchmark=path, root=root)
    with pytest.raises(FileNotFoundError):
        cell.reader("toy.missing")


def test_tiny_overrides_name_real_cells():
    for w in tiny.OVERRIDES:
        layout.load_cell(w)


def test_the_benchmark_file_keeps_its_limits():
    """Names, units, bounds and cross-references of BENCHMARK.json are
    within the limits a checker holds them to."""
    import re
    bench = json.loads((layout.CHECKOUT / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        assert 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 2)
