"""Find a cell's parts by name.

``BENCHMARK.json`` names each part; the files are found from the names,
so a later cell, configuration, traffic mix or per-layer metric is a new
file and never an edit:

  configs/<config>.json     the deployment (its ``file`` in BENCHMARK.json);
                            its ``system`` key names the module below
  systems/<system>.py       builds the data, warms up, drives the timed
                            call, and checks its answers against
                            ``reference.py``
  traffic/<traffic>.json    the parameters ``generate.py`` reads
  metrics/<metric>.py       a reader: ``read(ctx)`` -> number or None
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def load_module(path: pathlib.Path, name: str):
    """Import a Python file by path (its name may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names."""
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # entries of BENCHMARK.json's end_to_end
    per_layer: list       # entries of BENCHMARK.json's per_layer
    root: pathlib.Path    # the benchmark's directory

    def system(self):
        name = self.config["system"]
        return load_module(self.root / "systems" / f"{name}.py",
                           f"chipbench_system_{name}")

    def reader(self, metric: str):
        return load_module(self.root / "metrics" / f"{metric}.py",
                           f"chipbench_metric_{metric.replace('.', '_')}")


def _reports(entry: dict, workload: str) -> bool:
    return workload in entry.get("workloads", [workload])


def load_cell(workload: str, *, benchmark: pathlib.Path | None = None,
              root: pathlib.Path = HERE) -> Cell:
    """The cell ``workload`` of the benchmark file (default: the one at
    the root of the checkout)."""
    benchmark = CHECKOUT / "BENCHMARK.json" if benchmark is None \
        else benchmark
    bench = json.loads(pathlib.Path(benchmark).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_file = root.parent / configs[w["config"]]["file"]
    config = json.loads(conf_file.read_text())
    traffic = json.loads((root / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)
