"""launch/search_serve.py run in-process at a tiny size, once per flag
set its docstring documents: the call returns, the report is well
formed, and where the flags keep the exact hard-min search the printed
hits are SearchService.topk's on the same seeded data."""
import re

import pytest

from repro.core.spec import DPSpec
from repro.data.cbf import make_search_dataset
from repro.launch import search_serve
from repro.search import ReferenceIndex, SearchConfig, SearchService

SIZE = ["--refs", "2", "--motifs-per-ref", "4", "--queries", "8",
        "--chunk", "8"]
RATE = r"\s*[\d.]+ q/s"
HIT = r"track\d+(\[\d+\.\.\d+\]|@\d+) cost=-?[\d.]+"


@pytest.fixture(autouse=True)
def no_repo_cache(monkeypatch, tmp_path):
    """With the variable set, enable_compile_cache leaves JAX's own
    configuration alone, so no other test inherits a cache directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _offline_hit_lines(*, k=1, distance="sqeuclidean", band=None,
                       prune=True, windows=True):
    """The q-lines the driver prints, from SearchService.topk."""
    refs, queries, labels = make_search_dataset(
        seed=0, n_refs=2, motifs_per_ref=4, n_queries=8, query_motifs=2)
    index = ReferenceIndex(spec=DPSpec(distance=distance, band=band))
    for name, series in refs.items():
        index.add(name, series)
    svc = SearchService(index, SearchConfig(backend="engine", prune=prune,
                                            windows=windows))
    lines = []
    for i, m in enumerate(svc.topk(queries[:3], k=k)):
        best = ", ".join(
            (f"{x.reference}[{x.start}..{x.end}] cost={x.cost:.3f}"
             if x.start is not None else
             f"{x.reference}@{x.end} cost={x.cost:.3f}")
            for x in m)
        lines.append(f"  q{i} ({labels[i]}): {best}")
    return lines


CASES = {
    "default": ([], {}),
    "no-prune": (["--no-prune", "--k", "2"], {"prune": False, "k": 2}),
    "distance-abs": (["--distance", "abs"], {"distance": "abs"}),
    "band": (["--band", "300"], {"band": 300}),
    "no-windows": (["--no-windows"], {"windows": False}),
    "softmin": (["--reduction", "softmin", "--gamma", "1.0"], None),
    "stream": (["--stream", "--rate", "1000"], {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_search_serve_flag_sets(case, capsys):
    flags, exact = CASES[case]
    assert search_serve.main(SIZE + flags) is None
    out = capsys.readouterr().out.splitlines()
    tag = "stream" if case == "stream" else "search"
    head = [line for line in out if line.startswith(f"[{tag}]")]
    hits = [line for line in out if line.startswith("  q")]
    assert len(head) == 2 and len(hits) == 3, out
    if case == "stream":
        assert re.match(rf"\[stream\] offered \d+ q/s   goodput{RATE}   "
                        r"top-1 hit-rate \d+%   timeouts 0   rejects 0$",
                        head[0]), head[0]
        assert head[1].startswith("[stream] request latency ms: p50 ")
    else:
        assert re.match(rf"\[search\]{RATE}   top-1 hit-rate \d+%   "
                        r"sweeps \d+/\d+ \(skipped \d+%\)$", head[0]), head[0]
        assert head[1].startswith("[search] chunk latency ms: p50 ")
    for line in hits:
        assert re.match(rf"  q\d \(track\d+\): {HIT}(, {HIT})*$", line), line
    if exact is not None:
        assert hits == _offline_hit_lines(**exact)
