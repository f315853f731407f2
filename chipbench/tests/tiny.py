"""Tiny versions of the benchmark's cells, for CPU tests: the same
systems, traffic kinds and checks at sizes the Pallas interpreter runs
in seconds."""

OVERRIDES = {
    "paper_batch.sweep": {
        "config": {"backend": "kernel", "check_sample": 16,
                   "references": {"count": 1, "length": 1024,
                                  "process": "random_walk"}},
        "traffic": {"batch": 8, "query_len": 32, "pool": 2}},
}


def overrides(workload: str) -> dict:
    """Fresh copies: ``run_cell`` updates the cell's dicts in place."""
    return {part: dict(v) for part, v in OVERRIDES[workload].items()}
