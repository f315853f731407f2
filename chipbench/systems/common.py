"""What the system modules share: the sample of answers a check takes,
and the relative gap it compares."""

from __future__ import annotations

import numpy as np


def rel_gap(got, want) -> np.ndarray:
    """|got - want| / want, elementwise; inf where either is not finite
    (an answer that is missing or blocked is as wrong as it gets)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    ok = np.isfinite(got) & np.isfinite(want) & (want > 0)
    out = np.full(got.shape, np.inf)
    out[ok] = np.abs(got[ok] - want[ok]) / want[ok]
    return out


def sample(results, batches, batch: int, size: int, rng) -> dict:
    """Up to ``size`` of the window's answers, drawn by ``rng``: the
    (call, row) of each and its raw query.  ``results[c][0]`` is the
    index in ``batches`` of call ``c``'s query batch."""
    total = len(results) * batch
    take = np.sort(rng.choice(total, min(size, total), replace=False))
    calls, rows = np.divmod(take, batch)
    q = np.stack([np.asarray(batches[results[c][0]][r])
                  for c, r in zip(calls, rows)])
    return {"calls": calls, "rows": rows, "queries": q}
