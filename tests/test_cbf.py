"""repro.data.cbf: the seeded Cylinder-Bell-Funnel generators every
benchmark and example draws its series from."""
import numpy as np
import pytest

from repro.data.cbf import (KINDS, make_cylinder_bell_funnel,
                            make_sdtw_dataset, make_search_dataset)

GENERATORS = {
    "cbf": lambda seed: make_cylinder_bell_funnel(
        np.random.default_rng(seed), 6, 64),
    "search": lambda seed: make_search_dataset(
        seed, n_refs=3, motifs_per_ref=4, motif_len=32, n_queries=6),
    "sdtw": lambda seed: make_sdtw_dataset(seed, batch=4, query_len=50,
                                           ref_len=420),
}


def _leaves(x):
    if isinstance(x, dict):
        return [v for _, v in sorted(x.items())]
    if isinstance(x, (list, tuple)):
        return [leaf for item in x for leaf in _leaves(item)]
    return [x]


@pytest.mark.parametrize("name", list(GENERATORS))
def test_seeded_determinism(name):
    """One seed, one dataset; another seed, another."""
    gen = GENERATORS[name]
    a, b, c = _leaves(gen(0)), _leaves(gen(0)), _leaves(gen(1))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    arrays = [(np.asarray(x), np.asarray(z)) for x, z in zip(a, c)
              if not isinstance(x, str)]
    assert any(not np.array_equal(x, z) for x, z in arrays)


def test_shapes_dtypes_and_kinds():
    rng = np.random.default_rng(2)
    for kind in (*KINDS, None):
        x = make_cylinder_bell_funnel(rng, 5, 40, kind=kind)
        assert x.shape == (5, 40) and x.dtype == np.float32
        assert np.all(np.isfinite(x))
    with pytest.raises(ValueError, match="unknown kind"):
        make_cylinder_bell_funnel(rng, 1, 40, kind="ramp")
    queries, reference = make_sdtw_dataset(0, batch=4, query_len=50,
                                           ref_len=420)
    assert queries.shape == (4, 50) and reference.shape == (420,)
    assert queries.dtype == reference.dtype == np.float32


def test_search_dataset_balanced_motif_crops():
    """Queries go round the tracks in turn, and each is a motif-aligned
    crop of its own track plus small noise; every track is a run of
    z-normalized motifs."""
    n_refs, motifs, motif_len, n_q = 3, 4, 32, 9
    refs, queries, labels = make_search_dataset(
        5, n_refs=n_refs, motifs_per_ref=motifs, motif_len=motif_len,
        n_queries=n_q, query_motifs=2, noise=0.02)
    assert list(refs) == [f"track{i}" for i in range(n_refs)]
    assert labels == [f"track{i % n_refs}" for i in range(n_q)]
    for series in refs.values():
        blocks = series.reshape(motifs, motif_len)
        np.testing.assert_allclose(blocks.mean(axis=1), 0.0, atol=1e-5)
        np.testing.assert_allclose(blocks.std(axis=1), 1.0, atol=1e-4)
    m = 2 * motif_len
    for q, label in zip(queries, labels):
        assert q.shape == (m,) and q.dtype == np.float32
        crops = [refs[label][s * motif_len:s * motif_len + m]
                 for s in range(motifs - 1)]
        assert min(np.max(np.abs(q - c)) for c in crops) < 0.2
