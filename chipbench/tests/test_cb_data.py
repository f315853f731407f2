"""The traffic generator, the plain reference and the counts, at tiny
sizes on the CPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, generate, peaks, reference


def _ref_spec(process="random_walk"):
    return {"count": 3, "length": 600, "process": process}


def _traffic(**kw):
    t = {"batch": 6, "query_len": 40, "pool": 2,
         "queries": [{"kind": "excerpt", "share": 2, "resample": [0.9, 1.1],
                      "noise": 0.1}, {"kind": "fresh", "share": 1}]}
    t.update(kw)
    return t


def test_the_seed_fixes_the_data_and_a_large_seed_works():
    seed = 2**31 + 123
    refs = generate.references(generate.key_of(seed), _ref_spec())
    again = generate.references(generate.key_of(seed), _ref_spec())
    other = generate.references(generate.key_of(seed + 2**32), _ref_spec())
    assert np.array_equal(refs, again)
    assert not np.array_equal(refs, other)
    q = generate.queries(generate.key_of(seed), _traffic(), refs,
                         _ref_spec())
    assert q.shape == (2, 6, 40)
    assert np.array_equal(q, generate.queries(generate.key_of(seed),
                                              _traffic(), refs, _ref_spec()))
    with pytest.raises(ValueError):
        generate.key_of(-1)


def test_rows_per_kind_follow_the_shares():
    assert generate.rows_per_kind(32, [{"share": 1}]) == [32]
    assert generate.rows_per_kind(6, [{"share": 2}, {"share": 1}]) == [4, 2]
    assert sum(generate.rows_per_kind(7, [{"share": 1}] * 3)) == 7
    with pytest.raises(ValueError):
        generate.rows_per_kind(4, [{"share": 0}])


def test_processes():
    rw = np.asarray(generate.references(generate.key_of(1), _ref_spec()))
    steps = np.diff(rw, axis=1)
    assert abs(steps.std() - 1) < 0.1
    assert abs(steps.mean()) < 0.1
    with pytest.raises(ValueError):
        generate.references(generate.key_of(1), _ref_spec("brownian"))


def test_excerpts_are_found_where_they_were_cut():
    refs = generate.references(generate.key_of(3), _ref_spec())
    t = _traffic(queries=[{"kind": "excerpt", "share": 1,
                           "resample": [1.0, 1.0], "noise": 0.0}])
    q = np.asarray(generate.queries(generate.key_of(4), t, refs,
                                    _ref_spec()))[0]
    assert np.allclose(q.mean(axis=1), 0, atol=1e-5)
    assert np.allclose(q.std(axis=1), 1, atol=1e-4)
    r = np.asarray(refs)
    for row in q:          # an exact stretch of some reference
        hits = [(i, o) for i in range(3) for o in range(600 - 40)
                if np.allclose(reference.znorm(r[i, o:o + 40]), row,
                               atol=1e-4)]
        assert hits


def _sdtw_loop(q, r):
    """Full-matrix DP in float64 (bottom row)."""
    m, n = len(q), len(r)
    D = np.full((m + 1, n + 1), np.inf)
    D[0, :] = 0.0
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            D[i, j] = (q[i - 1] - r[j - 1]) ** 2 + min(
                D[i - 1, j], D[i, j - 1], D[i - 1, j - 1])
    return D[m, 1:]


def test_reference_sweep_matches_the_loop():
    rng = np.random.default_rng(0)
    q = reference.znorm(np.cumsum(rng.standard_normal((5, 9)), 1))
    r = reference.znorm(np.cumsum(rng.standard_normal((2, 30)), 1))
    ref_of = np.array([0, 1, 1, 0, 1])
    target = np.array([3, 29, 0, 17, -1])
    best, arg, at = reference.sweep(q, r, ref_of, target, block=2)
    for p in range(5):
        row = _sdtw_loop(q[p], r[ref_of[p]])
        assert best[p] == pytest.approx(row.min(), rel=1e-5)
        assert arg[p] == np.argmin(row)
        if target[p] >= 0:
            assert at[p] == pytest.approx(row[target[p]], rel=1e-5)
        else:
            assert at[p] == np.inf


def test_bfloat16_reference_is_far_off():
    rng = np.random.default_rng(1)
    q = reference.znorm(np.cumsum(rng.standard_normal((4, 64)), 1))
    r = reference.znorm(np.cumsum(rng.standard_normal((1, 512)), 1))
    b32, _, _ = reference.sweep(q, r, np.zeros(4, int))
    b16, _, _ = reference.sweep(q, r, np.zeros(4, int), dtype=jnp.bfloat16)
    assert np.max(np.abs(b16 - b32) / b32) > 1e-3


def test_counts_and_the_roofline_term():
    assert counts.cells(512, 2000, 100_000) == 102_400_000_000
    assert counts.sdtw_ops(10) == 50
    assert counts.sdtw_bytes(queries=2, m=3, references=1, n=5,
                             outputs=2) == 4 * (6 + 5 + 4)
    p = {"vpu_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert counts.roofline_s(ops=1e12, bytes_=1e9, peaks=p) == (1.0, "ops")
    assert counts.roofline_s(ops=1e9, bytes_=1e11, peaks=p) == (1.0,
                                                                 "bytes")


def test_the_paper_cell_is_bound_by_operations():
    p = peaks.peaks("TPU v5 lite")
    n_cells = counts.cells(512, 2000, 100_000)
    _, term = counts.roofline_s(
        ops=counts.sdtw_ops(n_cells),
        bytes_=counts.sdtw_bytes(queries=512, m=2000, references=1,
                                 n=100_000, outputs=2), peaks=p)
    assert term == "ops"


def test_an_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks("TPU v9")
    for entry in peaks.PEAKS.values():
        assert entry["vpu_source"] and entry["hbm_source"]
