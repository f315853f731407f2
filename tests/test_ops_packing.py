"""Packing invariants of the kernel prep path (ops.py) and the input
validation contract of the public API."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import sdtw
from repro.core.ref import sdtw_ref
from repro.kernels import ops
from repro.kernels.sdtw_wavefront import LANES, SUBLANES
from repro.kernels.wavefront import query_pack_len


def test_swizzle_round_trip(rng):
    r = rng.normal(size=(1000,)).astype(np.float32)
    w = 4
    layout = ops.swizzle_reference(jnp.asarray(r), w)
    flat = np.asarray(ops.unswizzle_reference(layout))
    assert flat.shape[0] % (LANES * w) == 0
    np.testing.assert_array_equal(flat[:1000], r)
    np.testing.assert_array_equal(flat[1000:], ops.PAD_VALUE)


def test_swizzle_index_mapping(rng):
    """layout[b, k, l] == r[(b*LANES + l)*w + k] — the DTWax offline
    reference layout the kernel docstring promises."""
    w = 2
    r = np.arange(LANES * w * 2, dtype=np.float32)   # exactly 2 blocks
    layout = np.asarray(ops.swizzle_reference(jnp.asarray(r), w))
    for b in range(2):
        for k in range(w):
            for l in range(0, LANES, 17):
                assert layout[b, k, l] == r[(b * LANES + l) * w + k]


def test_prepare_queries_layout(rng):
    B, M = 3, 20
    q = rng.normal(size=(B, M)).astype(np.float32)
    qk = np.asarray(ops.prepare_queries(jnp.asarray(q)))
    mp = query_pack_len(M)
    assert qk.shape == (1, SUBLANES, mp)
    # every per-step window, read as two aligned lane tiles, stays
    # inside the row: the last one starts at M + LANES - 2
    assert mp >= (M + LANES - 2) // LANES * LANES + 2 * LANES
    # row s holds the reversed query behind LANES-1 zeros, then zeros
    for s in range(B):
        np.testing.assert_array_equal(qk[0, s, :LANES - 1], 0.0)
        np.testing.assert_array_equal(
            qk[0, s, LANES - 1:LANES - 1 + M], q[s, ::-1])
        np.testing.assert_array_equal(qk[0, s, LANES - 1 + M:], 0.0)
    # rows beyond B are zero padding, dropped by the [:B] trim
    np.testing.assert_array_equal(qk[0, B:], 0.0)


@pytest.mark.parametrize("b", [1, 5, 8])
def test_prepped_path_matches_oracle_and_trims(rng, b):
    """The split prep + dispatch path equals the oracle per-row and the
    [:B] trim drops the padded query rows."""
    q = rng.normal(size=(b, 16)).astype(np.float32)
    r = rng.normal(size=(300,)).astype(np.float32)
    qk = ops.prepare_queries(jnp.asarray(q))
    rk = ops.swizzle_reference(jnp.asarray(r), 4)
    costs, ends = ops.sdtw_wavefront_prepped(
        qk, rk, batch=b, m=16, n=300, segment_width=4, interpret=True)
    assert costs.shape == (b,) and ends.shape == (b,)
    c0, e0 = sdtw_ref(jnp.asarray(q), jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(costs), np.asarray(c0),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(np.asarray(ends), np.asarray(e0))


def test_pad_columns_never_win_and_ends_clamped(rng):
    """Heavily padded reference (N far below the LANES*w block size):
    PAD_VALUE columns must not win the argmin and every returned end
    index must stay inside the true reference."""
    for n in (150, 513, 1000):
        q = rng.normal(size=(4, 12)).astype(np.float32)
        r = rng.normal(size=(n,)).astype(np.float32)
        # plant the best match at the very tail, next to the padding
        r[n - 12:] = q[0, :12]
        c, e = ops.sdtw_wavefront(jnp.asarray(q), jnp.asarray(r),
                                  segment_width=4, interpret=True)
        assert np.asarray(e).max() < n
        c0, e0 = sdtw_ref(jnp.asarray(q), jnp.asarray(r))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(e0))
        assert int(np.asarray(e)[0]) == n - 1


def test_sdtw_validates_inputs(rng):
    q = rng.normal(size=(2, 8)).astype(np.float32)
    r = rng.normal(size=(64,)).astype(np.float32)
    with pytest.raises(ValueError, match="2-D"):
        sdtw(q[0], r)
    with pytest.raises(ValueError, match="1-D"):
        sdtw(q, np.stack([r, r]))
    with pytest.raises(ValueError, match="empty query batch"):
        sdtw(q[:0], r)
    with pytest.raises(ValueError, match="zero-length"):
        sdtw(q[:, :0], r)
    with pytest.raises(ValueError, match="empty reference"):
        sdtw(q, r[:0])
    with pytest.raises(ValueError, match="segment_width"):
        sdtw(q, r, segment_width=0)
    with pytest.raises(ValueError, match="unknown backend"):
        sdtw(q, r, backend="gpu")
