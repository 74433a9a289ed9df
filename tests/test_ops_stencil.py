"""Stencil kernel incarnations (ops/stencil.py): the XLA tap loop and the
blocked Pallas variant (interpret mode here; Mosaic in chip_smoke.py) agree
with the numpy oracle across shapes, dtypes, batching and lane tiles.
"""

import numpy as np
import pytest

from parsec_tpu.ops.stencil import (_LANE_TILE, stencil1d_pallas,
                                    stencil1d_xla)


def _oracle(padded, w):
    n = padded.shape[-1] - len(w) + 1
    out = np.zeros(padded.shape[:-1] + (n,), np.float64)
    for j in range(len(w)):
        out += w[j] * padded[..., j:j + n].astype(np.float64)
    return out


@pytest.mark.parametrize("R", [1, 2, 4])
@pytest.mark.parametrize("n", [16, 128, 1000])
def test_xla_matches_oracle(R, n):
    rng = np.random.default_rng(R * n)
    w = rng.standard_normal(2 * R + 1)
    p = rng.standard_normal(n + 2 * R).astype(np.float32)
    got = np.asarray(stencil1d_xla(p, w))
    np.testing.assert_allclose(got, _oracle(p, w), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R", [1, 4])
@pytest.mark.parametrize("shape", [(256,), (4, 256), (3, 1000)])
def test_pallas_matches_xla(R, shape):
    """Interpret mode: same numerics as the XLA loop."""
    rng = np.random.default_rng(R)
    w = rng.standard_normal(2 * R + 1)
    p = rng.standard_normal(shape[:-1] + (shape[-1] + 2 * R,)).astype(
        np.float32)
    got = np.asarray(stencil1d_pallas(p, w, interpret=True))
    want = np.asarray(stencil1d_xla(p, w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got.shape == shape


def test_pallas_row_spanning_lane_tiles():
    """A row longer than one lane tile: the taps that straddle a tile
    boundary read the right neighbour's head, the last tile is ragged."""
    R = 4
    w = np.random.default_rng(1).standard_normal(2 * R + 1)
    n = 2 * _LANE_TILE + 72
    p = np.random.default_rng(2).standard_normal(n + 2 * R).astype(
        np.float32)
    got = np.asarray(stencil1d_pallas(p, w, interpret=True))
    assert got.shape == (n,)
    np.testing.assert_allclose(got, _oracle(p, w), rtol=1e-5, atol=1e-5)


def test_pallas_never_interprets_by_itself():
    """Off-TPU a call without ``interpret=True`` goes to Mosaic and fails:
    the kernel does not pick a mode from the backend."""
    with pytest.raises(ValueError, match="interpret mode"):
        np.asarray(stencil1d_pallas(np.ones(130, np.float32),
                                    np.array([0.25, 0.5, 0.25])))


def test_dtype_roundtrip():
    """f32 stays f32 through both kernels; f64 input (downcast under the
    suite's x64-off config) still matches the oracle at f32 tolerance."""
    w = np.array([0.2, 0.6, 0.2])
    p32 = np.ones(66, np.float32)
    assert np.asarray(stencil1d_xla(p32, w)).dtype == np.float32
    got = np.asarray(stencil1d_pallas(p32, w, interpret=True))
    assert got.dtype == np.float32
    p64 = np.linspace(0, 1, 66)
    np.testing.assert_allclose(
        np.asarray(stencil1d_pallas(p64, w, interpret=True)),
        _oracle(p64.astype(np.float32), w), rtol=1e-5, atol=1e-5)


def test_pallas_three_dim_batch():
    """Leading dims beyond 2 flatten and restore (same contract as xla)."""
    w = np.array([0.25, 0.5, 0.25])
    p = np.random.default_rng(0).standard_normal((2, 3, 130)).astype(
        np.float32)
    got = np.asarray(stencil1d_pallas(p, w, interpret=True))
    want = np.asarray(stencil1d_xla(p, w))
    assert got.shape == (2, 3, 128)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
