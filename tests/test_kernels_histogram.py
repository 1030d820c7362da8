"""Histogram Pallas kernel vs jnp oracle: shape/dtype sweeps + conflict
instrumentation fidelity (paper §4)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import counters, timing
from repro.kernels.histogram import ops, ref


@pytest.mark.parametrize("n_pixels", [256, 2048, 5000, 8192])
@pytest.mark.parametrize("variant", ["hist", "hist2"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint8, np.int64])
def test_histogram_matches_ref(n_pixels, variant, dtype):
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (n_pixels, 4)).astype(dtype)
    out = ops.histogram(jnp.asarray(img.astype(np.int32)), variant=variant)
    expect = ref.histogram_ref(jnp.asarray(img.astype(np.int32)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    assert int(out.sum()) == n_pixels * 4


def _image(kind: str, n_pixels: int, num_bins: int) -> np.ndarray:
    rng = np.random.default_rng(3)
    if kind == "uniform":
        return rng.integers(0, num_bins, (n_pixels, 4)).astype(np.int32)
    img = np.empty((n_pixels, 4), np.int32)
    img[:] = rng.integers(0, num_bins, 4)
    if kind == "noise":       # solid, with a few 32-pixel noise blocks
        blocks = rng.choice(n_pixels // 32, 5, replace=False)
        img[:n_pixels // 32 * 32].reshape(-1, 32, 4)[blocks] = \
            rng.integers(0, num_bins, (5, 32, 4))
    return img


# (image, variant, num_bins, tile): both launches of the factored commit
# against ref.py, over hi axes of 32 (256 bins), 8 (64) and 13 -> 16 (100)
_COMMIT_CASES = (
    [(k, v, 256, 2048) for k in ("solid", "noise", "uniform")
     for v in ("hist", "hist2")]
    + [(k, v, 256, 256) for k in ("noise", "uniform")
       for v in ("hist", "hist2")]
    + [("uniform", v, nb, 2048) for nb in (64, 100) for v in ("hist", "hist2")]
    + [("solid", "hist", 100, 256)])


@pytest.mark.parametrize("kind,variant,num_bins,tile", _COMMIT_CASES)
def test_factored_commit_matches_ref_and_degrees(kind, variant, num_bins,
                                                 tile):
    img = _image(kind, 6000, num_bins)       # not a tile multiple: padded
    expect = np.asarray(ref.histogram_ref(jnp.asarray(img), num_bins))
    out = ops.histogram(jnp.asarray(img), num_bins=num_bins,
                        variant=variant, tile=tile)
    np.testing.assert_array_equal(np.asarray(out), expect)
    hist, deg = ops._histogram_and_degrees(
        jnp.asarray(img), num_bins=num_bins, variant=variant, tile=tile)
    np.testing.assert_array_equal(np.asarray(hist), expect)
    stream = ops.committed_index_stream(img, num_bins=num_bins,
                                        variant=variant, tile=tile)
    waves = stream.reshape(-1, 1024)
    assert deg.shape == (waves.shape[0],)
    np.testing.assert_array_equal(
        np.asarray(deg), [counters.wave_degree(w) for w in waves])


def test_factored_commit_exact_at_full_step():
    """A one-channel solid image at a tile of 8192 puts every value of a
    step, 8192 counts, into one cell, step after step."""
    img = np.full((8192 * 6, 1), 77, np.int32)
    out = np.asarray(ops.histogram(jnp.asarray(img), tile=8192))
    expect = np.zeros((1, 256), np.int64)
    expect[0, 77] = img.shape[0]
    np.testing.assert_array_equal(out, expect)


@pytest.mark.parametrize("variant", ["hist", "hist2"])
def test_histogram_weighted_matches_ref(variant):
    rng = np.random.default_rng(1)
    img = rng.integers(0, 256, (3000, 4)).astype(np.int32)
    w = rng.random(3000).astype(np.float32)
    out = ops.histogram_weighted(jnp.asarray(img), jnp.asarray(w),
                                 variant=variant)
    expect = ref.histogram_weighted_ref(jnp.asarray(img), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2000), st.integers(0, 2**31 - 1))
def test_histogram_property_random_images(n_pixels, seed):
    rng = np.random.default_rng(seed)
    img = rng.integers(0, 256, (n_pixels, 4)).astype(np.int32)
    h1 = np.asarray(ops.histogram(jnp.asarray(img), variant="hist"))
    h2 = np.asarray(ops.histogram(jnp.asarray(img), variant="hist2"))
    expect = np.stack([np.bincount(img[:, c], minlength=256)
                       for c in range(4)])
    np.testing.assert_array_equal(h1, expect)
    np.testing.assert_array_equal(h2, expect)  # reorder preserves counts


def test_instrumented_degrees_solid_vs_reordered():
    """The paper's core observation: reordering cuts serialization ~4x."""
    solid = np.full((4096, 4), 9, np.int32)
    _, tr1 = ops.histogram_instrumented(jnp.asarray(solid), variant="hist")
    _, tr2 = ops.histogram_instrumented(jnp.asarray(solid), variant="hist2")
    assert tr1.degree.mean() == 32.0
    assert tr2.degree.mean() == 8.0


def test_instrumented_degrees_uniform():
    rng = np.random.default_rng(2)
    img = rng.integers(0, 256, (4096, 4)).astype(np.int32)
    _, tr = ops.histogram_instrumented(jnp.asarray(img), variant="hist")
    assert 1.0 <= tr.degree.mean() <= 4.0   # paper: e ~ 2-3 for uniform


def test_instruction_classes():
    img = np.zeros((2048, 4), np.int32)
    _, popc = ops.histogram_instrumented(jnp.asarray(img))
    _, fao = ops.histogram_instrumented(jnp.asarray(img), force_fao=True)
    _, cas = ops.histogram_instrumented(jnp.asarray(img), weighted=True)
    assert set(popc.job_class) == {timing.POPC}
    assert set(fao.job_class) == {timing.FAO}
    assert set(cas.job_class) == {timing.CAS}


def test_padding_correction():
    img = np.full((100, 4), 3, np.int32)   # far from tile multiple
    out = np.asarray(ops.histogram(jnp.asarray(img)))
    expect = np.asarray(ref.histogram_ref(jnp.asarray(img)))
    np.testing.assert_array_equal(out, expect)
