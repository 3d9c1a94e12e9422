"""Hypothesis property tests on the rendering system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import gaussians as G
from repro.core import projection as P
from repro.core import render as R
from repro.kernels.tile_raster.ref import compose_tile

from conftest import make_cam, make_scene


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(8, 128),
    seed=st.integers(0, 10_000),
    opac=st.floats(-3.0, 3.0),
)
def test_transmittance_and_range(n, seed, opac):
    """0 <= T <= 1; colors in [0, 1] when splat colors are; more opacity
    never increases transmittance."""
    g = make_scene(n, seed=seed)
    g = g._replace(opacity_logit=jnp.full((n,), opac, jnp.float32))
    cam = make_cam(32, 32)
    img, t = R.render(g, cam, img_h=32, img_w=32, tile_h=16, tile_w=16, k_per_tile=128)
    t = np.asarray(t)
    img = np.asarray(img)
    assert np.all(t >= -1e-6) and np.all(t <= 1 + 1e-6)
    assert np.all(img >= -1e-5) and np.all(img <= 1 + 1e-5)

    g2 = g._replace(opacity_logit=g.opacity_logit + 1.0)
    _, t2 = R.render(g2, cam, img_h=32, img_w=32, tile_h=16, tile_w=16, k_per_tile=128)
    assert np.all(np.asarray(t2) <= t + 1e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.sampled_from([16, 64, 256]))
def test_compose_permutation_of_padding_invariant(seed, k):
    """Invalid (masked) splats never affect the composite."""
    r = np.random.default_rng(seed)
    n_valid = r.integers(1, k)
    splats = r.normal(0, 1, (k, 11)).astype(np.float32)
    splats[:, P.OP] = r.uniform(0, 0.9, k)
    splats[:, P.CA] = r.uniform(0.1, 2, k)
    splats[:, P.CC] = r.uniform(0.1, 2, k)
    splats[:, P.CB] = 0.0
    splats[:, P.MX] = r.uniform(0, 16, k)
    splats[:, P.MY] = r.uniform(0, 16, k)
    valid = np.arange(k) < n_valid
    px = np.arange(16, dtype=np.float32) + 0.5
    py = np.zeros(16, dtype=np.float32) + 0.5
    bg = jnp.zeros(3)
    out1, t1 = compose_tile(jnp.asarray(splats), jnp.asarray(valid), jnp.asarray(px), jnp.asarray(py), bg)
    # scramble the masked-out tail
    splats2 = splats.copy()
    splats2[n_valid:] = r.normal(0, 10, (k - n_valid, 11))
    out2, t2 = compose_tile(jnp.asarray(splats2), jnp.asarray(valid), jnp.asarray(px), jnp.asarray(py), bg)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_tile_lists_cover_naive(seed):
    """Tiled render with K >= N equals the naive oracle (tile binning loses
    nothing)."""
    n = 100
    g = make_scene(n, seed=seed)
    cam = make_cam(32, 64)
    packed = P.project(g, cam)
    ps, _ = P.sort_by_depth(packed)
    img_t, _ = R.render_packed(ps, img_h=32, img_w=64, tile_h=16, tile_w=16, k_per_tile=128)
    from repro.kernels.tile_raster.ref import rasterize_naive

    img_n, _ = rasterize_naive(ps, 32, 64, jnp.zeros(3))
    np.testing.assert_allclose(np.asarray(img_t), np.asarray(img_n), atol=1e-6)


def _plain_depth_sort(packed):
    """``sort_by_depth``'s order, with autodiff's own transpose of the gather."""
    order = P.sort_by_depth(jax.lax.stop_gradient(packed))[1]
    return packed[order], order


@pytest.mark.parametrize("views,depths", [(None, "distinct"), (4, "distinct"), (4, "inf"),
                                          (None, "tied")])
def test_depth_sort_gradient_equals_plain_gather(views, depths):
    """The gradient through ``sort_by_depth`` (a gather through the inverse
    permutation) equals autodiff of ``packed[order]`` element for element:
    one view or vmapped views, invalid splats at +inf depth, tied depths."""
    r = np.random.default_rng(7)
    shape = (500, P.PACKED_DIM) if views is None else (views, 500, P.PACKED_DIM)
    packed = r.normal(0, 1, shape).astype(np.float32)
    depth = r.uniform(0.2, 5.0, shape[:-1]).astype(np.float32)
    if depths == "inf":
        depth[..., ::3] = np.inf
    if depths == "tied":
        depth = np.round(depth)
    packed[..., P.DEPTH] = depth
    w = jnp.asarray(r.normal(0, 1, shape).astype(np.float32))

    def loss(sort):
        fn = sort if views is None else jax.vmap(sort)

        def f(x):
            y = fn(x)[0]
            return jnp.sum(jnp.where(jnp.isfinite(y), y * w, 0.0))

        return f

    x = jnp.asarray(packed)
    got, want = (jax.jit(jax.value_and_grad(loss(s)))(x)
                 for s in (P.sort_by_depth, _plain_depth_sort))
    assert float(got[0]) == float(want[0])
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), depth_scale=st.floats(0.5, 2.0))
def test_projection_depth_ordering(seed, depth_scale):
    """Gaussians behind the camera are marked dead; depths are positive for
    visible ones."""
    g = make_scene(64, seed=seed, spread=depth_scale * 2)
    cam = make_cam(32, 32, dist=1.0)
    packed = np.asarray(P.project(g, cam))
    valid = packed[:, P.RAD] > 0
    assert np.all(packed[valid, P.DEPTH] > 0)
    assert np.all(packed[~valid, P.OP] == 0)
