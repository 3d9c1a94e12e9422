"""gsproject Pallas kernel vs the production-projection oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gsproject.ops import project_packed

from conftest import make_cam, make_scene

SWEEP = [(64, 32, 32), (700, 64, 64), (1500, 48, 96), (1024, 64, 64)]


@pytest.mark.parametrize("n,h,w", SWEEP)
def test_forward_allclose(n, h, w):
    g = make_scene(n, seed=n)
    cam = make_cam(h, w)
    ref = np.asarray(project_packed(g, cam, backend="ref"))
    pal = np.asarray(project_packed(g, cam, backend="pallas"))
    finite = np.isfinite(ref)
    assert (np.isfinite(pal) == finite).all()  # inf depth pattern identical
    np.testing.assert_allclose(pal[finite], ref[finite], atol=2e-5, rtol=2e-5)


def test_grad_matches_ref():
    g = make_scene(300, seed=1)
    cam = make_cam(32, 32)

    def loss(gm, backend):
        p = project_packed(gm, cam, backend=backend)
        p = jnp.where(jnp.isfinite(p), p, 0.0)
        return jnp.sum(jnp.sin(p))  # bounded cotangents

    gr = jax.grad(lambda m: loss(m, "ref"))(g)
    gp = jax.grad(lambda m: loss(m, "pallas"))(g)
    for name, a, b in zip(g._fields, gr, gp):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(np.abs(a).max(), 1e-6)
        np.testing.assert_allclose(b, a, atol=2e-4 * scale, rtol=2e-3, err_msg=name)


def test_nonzero_sh_falls_back():
    """Degree > 0 SH has no Pallas kernel: asking for one raises instead of
    quietly running the reference; the reference itself serves it."""
    g = make_scene(64, seed=2)
    g = g._replace(sh=jnp.zeros((64, 4, 3)))
    cam = make_cam(32, 32)
    with pytest.raises(ValueError, match="degree-0"):
        project_packed(g, cam, backend="pallas")
    out = project_packed(g, cam, backend="ref")
    assert out.shape == (64, 11)
