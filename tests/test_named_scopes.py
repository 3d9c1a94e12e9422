"""Each device stage of the train step and of the batched render program
carries its named scope in the compiled HLO's ``op_name`` metadata, the
backward stages as ``transpose(jvp(...))``, and no stage's scope wraps
another's."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import projection as P
from repro.core.config import GSConfig
from repro.core.scopes import STAGE_SCOPES
from repro.core.sharding import make_mesh
from repro.core.train import init_state, make_batched_eval_render, make_train_step, state_shardings

from conftest import make_cam, make_scene

H = W = 64
RENDER_SCOPES = ("project", "depth_sort", "binning", "tile_gather", "raster")
_WRAPPER = re.compile(r"^(?:[A-Za-z_]+\()+|\)+$")


def op_names(hlo: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def stages_in(path: str) -> list[str]:
    return [p for p in (_WRAPPER.sub("", part) for part in path.split("/")) if p in STAGE_SCOPES]


@pytest.fixture(scope="module")
def programs():
    mesh = make_mesh((1, 1))
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=32, backend="pallas", batch_size=2,
                   binning="hier")
    state = jax.device_put(init_state(make_scene(n=512)), state_shardings(mesh))
    cams = [make_cam(H, W, dist=2.5 + 0.2 * i) for i in range(2)]
    cams = P.Camera(*[jnp.stack(x) for x in zip(*cams)])
    gt = jnp.zeros((2, H, W, 3), jnp.float32)
    step = make_train_step(mesh, cfg).lower(state, cams, gt).compile().as_text()
    render = make_batched_eval_render(mesh, cfg).lower(state.params, cams).compile().as_text()
    return {"train": op_names(step), "render": op_names(render), "train_hlo": step}


@pytest.mark.parametrize("scope", STAGE_SCOPES)
def test_train_step_carries_every_stage_scope(programs, scope):
    assert any(scope in stages_in(p) for p in programs["train"]), scope


@pytest.mark.parametrize("scope", RENDER_SCOPES)
def test_batched_render_carries_every_render_stage_scope(programs, scope):
    assert any(scope in stages_in(p) for p in programs["render"]), scope


@pytest.mark.parametrize("scope", ["tile_gather", "depth_sort", "raster", "project", "loss",
                                   "splat_exchange"])
def test_backward_stages_read_transpose_of_their_scope(programs, scope):
    """The gathers' backward scatter-adds keep their stage's name: the
    views are vmapped, so the path reads ``transpose(jvp(vmap(<scope>)))``;
    the splat exchange runs outside the vmap, and its psum_scatter reads
    ``transpose(jvp(splat_exchange))``."""
    pattern = re.compile(r"(^|/)transpose\(jvp\((vmap\()?%s\)+(/|$)" % scope)
    assert any(pattern.search(p) for p in programs["train"]), scope


def test_depth_sort_backward_has_no_scatter(programs):
    """The depth sort's backward is a gather through the inverse permutation:
    no scatter of the compiled train step carries the ``depth_sort`` scope."""
    scatters = [line for line in programs["train_hlo"].splitlines()
                if re.search(r"\bscatter\(", line)
                and any("depth_sort" in stages_in(p) for p in op_names(line))]
    assert not scatters, scatters[:3]


@pytest.mark.parametrize("program", ["train", "render"])
def test_no_stage_scope_wraps_another(programs, program):
    nested = {p for p in programs[program] if len(set(stages_in(p))) > 1}
    assert not nested, sorted(nested)[:5]


def test_the_pallas_kernels_carry_their_names(programs):
    names = " ".join(programs["train"])
    assert "tile_raster_fwd" in names and "tile_raster_bwd" in names
    assert "tile_raster_fwd" in " ".join(programs["render"])
