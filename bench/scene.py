"""The scene of a configuration: isosurface points, colours and orbit views.

The scene belongs to the configuration, not to the seed. The first run of a
configuration in a checkout builds it through the program's data path
(``build_dataset``: the volume, its isosurface points on the host, and the
ground-truth (GT) views ray-marched on the device at ``gt_res``) and keeps it
under ``bench/.cache/<config>/``; later runs read it back. The GT is then
upsampled by nearest neighbour to the training resolution.

The seed picks only a jitter of the initial Gaussians: positions move by
under a voxel and colours by a few hundredths, so every seed trains and
serves the same amount of work.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache"


@dataclasses.dataclass
class Scene:
    points: np.ndarray   # (M, 3) float32 isosurface points
    colors: np.ndarray   # (M, 3) float32 shaded colours
    gt: np.ndarray       # (V, gt_res, gt_res, 3) float32 orbit views
    build_s: float       # seconds spent building (0 when read from the cache)


def scene_key(config: dict) -> dict:
    """Every setting that shapes the cached scene."""
    keys = ("dataset", "volume", "volume_res", "n_views", "orbit_radius", "gt_res",
            "gt_raymarch_steps")
    return {k: config[k] for k in keys}


def load_scene(config: dict, *, cache_root: Path = CACHE) -> Scene:
    d = cache_root / config["name"]
    key = scene_key(config)
    meta = d / "scene.json"
    if meta.is_file() and json.loads(meta.read_text()) == key:
        return Scene(np.load(d / "points.npy"), np.load(d / "colors.npy"),
                     np.load(d / "gt.npy"), 0.0)
    t0 = time.perf_counter()
    scene = build_scene(config)
    d.mkdir(parents=True, exist_ok=True)
    np.save(d / "points.npy", scene.points)
    np.save(d / "colors.npy", scene.colors)
    np.save(d / "gt.npy", scene.gt)
    meta.write_text(json.dumps(key))  # written last: a cut build is rebuilt
    scene.build_s = time.perf_counter() - t0
    return scene


def build_scene(config: dict) -> Scene:
    import jax.numpy as jnp

    from cameras import orbit, to_program
    from repro.volume import datasets as VD
    from repro.volume.isosurface import extract_isosurface_points
    from repro.volume.raymarch import render_isosurface

    vol = getattr(VD, config["volume"])(res=config["volume_res"])
    pts, _, cols = extract_isosurface_points(vol, max_points=None)
    res = config["gt_res"]
    field = jnp.asarray(vol.field)
    views = [render_isosurface(field, vol.isovalue, to_program(cam), img_h=res, img_w=res,
                               extent=vol.extent, n_steps=config["gt_raymarch_steps"])
             for cam in orbit(config, res)]
    gt = np.stack([np.asarray(v, np.float32) for v in views])
    return Scene(np.asarray(pts, np.float32), np.asarray(cols, np.float32), gt, 0.0)


def upsample(gt: np.ndarray, res: int) -> np.ndarray:
    """Nearest-neighbour upsampling of (V, r, r, 3) views to (V, res, res, 3)."""
    f = res // gt.shape[1]
    if f * gt.shape[1] != res:
        raise ValueError(f"training resolution {res} is not a multiple of GT {gt.shape[1]}")
    return np.repeat(np.repeat(gt, f, axis=1), f, axis=2)


def jitter(scene: Scene, config: dict, traffic: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded initial positions and colours (the only per-seed part of the model)."""
    rng = np.random.default_rng([seed, 0x5CE4E])
    voxel = 2.0 * config["volume_extent"] / (config["volume_res"] - 1)
    dp = rng.uniform(-1.0, 1.0, scene.points.shape) * traffic["jitter_voxels"] * voxel
    dc = rng.uniform(-1.0, 1.0, scene.colors.shape) * traffic["jitter_color"]
    pts = (scene.points + dp).astype(np.float32)
    cols = np.clip(scene.colors + dc, 0.0, 1.0).astype(np.float32)
    return pts, cols
