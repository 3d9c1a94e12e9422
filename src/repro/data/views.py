"""View data pipeline: GT render cache + shuffled batch iterator.

The paper trains against 448 synthetic orbit views; rendering those GT images
(ray-marched isosurface) is expensive, so they are produced once and cached
on disk, then served as shuffled host batches. The train loop draws each
batch while the previous step runs and puts its GT straight into the step's
sharding (:func:`draw_batch`).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.projection import Camera
from repro.obs import NO_SPAN
from repro.volume.cameras import camera_slice, orbit_cameras
from repro.volume.datasets import VolumeSpec
from repro.volume.raymarch import render_isosurface


class ViewDataset:
    def __init__(
        self,
        vol: VolumeSpec,
        *,
        n_views: int,
        img_h: int,
        img_w: int,
        radius: float = 3.0,
        cache_dir: str | None = None,
        n_steps_raymarch: int = 128,
        seed: int = 0,
    ):
        self.img_h, self.img_w = img_h, img_w
        self.n_views = n_views
        self.cams = orbit_cameras(n_views, img_h=img_h, img_w=img_w, radius=radius)
        self.rng = np.random.default_rng(seed)

        cache_file = None
        if cache_dir:
            os.makedirs(cache_dir, exist_ok=True)
            # every setting that shapes the images is in the name, so a file
            # made under other settings is never read back
            key = (
                f"{vol.name}_r{vol.field.shape[0]}_e{vol.extent:g}_{n_views}v_{img_h}x{img_w}"
                f"_rad{radius:g}_s{n_steps_raymarch}"
            )
            cache_file = os.path.join(cache_dir, key + ".npy")
        if cache_file and os.path.exists(cache_file):
            self.gt = np.load(cache_file)
        else:
            field = jnp.asarray(vol.field)
            imgs = []
            for i in range(n_views):
                img = render_isosurface(
                    field, vol.isovalue, camera_slice(self.cams, i),
                    img_h=img_h, img_w=img_w, extent=vol.extent, n_steps=n_steps_raymarch,
                )
                imgs.append(np.asarray(img))
            self.gt = np.stack(imgs).astype(np.float32)
            if cache_file:
                np.save(cache_file, self.gt)

    def batches(self, batch_size: int, *, steps: int):
        """Yield (Camera batch, host numpy gt batch) `steps` times (with replacement
        across epochs, without within an epoch — 3D-GS convention). When an
        epoch runs low the next permutation is *prepended*, so the leftover
        views are still drawn before any view repeats: every view is sampled
        exactly once per epoch. At the epoch seam a draw that would duplicate
        a view already in the batch is swapped deeper into the new
        permutation (possible whenever batch_size <= n_views)."""
        order = []
        for _ in range(steps):
            sel = []
            for _ in range(batch_size):
                if not order:
                    order = list(self.rng.permutation(self.n_views))
                if order[-1] in sel:
                    for j in range(len(order) - 1):
                        if order[j] not in sel:
                            order[-1], order[j] = order[j], order[-1]
                            break
                sel.append(order.pop())
            sel = np.asarray(sel)
            # a host index: indexing with a device array waits for the work
            # in flight on the device, and the draw must overlap the step
            yield camera_slice(self.cams, sel), self.gt[sel]

    def view(self, i: int):
        return camera_slice(self.cams, i), jnp.asarray(self.gt[i])


def draw_batch(batches, sharding, rec, rid: int, step: int):
    """The next ``(cams, gt)`` of the iterator ``batches``, with ``gt`` (host
    numpy or a ``jax.Array``) put on ``sharding``, the train step's own, so
    each device receives only its block; timed as step ``step``'s ``batch``
    span on ``rec``. None once the stream has ended."""
    with rec.span(rid, "batch", step=step) if rec else NO_SPAN as sp:
        got = next(batches, None)
        if got is None:
            sp.drop()  # the stream ended: no batch was assembled
            return None
        cams, gt = got
        return cams, jax.device_put(gt, sharding)
