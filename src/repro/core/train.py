"""Distributed 3D-GS train step (the paper's contribution, JAX-native).

One jitted step = shard_map over the (data, model) mesh:
  project local Gaussian shard -> all_gather projected splats over "model"
  -> depth sort -> tile-bin -> composite local pixel strip -> distributed
  L1+D-SSIM -> backward (all_gather transposes to psum_scatter) -> fused
  psum of packed grads over "data" -> sharded Adam update.

The step's shard_map checks which mesh axes each value varies over
(``check_vma=True``), so each collective transposes by its type and every
shard's gradient is that of the one global loss, on any mesh shape.

The "replicated baseline" of the paper (single-GPU semantics, data-parallel
only) is the same code on a mesh with model=1.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.core import gaussians as G
from repro.core import projection as P
from repro.core import render as R
from repro.core.config import GSConfig
from repro.core.sharding import distributed_gs_loss, exchange_splats
from repro.optim.adam import AdamState, adam_init, adam_update
from repro.optim.schedules import expon_lr, grendel_lr_scale
from repro.utils.tree import pack_pytree


class GSTrainState(NamedTuple):
    params: G.GaussianModel        # sharded over "model" (axis 0 of each leaf)
    adam: AdamState                # sharded like params
    step: jax.Array                # () int32, replicated
    # densification statistics, sharded like params (per local Gaussian)
    grad2d_accum: jax.Array        # (n,) sum of view-space grad norms
    vis_count: jax.Array           # (n,) number of views seen in
    max_radii: jax.Array           # (n,) max screen-space radius


def init_state(params: G.GaussianModel) -> GSTrainState:
    n = params.n
    return GSTrainState(
        params=params,
        adam=adam_init(params),
        step=jnp.zeros((), jnp.int32),
        grad2d_accum=jnp.zeros((n,), jnp.float32),
        vis_count=jnp.zeros((n,), jnp.float32),
        max_radii=jnp.zeros((n,), jnp.float32),
    )


def state_shardings(mesh: Mesh, model_axis: str = "model"):
    """NamedShardings for a GSTrainState on the given mesh."""
    shard0 = NamedSharding(mesh, PS(model_axis))
    rep = NamedSharding(mesh, PS())
    return GSTrainState(
        params=G.GaussianModel(*([shard0] * 5)),
        adam=AdamState(G.GaussianModel(*([shard0] * 5)), G.GaussianModel(*([shard0] * 5)), rep),
        step=rep,
        grad2d_accum=shard0,
        vis_count=shard0,
        max_radii=shard0,
    )


def resolve_gather_mode(cfg: GSConfig, mesh: Mesh, *, data_axes=("data",), model_axis="model") -> str:
    """The comm schedule ``make_train_step`` will actually use (resolves
    ``"auto"`` exactly like the step builder does)."""
    d = 1
    for a in data_axes:
        d *= mesh.shape[a]
    m = mesh.shape[model_axis]
    mode = cfg.gather_mode
    if mode == "auto":
        mode = "params3d" if (cfg.batch_size // d) >= 2 and m > 1 else "projected"
    return mode


def all_gather_bytes_per_step(
    cfg: GSConfig, mesh: Mesh, n_total: int,
    *, data_axes: tuple[str, ...] = ("data",), model_axis: str = "model",
) -> int:
    """Analytic model-axis all-gather payload one train step materializes per
    device (bytes of the gathered tensor; float32). This is the collective
    the paper's scaling lives or dies on, so it travels with the per-step
    telemetry: ``projected`` gathers 11-float splats per local view, the
    beyond-paper ``params3d`` schedule gathers the 3D state once per step."""
    m = mesh.shape[model_axis]
    if m <= 1:
        return 0
    d = 1
    for a in data_axes:
        d *= mesh.shape[a]
    if resolve_gather_mode(cfg, mesh, data_axes=data_axes, model_axis=model_axis) == "params3d":
        sh_k = (cfg.sh_degree + 1) ** 2
        floats = n_total * (11 + 3 * sh_k)
    else:
        b_local = max(cfg.batch_size // d, 1)
        floats = b_local * n_total * P.PACKED_DIM
    return int(floats) * 4


def shard_balance(state: GSTrainState, *, opacity_thresh: float = 0.005) -> dict:
    """Per-model-shard load statistics, the trigger signal for dynamic
    rebalancing (Grendel's result: static Gaussian splits skew).

    Walks the params' ``addressable_shards`` — the same shard-by-shard pull
    checkpoint save uses, deduped across data-axis replicas — and reduces
    each shard ON ITS DEVICE (a handful of scalars cross to host, never the
    arrays): ``alive`` counts Gaussians whose opacity clears
    ``opacity_thresh`` (dead padding + pruned slots don't load a worker),
    ``visible`` counts slots that have ever projected on screen
    (``max_radii > 0``), and ``projected`` sums the accumulated per-view
    visibility tallies (``vis_count``) — the actual splat workload each
    shard contributed since the densify stats were last zeroed.

    ``imbalance`` is max/mean of the per-shard alive counts (1.0 = perfectly
    balanced; 0.0 only for an all-dead model).
    """
    import numpy as np

    logit_thresh = float(np.log(opacity_thresh / (1.0 - opacity_thresh)))

    def _shards(leaf):
        seen = {}
        for shard in leaf.addressable_shards:
            key = tuple((s.start or 0) for s in shard.index)
            if key not in seen:
                seen[key] = shard.data
        return [seen[k] for k in sorted(seen)]

    opac = _shards(state.params.opacity_logit)
    vis = _shards(state.vis_count)
    radii = _shards(state.max_radii)
    capacity = [int(s.shape[0]) for s in opac]
    alive = [int(jnp.sum(s > logit_thresh)) for s in opac]
    visible = [int(jnp.sum(r > 0.0)) for r in radii]
    projected = [float(jnp.sum(v)) for v in vis]
    mean_alive = sum(alive) / len(alive)
    imbalance = (max(alive) / mean_alive) if mean_alive > 0 else 0.0
    return {
        "n_shards": len(capacity),
        "capacity": capacity,
        "alive": alive,
        "visible": visible,
        "projected": projected,
        "alive_total": sum(alive),
        "imbalance": imbalance,
    }


def record_shard_balance(metrics, bal: dict, *, prefix: str = "train") -> None:  # analysis: declare(train.shard_capacity.s*, train.shard_alive.s*, train.shard_visible.s*, train.shard_projected.s*, train.alive_total, train.shard_imbalance)
    """Land a :func:`shard_balance` result on a registry: per-shard gauges
    ``<prefix>.shard_alive.s<i>`` / ``.shard_visible.s<i>`` /
    ``.shard_projected.s<i>`` / ``.shard_capacity.s<i>`` plus the
    ``<prefix>.shard_imbalance`` gauge a rebalancing pass will trigger on."""
    for i in range(bal["n_shards"]):
        metrics.gauge(f"{prefix}.shard_capacity.s{i}").set(bal["capacity"][i])
        metrics.gauge(f"{prefix}.shard_alive.s{i}").set(bal["alive"][i])
        metrics.gauge(f"{prefix}.shard_visible.s{i}").set(bal["visible"][i])
        metrics.gauge(f"{prefix}.shard_projected.s{i}").set(bal["projected"][i])
    metrics.gauge(f"{prefix}.alive_total").set(bal["alive_total"])
    metrics.gauge(f"{prefix}.shard_imbalance").set(round(float(bal["imbalance"]), 6))


def make_train_step(
    mesh: Mesh,
    cfg: GSConfig,
    *,
    data_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
):
    """Build the jitted distributed train step for a fixed Gaussian count.

    Returned fn: (state, cams: Camera batched (B,...), gt: (B,H,W,3)) ->
    (state, metrics). Views are sharded over ``data_axes``; pixels strips over
    ``model_axis`` when cfg.pixel_parallel (each device then holds both a
    Gaussian shard and a pixel block — the Grendel worker model).
    """
    d = 1
    for a in data_axes:
        d *= mesh.shape[a]
    m = mesh.shape[model_axis]
    strip = cfg.pixel_parallel and m > 1
    if strip:
        assert cfg.img_h % (m * cfg.tile_h) == 0, "img_h must split into model-axis strips of whole tiles"
    assert cfg.batch_size % d == 0, "global batch must divide data axes"
    strip_h = cfg.img_h // m if strip else cfg.img_h
    bg = jnp.asarray(cfg.bg, jnp.float32)
    all_axes = tuple(data_axes) + (model_axis,)
    # comm-schedule selection (EXPERIMENTS.md G3 ablation): the 3D-state
    # gather wins whenever a worker renders >= 2 views of the same params
    gather_mode = resolve_gather_mode(cfg, mesh, data_axes=data_axes, model_axis=model_axis)

    def local_step(state: GSTrainState, cams: P.Camera, gt: jax.Array):
        # each data shard differentiates the loss of its own views: the
        # gradient varies over the data axes until the fused psum below
        # (Adam updates state.params, which the data axes replicate)
        params = jax.lax.pcast(state.params, data_axes, to="varying")
        n_local = params.means.shape[0]
        b_local = gt.shape[0]

        def loss_fn(p, probe):
            if gather_mode == "params3d":
                # ---- beyond-paper comm schedule: all-gather the 3D state
                # ONCE per step (14+3K floats/gaussian) instead of 11-float
                # projected splats PER VIEW; projection recomputed locally.
                # Wins whenever B_local >= 2 (§Perf GS iteration G3).
                flat3d = jnp.concatenate(
                    [p.means, p.log_scales, p.quats, p.opacity_logit[:, None],
                     p.sh.reshape(n_local, -1)], axis=1,
                )
                flat_all = exchange_splats(flat3d, model_axis, axis=0)
                n_total = flat_all.shape[0]
                sh_k = p.sh.shape[1]
                p_full = G.GaussianModel(
                    means=flat_all[:, 0:3],
                    log_scales=flat_all[:, 3:6],
                    quats=flat_all[:, 6:10],
                    opacity_logit=flat_all[:, 10],
                    sh=flat_all[:, 11:].reshape(n_total, sh_k, 3),
                )
                gathered = jax.vmap(lambda cam: P.project(p_full, cam))(cams)  # (B_l,N,11)
                gathered = gathered + jnp.pad(probe, ((0, 0), (0, 0), (0, P.PACKED_DIM - 2)))
                shard0 = jax.lax.axis_index(model_axis) * n_local
                radii_local = jax.lax.dynamic_slice_in_dim(
                    gathered[..., P.RAD], shard0, n_local, axis=1
                )  # own shard's visibility stats
            else:
                # ---- paper-faithful (Grendel): project own shard, gather 2D
                def proj_one(cam):
                    return P.project(p, cam)

                packed = jax.vmap(proj_one)(cams)                  # (B_l, n_local, 11)
                packed = packed + jnp.pad(probe, ((0, 0), (0, 0), (0, P.PACKED_DIM - 2)))
                radii_local = packed[..., P.RAD]                   # (B_l, n_local)
                gathered = exchange_splats(packed, model_axis, axis=1)

            if strip:
                off = (jax.lax.axis_index(model_axis) * strip_h).astype(jnp.float32)
                gathered = gathered.at[..., P.MY].add(-off)

            def render_one(pk):
                pk_sorted, _ = P.sort_by_depth(pk)
                img, _ = R.render_packed(
                    pk_sorted,
                    img_h=strip_h,
                    img_w=cfg.img_w,
                    tile_h=cfg.tile_h,
                    tile_w=cfg.tile_w,
                    k_per_tile=cfg.k_per_tile,
                    bg=bg,
                    backend=cfg.backend,
                    binning=cfg.binning,
                )
                return img

            imgs = jax.vmap(render_one)(gathered)                  # (B_l, strip_h, W, 3)
            loss = distributed_gs_loss(
                imgs,
                gt,
                lam=cfg.lambda_dssim,
                strip_axis=model_axis if strip else None,
                reduce_axes=all_axes,
            )
            return loss, radii_local

        # The probe's gradient is each splat's view-space positional gradient
        # over the whole view. It varies over the views' axes; over the model
        # axis only where each shard projects its own splats. Under params3d
        # every shard probes all N splats of its own strip, so the probe is
        # replicated over the model axis and its gradient summed over strips.
        if gather_mode == "params3d":
            probe_n, probe_axes = n_local * m, tuple(data_axes)
        else:
            probe_n, probe_axes = n_local, all_axes
        probe = jax.lax.pcast(jnp.zeros((b_local, probe_n, 2), jnp.float32), probe_axes,
                              to="varying")
        (loss, radii), (grads, probe_grad) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
            params, probe
        )

        with jax.named_scope("grad_reduce"):
            # ---- the paper's fused all-reduce: ONE collective over packed grads
            flat, unpack = pack_pytree(grads)
            flat = jax.lax.psum(flat, data_axes)
            grads = unpack(flat)
            # view-space positional gradient stats for densification
            g2d = jnp.sqrt(jnp.sum(probe_grad * probe_grad, axis=-1) + 1e-20)  # (B_l, probe_n)
            if gather_mode == "params3d":
                g2d = jax.lax.dynamic_slice_in_dim(
                    g2d, jax.lax.axis_index(model_axis) * n_local, n_local, axis=1
                )
            g2d = jax.lax.psum(jnp.sum(g2d, axis=0), data_axes)
            visible = radii > 0.0
            vis = jax.lax.psum(jnp.sum(visible.astype(jnp.float32), axis=0), data_axes)
            maxr = jax.lax.pmax(jnp.max(radii, axis=0), data_axes)

        with jax.named_scope("adam"):
            # ---- sharded Adam update (per-field LRs; Grendel sqrt-batch scaling)
            scale = grendel_lr_scale(cfg.batch_size) if cfg.grendel_sqrt_lr_scaling else 1.0
            lr_means = expon_lr(
                state.step, lr_init=cfg.lr_means_init, lr_final=cfg.lr_means_final,
                max_steps=cfg.max_steps,
            )
            lrs = G.GaussianModel(
                means=lr_means * scale,
                log_scales=cfg.lr_scales * scale,
                quats=cfg.lr_quats * scale,
                opacity_logit=cfg.lr_opacity * scale,
                sh=cfg.lr_sh * scale,
            )
            new_params, new_adam = adam_update(grads, state.adam, state.params, lrs)

        new_state = GSTrainState(
            params=new_params,
            adam=new_adam,
            step=state.step + 1,
            grad2d_accum=state.grad2d_accum + g2d,
            vis_count=state.vis_count + vis,
            max_radii=jnp.maximum(state.max_radii, maxr),
        )
        metrics = {"loss": loss}
        return new_state, metrics

    st_specs = GSTrainState(
        params=G.GaussianModel(*([PS(model_axis)] * 5)),
        adam=AdamState(
            G.GaussianModel(*([PS(model_axis)] * 5)),
            G.GaussianModel(*([PS(model_axis)] * 5)),
            PS(),
        ),
        step=PS(),
        grad2d_accum=PS(model_axis),
        vis_count=PS(model_axis),
        max_radii=PS(model_axis),
    )
    cam_spec = P.Camera(*([PS(data_axes)] * 5))
    gt_spec = PS(data_axes, model_axis) if strip else PS(data_axes)

    stepped = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(st_specs, cam_spec, gt_spec),
        out_specs=(st_specs, {"loss": PS()}),
        check_vma=True,
    )
    # Pin output shardings to the exact NamedShardings of state_shardings():
    # on size-1 mesh axes XLA otherwise normalizes some outputs to PS(), so
    # feeding step t's output state back as step t+1's input would retrace.
    # One trace per Gaussian capacity is what the streaming trainer
    # (repro.insitu) relies on across a whole timestep sequence.
    out_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), (st_specs, {"loss": PS()})
    )
    return jax.jit(stepped, out_shardings=out_shardings)


def make_eval_render(mesh: Mesh, cfg: GSConfig, *, model_axis: str = "model"):
    """Distributed eval render of one view: full image, replicated output."""

    def local(params: G.GaussianModel, cam: P.Camera):
        packed = P.project(params, cam)
        gathered = jax.lax.all_gather(packed, model_axis, axis=0, tiled=True)
        pk_sorted, _ = P.sort_by_depth(gathered)
        img, t = R.render_packed(
            pk_sorted,
            img_h=cfg.img_h,
            img_w=cfg.img_w,
            tile_h=cfg.tile_h,
            tile_w=cfg.tile_w,
            k_per_tile=cfg.k_per_tile,
            bg=jnp.asarray(cfg.bg, jnp.float32),
            backend=cfg.backend,
            binning=cfg.binning,
        )
        return img, t

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(G.GaussianModel(*([PS(model_axis)] * 5)), P.Camera(*([PS()] * 5))),
        out_specs=(PS(), PS()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_tile_row_render(mesh: Mesh, cfg: GSConfig, *, row: int, model_axis: str = "model"):
    """Distributed eval render of ONE horizontal tile row of one view.

    Returned fn: (params sharded over ``model_axis``, a single Camera) ->
    (cfg.tile_h, cfg.img_w, 3) image — the pixel rows
    ``[row*tile_h, (row+1)*tile_h)`` of the full-frame render, **bit-identical**
    to the same rows of :func:`make_batched_eval_render`'s output. The
    project -> all_gather -> depth-sort prefix is the full-frame computation
    verbatim; only the rasterize stage narrows, via the tile binner's
    ``row_offset`` (tile rectangles and per-tile pixel coordinates come out
    as the same integers, so binning and compositing see identical inputs
    per tile). This is the serve-side partial-render primitive: a cache that
    already holds most of a frame's tiles re-renders only the missing rows.

    ``row`` is static (the Pallas raster kernel specializes on the offset),
    so each (level-config, row) pair is its own jit trace — a bounded set,
    levels x tiles_y, paid lazily on first partial hit per row.
    """
    bg = jnp.asarray(cfg.bg, jnp.float32)
    row_offset = int(row) * cfg.tile_h

    def local(params: G.GaussianModel, cam: P.Camera):
        packed = P.project(params, cam)
        gathered = jax.lax.all_gather(packed, model_axis, axis=0, tiled=True)
        pk_sorted, _ = P.sort_by_depth(gathered)
        img, _ = R.render_packed(
            pk_sorted,
            img_h=cfg.tile_h,
            img_w=cfg.img_w,
            tile_h=cfg.tile_h,
            tile_w=cfg.tile_w,
            k_per_tile=cfg.k_per_tile,
            bg=bg,
            backend=cfg.backend,
            # always flat: a strip cannot reproduce the full frame's "hier"
            # superblock geometry, and hier is defined (and tested) to equal
            # flat binning — flat is the deterministic common denominator
            binning="flat",
            row_offset=row_offset,
        )
        return img

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(G.GaussianModel(*([PS(model_axis)] * 5)), P.Camera(*([PS()] * 5))),
        out_specs=PS(),
        check_vma=False,
    )
    return jax.jit(fn)


def make_batched_eval_render(
    mesh: Mesh,
    cfg: GSConfig,
    *,
    data_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
    batch_mode: str = "auto",
):
    """Distributed eval render of a BATCH of views (the serving hot path).

    Returned fn: (params sharded over ``model_axis``, cams: Camera with a
    leading batch dim B sharded over ``data_axes``) -> (B, H, W, 3) images
    sharded over ``data_axes``. B must divide the data-axes device product.

    ``batch_mode`` picks how the local views fuse into one dispatch:
    "vmap" interleaves all views (maximum parallelism — right on TPU/GPU),
    "map" runs them sequentially inside the one jitted call (one view's
    working set at a time — right on cache-bound CPU hosts, where vmap's
    interleaving goes super-linear in B). "auto" selects by backend.

    Each trace is specialized to the local batch shape — callers (the
    ``repro.serve_gs`` micro-batcher) pad request groups to a fixed set of
    bucket sizes so the number of recompiles stays bounded.
    """
    bg = jnp.asarray(cfg.bg, jnp.float32)
    if batch_mode == "auto":
        batch_mode = "map" if jax.default_backend() == "cpu" else "vmap"
    assert batch_mode in ("vmap", "map"), batch_mode

    def local(params: G.GaussianModel, cams: P.Camera):
        def one(cam):
            packed = P.project(params, cam)
            gathered = jax.lax.all_gather(packed, model_axis, axis=0, tiled=True)
            pk_sorted, _ = P.sort_by_depth(gathered)
            img, _ = R.render_packed(
                pk_sorted,
                img_h=cfg.img_h,
                img_w=cfg.img_w,
                tile_h=cfg.tile_h,
                tile_w=cfg.tile_w,
                k_per_tile=cfg.k_per_tile,
                bg=bg,
                backend=cfg.backend,
                binning=cfg.binning,
            )
            return img

        b_local = cams.fx.shape[0]
        if b_local == 1:  # single local view: no batching wrapper at all
            return one(P.Camera(*[x[0] for x in cams]))[None]
        if batch_mode == "map":
            return jax.lax.map(one, cams)
        return jax.vmap(one)(cams)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            G.GaussianModel(*([PS(model_axis)] * 5)),
            P.Camera(*([PS(data_axes)] * 5)),
        ),
        out_specs=PS(data_axes),
        check_vma=False,
    )
    return jax.jit(fn)
