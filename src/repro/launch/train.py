"""End-to-end distributed 3D-GS training driver (the paper pipeline).

  volume -> isosurface points -> Gaussian init -> GT orbit renders ->
  distributed Grendel-style optimization (+ densification rounds) ->
  metrics (PSNR / SSIM / LPIPS-proxy) + checkpoints.

Usage (CPU demo scale):
  PYTHONPATH=src python -m repro.launch.train --dataset kingsnake \
      --volume-res 48 --max-points 4000 --res 64 --steps 200 --views 24
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.densify import densify_and_rebalance, reset_opacity
from repro.core.losses import lpips_proxy, psnr, ssim
from repro.core.sharding import make_mesh
from repro.core.train import (
    all_gather_bytes_per_step,
    init_state,
    make_eval_render,
    make_train_step,
    record_shard_balance,
    shard_balance,
    state_shardings,
)
from repro.configs.gs_datasets import DATASETS
from repro.data.views import ViewDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import (
    NO_SPAN,
    Obs,
    devmem,
    new_request_id,
    trace_meta,
    validate_trace_jsonl,
    write_trace,
)
from repro.obs.clock import now, since
from repro.volume import datasets as VD
from repro.volume.isosurface import extract_isosurface_points


class GSTrainer:
    """Owns the (re-jitted-per-densify-round) distributed train step."""

    def __init__(self, cfg: GSConfig, mesh, points, colors, *, verbose: bool = True,
                 obs: Obs | None = None):
        self.cfg = cfg
        self.mesh = mesh
        self.n_shards = mesh.shape["model"]
        self.verbose = verbose
        # training telemetry bundle: share one with a serving stack and
        # train spans/metrics land next to request spans on one clock
        self.obs = obs if obs is not None else Obs()
        n0 = points.shape[0]
        quantum = self.n_shards * cfg.pad_quantum
        pad = (-n0) % quantum
        pts = np.concatenate([np.asarray(points), np.full((pad, 3), 1e6, np.float32)])
        cols = np.concatenate([np.asarray(colors), np.zeros((pad, 3), np.float32)])
        # scale from the real points: the dead padding at 1e6 would stretch
        # the bounding box and make every Gaussian the largest allowed
        g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), sh_degree=cfg.sh_degree,
                               init_scale=G.default_init_scale(points))
        g = g._replace(opacity_logit=g.opacity_logit.at[n0:].set(-20.0))
        self.state = jax.device_put(init_state(g), state_shardings(mesh))
        self._step_fn = None
        self._n_jitted = None

    @property
    def step_fn(self):
        n = self.state.params.n
        if self._step_fn is None or self._n_jitted != n:
            self._step_fn = make_train_step(self.mesh, self.cfg)
            self._n_jitted = n
        return self._step_fn

    def shard_balance(self, *, record: bool = True) -> dict:
        """Per-model-shard load stats (``train.shard_*`` gauges when
        ``record``) — the skew signal densification creates and a dynamic
        rebalancing pass will consume."""
        bal = shard_balance(self.state, opacity_thresh=self.cfg.prune_opacity_thresh)
        if record:
            record_shard_balance(self.obs.metrics, bal)
        return bal

    def fit(self, data: ViewDataset, *, steps: int, densify: bool = True, log_every: int = 50,
            scene_extent: float = 1.0):
        """Per-step telemetry rides the registry (``train.loss`` gauge,
        ``train.step_ms`` histogram, ``train.gather_bytes``); spans cover
        batch assembly -> jitted dispatch -> device compute (bounded by
        block_until_ready, traced runs only) -> densify rounds. The
        ``log_every`` print reads ONE atomic registry snapshot instead of
        loose locals, so what it prints is exactly what ``--metrics-out``
        exports."""
        m = self.obs.metrics
        loss_gauge = m.gauge("train.loss")
        step_ms = m.histogram("train.step_ms")
        device_ms = m.histogram("train.device_ms")
        gather_bytes = m.counter("train.gather_bytes")
        steps_total = m.counter("train.steps")
        rid = new_request_id()  # one span tree per fit call
        gb = all_gather_bytes_per_step(self.cfg, self.mesh, self.state.params.n)
        losses = []
        t0 = now()
        batches = iter(data.batches(self.cfg.batch_size, steps=steps))
        for i in range(steps):
            rec = self.obs.trace
            with rec.span(rid, "batch", step=i) if rec else NO_SPAN as sp:
                got = next(batches, None)
                if got is None:
                    sp.drop()  # the stream ended early: no batch was assembled
            if got is None:
                break
            cams, gt = got
            t_batch = now()
            with rec.span(rid, "dispatch", step=i) if rec else NO_SPAN:
                self.state, metrics = self.step_fn(self.state, cams, gt)
            if rec:
                t_disp = now()
                jax.block_until_ready(self.state)
                t_dev = now()
                rec.record(rid, "device", t_disp, t_dev, step=i)
                device_ms.observe((t_dev - t_disp) * 1e3)
            losses.append(float(metrics["loss"]))  # blocks on the step
            loss_gauge.set(losses[-1])
            steps_total.inc()
            gather_bytes.inc(gb)
            step_ms.observe(since(t_batch) * 1e3)
            step = int(self.state.step)
            if densify and self.cfg.densify_from <= step <= self.cfg.densify_until and step % self.cfg.densify_interval == 0:
                rec = self.obs.trace
                with rec.span(rid, "densify", step=step) if rec else NO_SPAN as sp:
                    self.state, report = densify_and_rebalance(
                        self.state, self.cfg, n_shards=self.n_shards, scene_extent=scene_extent
                    )
                    self.state = jax.device_put(self.state, state_shardings(self.mesh))
                    if rec:
                        sp.meta["n"] = int(self.state.params.n)
                gb = all_gather_bytes_per_step(self.cfg, self.mesh, self.state.params.n)
                self.shard_balance()  # densify is where shards skew
                if self.verbose:
                    print(f"  densify @ {step}: {report}")
            if densify and step % self.cfg.opacity_reset_interval == 0 and step > 0:
                self.state = reset_opacity(self.state)
            if self.verbose and i % log_every == 0:
                snap = m.snapshot()  # ONE atomic read: loss + timing agree
                print(
                    f"step {step:6d} loss {snap['train.loss']:.5f} "
                    f"step_ms p50 {snap['train.step_ms']['p50']:.1f} "
                    f"({since(t0):.1f}s)"
                )
        self.shard_balance()
        devmem.record(m)
        return losses

    def evaluate(self, data: ViewDataset, view_ids) -> dict:
        eval_fn = make_eval_render(self.mesh, self.cfg)
        rec = self.obs.trace
        rid = new_request_id()
        with rec.span(rid, "eval") if rec else NO_SPAN as sp:
            ps, ss, lp = [], [], []
            for i in view_ids:
                cam, gt = data.view(int(i))
                img, _ = eval_fn(self.state.params, cam)
                ps.append(float(psnr(img, gt)))
                ss.append(float(ssim(img, gt)))
                lp.append(float(lpips_proxy(img, gt)))
            out = {"psnr": float(np.mean(ps)), "ssim": float(np.mean(ss)),
                   "lpips_proxy": float(np.mean(lp))}
            self.obs.metrics.gauge("train.psnr").set(round(out["psnr"], 4))
            if rec:
                sp.meta.update(views=len(ps), psnr=round(out["psnr"], 3))
        return out


def build_dataset(name: str, *, volume_res: int, n_views: int, img_h: int, img_w: int,
                  max_points: int | None, cache_dir: str | None = "experiments/gt_cache"):
    ds = DATASETS[name]
    vol = getattr(VD, ds.volume)(res=volume_res)
    pts, nrm, cols = extract_isosurface_points(vol, max_points=max_points)
    data = ViewDataset(vol, n_views=n_views, img_h=img_h, img_w=img_w, radius=ds.radius, cache_dir=cache_dir)
    return vol, pts, cols, data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=list(DATASETS), default="kingsnake")
    ap.add_argument("--res", type=int, default=64)
    ap.add_argument("--volume-res", type=int, default=48)
    ap.add_argument("--views", type=int, default=24)
    ap.add_argument("--max-points", type=int, default=4000)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--backend", choices=["ref", "pallas"], default="ref")
    ap.add_argument("--k-per-tile", type=int, default=256)
    ap.add_argument("--gather-mode", default="auto", choices=["auto", "projected", "params3d"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write per-step span trace (JSONL; .chrome.json sibling for Perfetto)")
    ap.add_argument("--metrics-out", default=None,
                    help="write final train.* registry snapshot as JSON")
    ap.add_argument("--trace-capacity", type=int, default=65536)
    args = ap.parse_args()
    enable_compile_cache()

    obs = Obs(trace=args.trace_out is not None, trace_capacity=args.trace_capacity)

    mesh = make_mesh((args.data_par, args.model_par))
    cfg = GSConfig(
        img_h=args.res, img_w=args.res, batch_size=args.batch, backend=args.backend,
        k_per_tile=args.k_per_tile, max_steps=max(args.steps, 1),
        gather_mode=args.gather_mode,
        densify_from=100, densify_interval=150, densify_until=max(args.steps - 50, 101),
        opacity_reset_interval=10**9,
    )
    vol, pts, cols, data = build_dataset(
        args.dataset, volume_res=args.volume_res, n_views=args.views,
        img_h=args.res, img_w=args.res, max_points=args.max_points,
    )
    print(f"{args.dataset}: {pts.shape[0]} isosurface points, {args.views} views @ {args.res}^2, mesh {dict(mesh.shape)}")
    tr = GSTrainer(cfg, mesh, pts, cols, obs=obs)
    t0 = now()
    losses = tr.fit(data, steps=args.steps)
    train_time = since(t0)
    metrics = tr.evaluate(data, range(0, args.views, max(args.views // 8, 1)))
    print(f"train {train_time:.1f}s  final-loss {losses[-1]:.5f}  {metrics}")
    if args.ckpt:
        rec, rid = obs.trace, new_request_id()
        with rec.span(rid, "ckpt", step=int(tr.state.step)) if rec else NO_SPAN:
            path = save_checkpoint(args.ckpt, int(tr.state.step), tr.state)
        print("checkpoint:", path)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(obs.metrics.snapshot(), f, indent=1, sort_keys=True)
        print("metrics:", args.metrics_out)
    if args.trace_out:
        spans = obs.trace.drain()
        meta = trace_meta(obs.trace, knobs={
            "dataset": args.dataset, "steps": args.steps, "batch": args.batch,
            "data_par": args.data_par, "model_par": args.model_par,
            "backend": args.backend, "gather_mode": cfg.gather_mode,
        })
        jsonl_path, chrome_path = write_trace(args.trace_out, spans, meta=meta)
        with open(jsonl_path) as f:
            n = validate_trace_jsonl(f.read())
        print(f"trace: {n} spans -> {jsonl_path} + {chrome_path}")


if __name__ == "__main__":
    main()
