"""Bring the main path up on a TPU: isosurface -> Gaussian init ->
distributed train steps -> checkpoint -> render serving, at the Miranda
stand-in with volume_res 512 (574,130 isosurface points) and 512x512 px.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded step on a (1, 4) mesh
                                       # against the same step on one chip

Everything runs in this one process, through the entry points a user calls
(``build_dataset``, ``GSTrainer``, ``save_checkpoint``,
``load_params_from_ckpt``, ``RenderServer``). Without a TPU it exits nonzero
before any other work. Every check that fails exits nonzero; on success the
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent

DATASET = "miranda"
VOLUME_RES = 512
RES = 512
N_VIEWS = 16
BATCH = 4
K_PER_TILE = 256
TRAIN_STEPS = 5
N_REQUESTS = 8
N_EVAL_VIEWS = 2

# Pallas kernel against the pure-jnp reference, from one shared state. The
# two compositors round differently (log-space scan vs cumprod, matmul vs
# einsum), so a splat whose alpha sits on the 1/255 cut-off can be kept by
# one and dropped by the other: that moves a pixel by about 4e-3, which the
# max bound allows. A kernel the compiler got wrong moves whole tiles.
LOSS_TOL = 1e-4          # |loss_pallas - loss_ref| after one batch-1 step
IMAGE_MAX_TOL = 2e-2     # max |pixel| difference of an eval render
IMAGE_MEAN_TOL = 1e-4    # mean |pixel| difference of an eval render
MEANS_REL_TOL = 1e-2     # ||d means_pallas - d means_ref|| / ||d means_ref||
# Adam's first steps move each mean by about +-lr whatever the gradient's
# size, so one Gaussian whose tiny gradient changes sign between the two
# backends moves the max difference to ~lr. The max is bounded by twice the
# step's lr; the relative norm above is what sees a wrong backward kernel.
MEANS_MAX_LR_MULT = 2.0
# The (1, 4) step against the (1, 1) step: same kernels and inputs, the
# loss sums only reduce in another order.
MESH_LOSS_TOL = 2e-5
# Each leaf's first-step gradient, (1, 4) against (1, 1), as the norm of the
# difference over the (1, 1) norm: the same float32 terms summed in another
# order. A gradient scaled by the number of shards reads 3. Adam's updates
# hardly see such a scale, which is why the loss and the means cannot.
MESH_GRAD_TOL = 1e-3
STATE_SHARE_TOL = 0.02   # each of 4 devices holds 1/4 +- this of the state
FRAME_SLACK = 1e-6       # served frames lie in [0, 1] up to float rounding


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def device_phase(n_chips: int):
    import jax

    devs = jax.devices()
    require(devs[0].platform == "tpu",
            f"needs a TPU; JAX found {devs[0].platform!r} devices")
    require(len(devs) >= n_chips, f"needs {n_chips} chips; found {len(devs)}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    return devs


def data_phase(*, volume_res: int, res: int, n_views: int):
    """Isosurface points and ground-truth views; no cache from another run."""
    import numpy as np

    from repro.launch.train import build_dataset

    t0 = time.perf_counter()
    _, pts, cols, data = build_dataset(
        DATASET, volume_res=volume_res, n_views=n_views, img_h=res, img_w=res,
        max_points=None, cache_dir=None,
    )
    dt = time.perf_counter() - t0
    print(f"data: {DATASET} volume_res {volume_res}: {pts.shape[0]} isosurface points, "
          f"{n_views} views at {res}x{res} px, {dt:.1f} s")
    require(pts.shape[0] > 0, "no isosurface points")
    require(bool(np.all(np.isfinite(data.gt))), "ground truth has non-finite pixels")
    return pts, cols, data


def train_config(res: int, *, n_model: int = 1):
    from repro.core.config import GSConfig

    # GSTrainer pads the model to n_model x pad_quantum Gaussians: 1024 on
    # any mesh here, so one model (and one checkpoint) fits one chip or four
    return GSConfig(img_h=res, img_w=res, batch_size=BATCH, backend="pallas",
                    k_per_tile=K_PER_TILE, pad_quantum=4 * 256 // n_model)


def first_views(data, n: int):
    import jax.numpy as jnp

    from repro.volume.cameras import camera_slice

    return camera_slice(data.cams, jnp.arange(n)), jnp.asarray(data.gt[:n])


def compile_phase(jobs: dict, warm=None) -> dict:
    """Compile every program of the run at once, one thread each.

    ``jobs`` maps a name to ``(jitted fn, example args)``; later calls with
    such args reuse the executable. ``warm`` (optional) compiles something
    that has no handle of its own, on this thread. Separate programs compile
    side by side on the host's cores; one after another they would take most
    of the run's time limit.
    """
    t0 = time.perf_counter()
    lowered = {name: fn.lower(*args) for name, (fn, args) in jobs.items()}

    def compile_one(low):
        t = time.perf_counter()
        return low.compile(), time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=len(lowered)) as pool:
        futures = {name: pool.submit(compile_one, low) for name, low in lowered.items()}
        if warm is not None:
            t = time.perf_counter()
            warm()
            print(f"compile: {warm.__name__} {time.perf_counter() - t:.1f} s")
        compiled = {}
        for name, fut in futures.items():
            compiled[name], dt = fut.result()
            print(f"compile: {name} {dt:.1f} s")
    print(f"compile: {len(jobs) + (warm is not None)} programs in "
          f"{time.perf_counter() - t0:.1f} s wall")
    return compiled


def train_phase(tr, data, *, steps: int, device):
    """GSTrainer.fit for ``steps`` steps, densify off."""
    import numpy as np

    losses = tr.fit(data, steps=steps, densify=False)
    spans = tr.obs.trace.drain()
    start = {s.meta["step"]: s.t0 for s in spans if s.name == "dispatch"}
    end = {s.meta["step"]: s.t1 for s in spans if s.name == "device"}
    for i, loss in enumerate(losses):
        print(f"train: step {i + 1} loss {loss:.6f} time {end[i] - start[i]:.4f} s")
    require(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    require(bool(np.all(np.isfinite(losses))), f"non-finite loss in {losses}")
    for name, leaf in zip(tr.state.params._fields, tr.state.params):
        bad = int(np.sum(~np.isfinite(np.asarray(leaf))))
        require(bad == 0, f"training left {bad} non-finite values in {name}")
    stats = device.memory_stats() or {}
    require("peak_bytes_in_use" in stats, "the device reports no peak_bytes_in_use")
    print(f"train: peak_bytes_in_use {stats['peak_bytes_in_use']}")


def reference_phase(state, data, steps_b1: dict, evals: dict, *, lr: float, n_eval: int):
    """One batch-1 train step and ``n_eval`` eval renders, Pallas against
    the reference, from the same state. Returns the Pallas eval images."""
    import numpy as np

    from repro.volume.cameras import camera_slice

    cam, gt = first_views(data, 1)
    loss, means = {}, {}
    for backend, step in steps_b1.items():
        new, metrics = step(state, cam, gt)
        loss[backend] = float(metrics["loss"])
        means[backend] = np.asarray(new.params.means)
        bad = int(np.sum(~np.isfinite(means[backend])))
        require(bad == 0, f"the {backend} step left {bad} non-finite values in means")
        del new
    m0 = np.asarray(state.params.means)
    d_pal, d_ref = means["pallas"] - m0, means["ref"] - m0
    loss_diff = abs(loss["pallas"] - loss["ref"])
    means_max = float(np.max(np.abs(d_pal - d_ref)))
    means_rel = float(np.linalg.norm(d_pal - d_ref) / max(np.linalg.norm(d_ref), 1e-30))
    print(f"reference: batch-1 step loss pallas {loss['pallas']:.7f} ref {loss['ref']:.7f} "
          f"diff {loss_diff:.3e} (tol {LOSS_TOL:g})")
    print(f"reference: updated means max diff {means_max:.3e} (tol {MEANS_MAX_LR_MULT:g} x lr "
          f"= {MEANS_MAX_LR_MULT * lr:.3e}), relative norm of update diff {means_rel:.3e} "
          f"(tol {MEANS_REL_TOL:g})")
    require(np.isfinite(loss_diff) and loss_diff <= LOSS_TOL, "loss: Pallas differs from reference")
    require(means_max <= MEANS_MAX_LR_MULT * lr, "means: Pallas update differs from reference")
    require(means_rel <= MEANS_REL_TOL, "means: Pallas update differs from reference")

    images = {}
    for view in eval_views(data, n_eval):
        cam = camera_slice(data.cams, view)
        img = {b: np.asarray(fn(state.params, cam)[0]) for b, fn in evals.items()}
        check_image(f"reference: eval view {view} pallas vs ref", img["pallas"], img["ref"])
        images[view] = img["pallas"]
    return images


def eval_views(data, n: int) -> list[int]:
    return [i * (data.n_views // n) for i in range(n)]


def check_image(label: str, got, want) -> None:
    import numpy as np

    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    mx, mean = float(diff.max()), float(diff.mean())
    print(f"{label}: max diff {mx:.3e} (tol {IMAGE_MAX_TOL:g}), mean diff {mean:.3e} "
          f"(tol {IMAGE_MEAN_TOL:g})")
    require(bool(np.all(np.isfinite(got))), f"{label}: non-finite pixels")
    require(mx <= IMAGE_MAX_TOL and mean <= IMAGE_MEAN_TOL, f"{label}: images differ")


def serve_phase(server, state, data, eval_image, *, n_requests: int):
    """Checkpoint round trip, then ``n_requests`` frames of the loaded model
    from ``server``: half at training poses, half at novel orbit poses.
    ``eval_image`` is ``make_eval_render``'s image of training view 0."""
    import numpy as np

    from repro.checkpoint import save_checkpoint
    from repro.launch.serve_gs import load_params_from_ckpt
    from repro.volume.cameras import camera_slice, orbit_cameras

    ckpt_root = ROOT / "experiments" / "ckpts"
    ckpt_root.mkdir(parents=True, exist_ok=True)
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_", dir=ckpt_root)
    try:
        path = save_checkpoint(ckpt, int(state.step), state)
        params = load_params_from_ckpt(ckpt)
    finally:
        shutil.rmtree(ckpt)
    print(f"serve: checkpoint {Path(path).name} round trip, {params.n} Gaussians")
    for name, a, b in zip(params._fields, params, state.params):
        require(np.array_equal(np.asarray(a), np.asarray(b)), f"checkpoint changed {name}")

    cfg = server.cfg
    n_train = n_requests // 2
    novel = orbit_cameras(n_requests - n_train, img_h=cfg.img_h, img_w=cfg.img_w,
                          radius=3.4, elev_cycles=1.0)
    cams = [camera_slice(data.cams, i) for i in range(n_train)]
    cams += [camera_slice(novel, i) for i in range(n_requests - n_train)]
    with server:
        server.add_timestep(0, params)  # the loaded model replaces the initial one
        t0 = time.perf_counter()
        futs = [server.submit(c, client_id=i) for i, c in enumerate(cams)]
        frames = [f.result() for f in futs]
        dt = time.perf_counter() - t0
        report = server.report()
    print(f"serve: {len(frames)} frames of {frames[0].shape} in {dt:.4f} s, "
          f"completed {report['completed']}")
    require(report["completed"] == n_requests, f"served {report['completed']} of {n_requests}")
    for i, fr in enumerate(frames):
        require(fr.shape == (cfg.img_h, cfg.img_w, 3), f"frame {i} has shape {fr.shape}")
        require(bool(np.all(np.isfinite(fr))), f"frame {i} has non-finite pixels")
        require(fr.min() >= -FRAME_SLACK and fr.max() <= 1 + FRAME_SLACK,
                f"frame {i} leaves [0, 1]: [{fr.min()}, {fr.max()}]")
    check_image("serve: frame at training view 0 vs make_eval_render", frames[0], eval_image)


def one_chip(devs, pts, cols, data) -> None:
    from repro.core.sharding import make_mesh
    from repro.core.train import make_eval_render, make_train_step
    from repro.launch.train import GSTrainer
    from repro.obs import Obs
    from repro.optim.schedules import expon_lr
    from repro.serve_gs import RenderServer
    from repro.volume.cameras import camera_slice

    mesh = make_mesh((1, 1), devices=devs[:1])
    cfg = train_config(RES)
    obs = Obs(trace=True)  # tracing fences every step with block_until_ready
    tr = GSTrainer(cfg, mesh, pts, cols, verbose=False, obs=obs)
    print(f"train: {tr.state.params.n} Gaussians, batch {cfg.batch_size}, "
          f"k_per_tile {cfg.k_per_tile}, backend {cfg.backend}, mesh {dict(mesh.shape)}")
    backends = ("pallas", "ref")
    steps_b1 = {b: make_train_step(mesh, dataclasses.replace(cfg, batch_size=1, backend=b))
                for b in backends}
    evals = {b: make_eval_render(mesh, dataclasses.replace(cfg, backend=b)) for b in backends}
    # built now so its render compiles with the rest; serve_phase swaps in
    # the checkpointed model through add_timestep
    server = RenderServer(tr.state.params, cfg, mesh=mesh, n_levels=1, max_batch=N_REQUESTS,
                          store_frames=False)

    def serving_render():
        server.warmup(buckets=(N_REQUESTS,))

    state, view = tr.state, camera_slice(data.cams, 0)
    jobs = {"train step": (tr.step_fn, (state,) + first_views(data, cfg.batch_size))}
    jobs.update({f"batch-1 step {b}": (fn, (state,) + first_views(data, 1))
                 for b, fn in steps_b1.items()})
    jobs.update({f"eval render {b}": (fn, (state.params, view)) for b, fn in evals.items()})
    compiled = compile_phase(jobs, warm=serving_render)
    n_kernels = compiled["train step"].as_text().count("tpu_custom_call")
    print(f"train: compiled step holds {n_kernels} tpu_custom_call ops")
    require(n_kernels > 0, "the Pallas train step holds no tpu_custom_call")
    del compiled

    train_phase(tr, data, steps=TRAIN_STEPS, device=devs[0])
    lr = float(expon_lr(tr.state.step, lr_init=cfg.lr_means_init,
                        lr_final=cfg.lr_means_final, max_steps=cfg.max_steps))
    images = reference_phase(tr.state, data, steps_b1, evals, lr=lr, n_eval=N_EVAL_VIEWS)
    serve_phase(server, tr.state, data, images[0], n_requests=N_REQUESTS)


def state_bytes_per_device(state) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def four_chips(devs, pts, cols, data) -> None:
    """The sharded step on a (1, 4) mesh against the same step on device 0:
    the loss and each leaf's first gradient, read from Adam's first moment."""
    import jax
    import numpy as np

    from repro.core.sharding import make_mesh
    from repro.launch.train import GSTrainer

    cams, gt = next(data.batches(BATCH, steps=1))
    meshes = {(1, 4): devs[:4], (1, 1): devs[:1]}
    trainers = {shape: GSTrainer(train_config(RES, n_model=shape[1]),
                                 make_mesh(shape, devices=d), pts, cols, verbose=False)
                for shape, d in meshes.items()}
    compile_phase({f"train step {shape}": (tr.step_fn, (tr.state, cams, gt))
                   for shape, tr in trainers.items()})
    losses, grads = {}, {}
    b1 = 0.9  # repro.optim.adam's first-moment decay: m = (1 - b1) g after one step
    for shape, tr in trainers.items():
        t0 = time.perf_counter()
        state, metrics = tr.step_fn(tr.state, cams, gt)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        losses[shape] = float(metrics["loss"])
        grads[shape] = {k: np.asarray(v) / (1 - b1) for k, v in state.adam.m._asdict().items()}
        print(f"mesh {shape}: {state.params.n} Gaussians, loss {losses[shape]:.7f}, "
              f"step {dt:.4f} s")
        if shape == (1, 4):
            held = state_bytes_per_device(state)
            total = sum(held.values())
            for d in meshes[shape]:
                stats = d.memory_stats() or {}
                print(f"mesh {shape}: device {d.id} holds {held.get(d, 0)} state bytes "
                      f"({held.get(d, 0) / total:.4f} of the state), "
                      f"bytes_in_use {stats.get('bytes_in_use')}")
                require("bytes_in_use" in stats, f"device {d.id} reports no bytes_in_use")
                require(abs(held.get(d, 0) / total - 0.25) <= STATE_SHARE_TOL,
                        f"device {d.id} holds {held.get(d, 0) / total:.4f} of the state, not 1/4")
        del state, metrics
    diff = abs(losses[(1, 4)] - losses[(1, 1)])
    print(f"mesh: loss diff (1, 4) vs (1, 1) {diff:.3e} (tol {MESH_LOSS_TOL:g})")
    require(np.isfinite(diff) and diff <= MESH_LOSS_TOL, "the (1, 4) step differs from one chip")
    for leaf, want in grads[(1, 1)].items():
        gap = float(np.linalg.norm(grads[(1, 4)][leaf] - want) / max(np.linalg.norm(want), 1e-30))
        print(f"mesh: first-step gradient of {leaf}, (1, 4) vs (1, 1): relative gap {gap:.3e} "
              f"(tol {MESH_GRAD_TOL:g})")
        require(np.isfinite(gap) and gap <= MESH_GRAD_TOL,
                f"the (1, 4) step's gradient of {leaf} differs from one chip's")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4)-mesh step and its one-chip comparison")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devs = device_phase(n_chips)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    pts, cols, data = data_phase(volume_res=VOLUME_RES, res=RES, n_views=N_VIEWS)
    (four_chips if args.four_chips else one_chip)(devs, pts, cols, data)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
