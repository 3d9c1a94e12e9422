"""Training traffic: ``GSTrainer.fit`` on the configuration's orbit views.

Parameters of a mix (``bench/traffic/<mix>.json``): ``batch`` views per
step, drawn from the orbit views by the program's 3D-GS epoch rule in an
order seeded by ``--seed``; ``checked_steps`` warm-up steps that the plain
reference follows; ``jitter_voxels`` and ``jitter_color``, the seeded jitter
of the initial Gaussians. Densification is off.

Set-up builds one trainer and drives it through the checked steps with the
window's own call (``fit``) and feed; the first compiles. The window then
hands the same trainer to ``fit`` again and stops feeding it once
``--seconds`` have passed; it ends in ``block_until_ready`` on the state.
``train_views_per_s`` is every view of the window over its wall time.

Afterwards the reference trains from its own initial Gaussians on the same
views, and three numbers are compared (``bench/limits/<cell>.json``):
``loss_gap``, the largest relative gap of a checked step's loss;
``grad_gap``, the largest gap between the norms of a leaf's first gradient
(the program's from Adam's first moment after one step); ``change_gap``,
the same for each leaf's change over the checked steps, leaving out leaves
whose reference gradient is under a thousandth of the median leaf's. A
norm gap is taken against the larger of the leaf's reference norm and the
median leaf's.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import scene as S
from cameras import orbit, stack_to_program
from harness import Check, Outcome, load_module, memory_analysis

LEAVES = ("means", "log_scales", "quats", "opacity_logit", "sh")
QUIET_GRAD = 1e-3  # leaves whose reference gradient is under this x median move by round-off


def gs_config(config: dict, traffic: dict):
    """The program's GSConfig, every number from the configuration file."""
    from repro.core.config import GSConfig

    lr = config["lr"]
    return GSConfig(
        img_h=config["img_res"], img_w=config["img_res"], tile_h=config["tile"],
        tile_w=config["tile"], k_per_tile=config["k_per_tile"], backend=config["backend"],
        binning=config["binning"], bg=tuple(config["bg"]), sh_degree=config["sh_degree"],
        batch_size=traffic["batch"], max_steps=config["max_steps"],
        lambda_dssim=config["lambda_dssim"], lr_means_init=lr["means_init"],
        lr_means_final=lr["means_final"], lr_scales=lr["scales"], lr_quats=lr["quats"],
        lr_opacity=lr["opacity"], lr_sh=lr["sh"],
        grendel_sqrt_lr_scaling=config["sqrt_batch_lr_scaling"],
        pixel_parallel=config["pixel_parallel"], gather_mode=config["gather_mode"],
        pad_quantum=config["pad_quantum"] // config["mesh"][1],
    )


def make_mesh(config: dict, devices):
    from repro.core.sharding import make_mesh as program_mesh

    shape = tuple(config["mesh"])
    return program_mesh(shape, devices=devices[: int(np.prod(shape))])


def make_feed(cams: list, gt: np.ndarray, seed: int):
    """A ``ViewDataset`` over given views: the program's epoch rule draws
    from one stream across ``fit`` calls, and stops at ``deadline``."""
    import jax.numpy as jnp

    from repro.data.views import ViewDataset

    class Feed(ViewDataset):
        def __init__(self):  # the views are given: nothing to ray-march
            self.img_h, self.img_w = gt.shape[1], gt.shape[2]
            self.n_views = gt.shape[0]
            batched = stack_to_program(cams)
            self.cams = type(batched)(*[jnp.asarray(x) for x in batched])
            self.gt = gt
            self.rng = np.random.default_rng([seed, 0x7EED])
            self.deadline = None
            self._stream = None

        def batches(self, batch_size: int, *, steps: int):
            if self._stream is None:
                self._stream = ViewDataset.batches(self, batch_size, steps=1 << 62)
            for _ in range(steps):
                if self.deadline is not None and time.perf_counter() >= self.deadline:
                    return
                yield next(self._stream)

    return Feed()


def view_ids(cams_batch, viewmats: np.ndarray) -> list[int]:
    """Which orbit views a batch holds, by its view matrices."""
    got = np.asarray(cams_batch.viewmat)
    return [int(np.argmin(np.abs(viewmats - v).reshape(len(viewmats), -1).sum(1))) for v in got]


def host(tree) -> dict:
    return {k: np.asarray(getattr(tree, k), np.float64) for k in LEAVES}


def norm_gaps(prog: dict, ref: dict, leaves) -> dict:
    """Per leaf |prog norm - ref norm| / max(ref norm, median ref norm)."""
    rn = {k: float(np.linalg.norm(ref[k])) for k in LEAVES}
    med = float(np.median(list(rn.values())))
    return {k: abs(float(np.linalg.norm(prog[k])) - rn[k]) / max(rn[k], med, 1e-30) for k in leaves}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers compared: see the module docstring."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    gref = {k: np.asarray(ref["first_grad"][k], np.float64).reshape(prog["first_grad"][k].shape)
            for k in LEAVES}
    gnorm = {k: float(np.linalg.norm(gref[k])) for k in LEAVES}
    med = float(np.median(list(gnorm.values())))
    moving = [k for k in LEAVES if gnorm[k] >= QUIET_GRAD * med]
    dprog = {k: prog["params"][k] - prog["params0"][k] for k in LEAVES}
    dref = {k: np.asarray(ref["params"][k], np.float64).reshape(dprog[k].shape)
            - np.asarray(ref["params0"][k], np.float64).reshape(dprog[k].shape) for k in LEAVES}
    grad = norm_gaps(prog["first_grad"], gref, LEAVES)
    change = norm_gaps(dprog, dref, moving)
    return {"loss_gap": loss, "grad_gap": max(grad.values()), "change_gap": max(change.values()),
            "detail": {"grad": grad, "change": change, "moving": moving,
                       "prog_losses": prog["losses"], "ref_losses": ref["losses"]}}


@dataclasses.dataclass
class Trained:
    """The program's side of the comparison, and the inputs it trained on."""

    losses: list
    first_grad: dict
    params0: dict
    params: dict
    batches: list  # orbit view ids of each checked step


def warm_up(tr, feed, steps: int, viewmats: np.ndarray, b1: float) -> Trained:
    """The checked steps, one ``fit`` call each; the first compiles."""
    params0 = host(tr.state.params)
    losses, batches, first_grad = [], [], None
    stream_batches = feed.batches

    def recording(batch_size, *, steps):
        for cams, gt in stream_batches(batch_size, steps=steps):
            batches.append(view_ids(cams, viewmats))
            yield cams, gt

    feed.batches = recording
    for _ in range(steps):
        losses += tr.fit(feed, steps=1, densify=False)
        if first_grad is None:  # m = (1 - b1) g after one step from m = 0
            first_grad = {k: v / (1 - b1) for k, v in host(tr.state.adam.m).items()}
    feed.batches = stream_batches
    return Trained(losses, first_grad, params0, host(tr.state.params), batches)


def inputs(ctx):
    """The seed's scene: jittered points and colours, views, GT and feed."""
    config, traffic = ctx.cell.config, ctx.cell.traffic
    sc = S.load_scene(config, cache_root=ctx.cache)
    pts, cols = S.jitter(sc, config, traffic, ctx.seed)
    res = config["img_res"]
    cams = orbit(config, res)
    gt = S.upsample(sc.gt, res)
    return sc, pts, cols, cams, gt, make_feed(cams, gt, ctx.seed)


def reference(ctx, pts, cols, cams, gt, batches: list, n_gauss: int, dtype=None) -> dict:
    """The plain reference trained on ``batches`` (orbit view ids per step)."""
    config = ctx.cell.config
    ref = load_module(ctx.cell.root / "bench" / "configs" / f"{config['reference']}.py")
    params0 = ref.init_params(pts, cols, n_gauss)
    out = ref.train(params0, [[cams[i] for i in b] for b in batches],
                    [[gt[i] for i in b] for b in batches], config,
                    **({} if dtype is None else {"dtype": dtype}))
    out["params0"] = params0
    return out


def checks(ctx, gaps: dict) -> list:
    limits = ctx.cell.limits
    return [Check(k, float(gaps[k]), float(limits[k])) for k in ("loss_gap", "grad_gap", "change_gap")]


def run(ctx) -> Outcome:
    import jax

    from repro.launch.train import GSTrainer
    from repro.obs import Obs

    config, traffic = ctx.cell.config, ctx.cell.traffic
    sc, pts, cols, cams, gt, feed = inputs(ctx)
    cfg = gs_config(config, traffic)
    obs = Obs(trace=ctx.trace)
    tr = GSTrainer(cfg, make_mesh(config, ctx.devices), pts, cols, verbose=False, obs=obs)
    viewmats = np.stack([c["viewmat"] for c in cams])
    done = warm_up(tr, feed, traffic["checked_steps"], viewmats, config["adam"]["b1"])
    obs.trace.drain()  # the window's spans only
    ctx.setup_done()

    with ctx.window():
        feed.deadline = ctx.window_t0 + ctx.seconds
        losses = tr.fit(feed, steps=1 << 62, densify=False)
        jax.block_until_ready(tr.state)
    ctx.read_memory()
    spans = [s for s in obs.trace.drain() if ctx.window_t0 <= s.t0 <= ctx.window_t1]
    if ctx.trace:  # the step's compiled footprint, for the record
        feed.deadline = None
        cams_b, gt_b = next(feed.batches(traffic["batch"], steps=1))
        print(f"memory: train step {memory_analysis(tr.step_fn, tr.state, cams_b, gt_b)}",
              file=sys.stderr)
    steps = len(losses)
    bad = int(np.sum(~np.isfinite(np.asarray(losses, np.float64))))
    n_gauss = tr.state.params.n
    del tr, feed, obs
    gc.collect()

    t0 = time.perf_counter()
    ref_out = reference(ctx, pts, cols, cams, gt, done.batches, n_gauss)
    gaps = compare(dataclasses.asdict(done), ref_out)
    print(f"reference: {time.perf_counter() - t0:.1f} s; {gaps['detail']}", file=sys.stderr)
    batch = traffic["batch"]
    return Outcome(
        e2e={"train_views_per_s": steps * batch / ctx.window_s, "setup_s": ctx.setup_s},
        attempted=steps * batch, failed=bad * batch, checks=checks(ctx, gaps),
        layer={"steps": steps, "views": steps * batch, "batch": batch, "n_gaussians": n_gauss,
               "host_spans": [(s.name, s.t0, s.t1) for s in spans], "scene_build_s": sc.build_s},
    )


def control(ctx) -> list:
    """The control: the reference in bfloat16 in the program's place, on the
    views the seed's first steps draw. Its numbers have to fail the limits."""
    import jax.numpy as jnp

    config, traffic = ctx.cell.config, ctx.cell.traffic
    _, pts, cols, cams, gt, feed = inputs(ctx)
    viewmats = np.stack([c["viewmat"] for c in cams])
    batches = [view_ids(c, viewmats)
               for c, _ in feed.batches(traffic["batch"], steps=traffic["checked_steps"])]
    quantum = config["pad_quantum"]
    n_gauss = -(-pts.shape[0] // quantum) * quantum
    ref = reference(ctx, pts, cols, cams, gt, batches, n_gauss)
    low = reference(ctx, pts, cols, cams, gt, batches, n_gauss, dtype=jnp.bfloat16)
    gaps = compare(low, ref)
    print(f"control: {gaps['detail']}", file=sys.stderr)
    return checks(ctx, gaps)
