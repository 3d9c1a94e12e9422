"""Open-loop viewer traffic: frames requested on a schedule from a ``RenderServer``.

Parameters of a mix (``bench/traffic/<mix>.json``): ``rate_per_s``, the
offered load; ``azimuth_deg``, ``elevation_deg`` and ``radius``, the ranges
each pose is drawn from uniformly (looking at the origin, +z up, field of
view ``fov_deg``); the server's ``n_levels``, ``max_batch``,
``tile_cache`` and ``pipeline_depth``; ``checked_frames``, how many served
frames are compared with the reference; ``late_s``, how long past the
window a request due in it may take before it counts as failed; and the
seeded jitter of the served Gaussians.

Arrivals: ``round(rate * seconds)`` requests, their gaps drawn once from an
exponential distribution (one fixed realisation of a Poisson process) and
scaled to fill the window. Every seed offers that same schedule; the seed
draws the pose of each request. (Seeds that also reordered the gaps moved
the 95th percentile by a factor of three: the tail follows where the bursts
fall.) One thread submits each request at its due time and drives the
server's pipeline in between.

Each request is timed from its due time until ``FrameFuture.result()``
returns its frame. ``frame_p95_ms`` is the 95th percentile over every
request due in the window; one that never lands counts as failed, and as
late as the run waited for it. (The latencies cluster by batch: the 90th
percentile falls between clusters and swung twice as widely from run to
run as the 95th.) The served model is the configuration's initial
Gaussians, jittered by the seed. Afterwards a seeded sample of the requests
is rendered by the plain reference at the same poses, and the largest
pixel gap (``frame_max_gap``) and the largest mean gap of a frame
(``frame_mean_gap``) are compared (``bench/limits/<cell>.json``).
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

import scene as S
from cameras import camera, spherical, to_program
from harness import Check, Outcome, load_module, memory_analysis
from traffic.train_views import gs_config, make_mesh

GAP_SEED = 0x6A95  # the one arrival schedule every seed shares


def arrivals(rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)`` requests."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(GAP_SEED).exponential(size=n)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def poses(traffic: dict, n: int, seed: int, res: int, stream: int = 0x9053) -> list[dict]:
    """``n`` cameras drawn uniformly from the mix's ranges."""
    rng = np.random.default_rng([seed, stream])
    az = np.deg2rad(rng.uniform(*traffic["azimuth_deg"], n))
    el = np.deg2rad(rng.uniform(*traffic["elevation_deg"], n))
    rad = rng.uniform(*traffic["radius"], n)
    return [camera(spherical(a, e, r), res, traffic["fov_deg"]) for a, e, r in zip(az, el, rad)]


def percentile_ms(latencies_s: np.ndarray, q: float = 95.0) -> float:
    """The ``q``-th percentile in ms over all requests (the higher sample)."""
    return float(np.percentile(latencies_s, q, method="higher")) * 1e3


def drive(server, cams: list, due: np.ndarray, *, late_s: float, keep: set[int], t0: float):
    """Submit request i at ``t0 + due[i]`` and drive the pipeline until every
    request landed or ``late_s`` past the last due time. Returns (completion
    times, frames kept for ``keep``, submit lags)."""
    n = len(cams)
    done = np.full(n, np.nan)
    lag = np.zeros(n)
    frames, pending = {}, {}
    nxt = 0
    deadline = t0 + due[-1] + late_s
    while True:
        now = time.perf_counter()
        while nxt < n and t0 + due[nxt] <= now:
            lag[nxt] = now - (t0 + due[nxt])
            pending[nxt] = server.submit(to_program(cams[nxt]), client_id=nxt, t_submit=t0 + due[nxt])
            nxt += 1
        for i in [i for i, f in pending.items() if f.done()]:
            frame = pending.pop(i).result()
            done[i] = time.perf_counter()
            if i in keep:
                frames[i] = frame
        if (nxt == n and not pending) or now > deadline:
            break
        if server.step() == 0 and not pending and nxt < n:
            time.sleep(max(0.0, min(t0 + due[nxt] - time.perf_counter(), 0.05)))
    return done, frames, lag


def reference(ctx, pts, cols, n_gauss: int, cams: list, dtype=None) -> list:
    """The plain reference's frames of the served model at ``cams``."""
    config = ctx.cell.config
    ref = load_module(ctx.cell.root / "bench" / "configs" / f"{config['reference']}.py")
    params0 = ref.init_params(pts, cols, n_gauss)
    kw = {} if dtype is None else {"dtype": dtype}
    return [ref.render_view(params0, c, config, **kw) for c in cams]


def frame_gaps(got: list, want: list) -> tuple[float, float]:
    """(largest pixel gap, largest mean gap of a frame)."""
    max_gap = mean_gap = 0.0
    for g, w in zip(got, want):
        gap = np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64))
        max_gap, mean_gap = max(max_gap, float(gap.max())), max(mean_gap, float(gap.mean()))
    return max_gap, mean_gap


def checks(ctx, gaps: tuple) -> list:
    limits = ctx.cell.limits
    return [Check("frame_max_gap", gaps[0], float(limits["frame_max_gap"])),
            Check("frame_mean_gap", gaps[1], float(limits["frame_mean_gap"]))]


def sample(ctx, n: int) -> list:
    """The seed's sample of the requests whose frames are compared."""
    k = min(ctx.cell.traffic["checked_frames"], n)
    return sorted(np.random.default_rng([ctx.seed, 0xC4EC]).choice(n, size=k, replace=False).tolist())


def start_server(ctx):
    """The server of the seed's model, every bucket compiled and its host
    path run once. Returns (server, obs, points, colours, Gaussian count)."""
    from repro.launch.train import GSTrainer
    from repro.obs import Obs
    from repro.serve_gs import RenderServer

    config, traffic = ctx.cell.config, ctx.cell.traffic
    sc = S.load_scene(config, cache_root=ctx.cache)
    pts, cols = S.jitter(sc, config, traffic, ctx.seed)
    cfg = gs_config(config, {"batch": traffic["max_batch"]})
    mesh = make_mesh(config, ctx.devices)
    params = GSTrainer(cfg, mesh, pts, cols, verbose=False).state.params
    n_gauss = params.n
    obs = Obs(trace=ctx.trace)
    server = RenderServer(params, cfg, mesh=mesh, n_levels=traffic["n_levels"],
                          max_batch=traffic["max_batch"], tile_cache=traffic["tile_cache"],
                          pipeline_depth=traffic["pipeline_depth"], store_frames=False, obs=obs)
    del params
    server.warmup()
    # one lap of the host path at each bucket size, on poses of their own
    for b in server.batcher.buckets:
        cams = poses(traffic, b, ctx.seed, config["img_res"], 0xAA + b)
        for f in [server.submit(to_program(c)) for c in cams]:
            f.result()
    server.reset_metrics()
    obs.trace.drain()
    return server, obs, pts, cols, n_gauss


def run(ctx) -> Outcome:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    res = config["img_res"]
    server, obs, pts, cols, n_gauss = start_server(ctx)

    due = arrivals(traffic["rate_per_s"], ctx.seconds)
    cams = poses(traffic, len(due), ctx.seed, res)
    keep = set(sample(ctx, len(due)))
    ctx.setup_done()
    with ctx.window():
        done, frames, lag = drive(server, cams, due, late_s=traffic["late_s"], keep=keep,
                                  t0=ctx.window_t0)
    ctx.read_memory()
    if ctx.trace:  # each bucket's compiled footprint, for the record
        import jax

        from repro.serve_gs.batcher import stack_cameras

        lp = server._entry(0).level_params[0]
        for b in server.batcher.buckets:
            batch = stack_cameras([to_program(cams[0])] * b)
            batch = jax.tree_util.tree_map(np.asarray, batch)
            print(f"memory: render bucket {b} {memory_analysis(server._level_render[0], lp, batch)}",
                  file=sys.stderr)
    failed = int(np.sum(np.isnan(done)))
    # a request that never landed waited at least until the run gave up on it
    latency = np.where(np.isnan(done), ctx.window_t1, done) - (ctx.window_t0 + due)
    spans = [(s.name, s.t0, s.t1) for s in obs.trace.drain()]
    report = server.report()
    counters = {
        "batch_size_mean": obs.metrics.histogram("server.batch_size").mean,
        "render_calls": server.obs.metrics.counter("server.render_calls").value,
        "render_rows": server.render_rows,
        "completed": server.completed,
        "tiles_y": server.tiles_y,
    }
    server.close()
    del server, obs
    gc.collect()
    print(f"serve: {len(due)} requests at {traffic['rate_per_s']}/s, completed "
          f"{report['completed']}, failed {failed}, submit lag mean "
          f"{lag.mean() * 1e3:.3f} ms max {lag.max() * 1e3:.3f} ms, latency ms "
          + " ".join(f"p{q} {percentile_ms(latency, q):.1f}" for q in (50, 90, 95, 99)),
          file=sys.stderr)

    t0 = time.perf_counter()
    want = reference(ctx, pts, cols, n_gauss, [cams[i] for i in sorted(frames)])
    gaps = frame_gaps([frames[i] for i in sorted(frames)], want)
    print(f"reference: {len(frames)} frames in {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    if len(frames) < len(keep):  # a sampled frame never came: nothing to compare
        gaps = (float("inf"), float("inf"))
    return Outcome(
        e2e={"frame_p95_ms": percentile_ms(latency), "setup_s": ctx.setup_s},
        attempted=len(due), failed=failed, checks=checks(ctx, gaps),
        layer={"frames": len(due) - failed, "requests": len(due), "counters": counters,
               "latency_s": latency.tolist(), "submit_lag_s": lag.tolist(),
               "host_spans": spans, "n_gaussians": n_gauss},
    )


def control(ctx) -> list:
    """The control: the reference in bfloat16 in the program's place, at the
    seed's sampled poses. Its numbers have to fail the limits."""
    import jax.numpy as jnp

    config, traffic = ctx.cell.config, ctx.cell.traffic
    sc = S.load_scene(config, cache_root=ctx.cache)
    pts, cols = S.jitter(sc, config, traffic, ctx.seed)
    n = len(arrivals(traffic["rate_per_s"], ctx.seconds))
    cams = poses(traffic, n, ctx.seed, config["img_res"])
    picked = [cams[i] for i in sample(ctx, n)]
    quantum = config["pad_quantum"]
    n_gauss = -(-pts.shape[0] // quantum) * quantum
    gaps = frame_gaps(reference(ctx, pts, cols, n_gauss, picked, jnp.bfloat16),
                      reference(ctx, pts, cols, n_gauss, picked))
    print(f"control: frame gaps {gaps}", file=sys.stderr)
    return checks(ctx, gaps)
