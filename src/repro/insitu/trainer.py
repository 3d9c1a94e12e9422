"""Warm-start incremental trainer for streaming time-varying volumes.

The static pipeline (``repro.launch.train``) pays two costs per volume that a
stream cannot afford: a from-scratch optimization and — via densification's
shape changes — repeated jit traces. This trainer fixes both:

  * **Fixed padded capacity.** The Gaussian count is padded once, at the
    first timestep, to ``capacity`` (a shard-aligned multiple of
    ``n_shards * cfg.pad_quantum``). Every subsequent timestep reuses the
    same shapes, so the jitted train step is traced exactly once for the
    whole sequence (``n_traces`` tracks this via the jit cache size).

  * **Warm start + dead-slot reseeding.** Params *and* Adam moments carry
    over from timestep t to t+1; only ``warm_steps`` delta-optimization
    steps run (vs ``cold_steps`` at t=0). Instead of densification, dead
    slots (padding + pruned-to-transparent Gaussians) are re-seeded from the
    new timestep's isosurface extraction — a shape-preserving stand-in for
    adaptive density control that lets the model follow surface regions that
    appear over time.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core.config import GSConfig
from repro.core.densify import DEAD_LOGIT
from repro.core.losses import psnr
from repro.core.train import (
    GSTrainState,
    all_gather_bytes_per_step,
    init_state,
    make_eval_render,
    make_train_step,
    record_shard_balance,
    shard_balance,
    state_shardings,
)
from repro.obs import NO_SPAN, Obs, devmem, new_request_id
from repro.obs.clock import now, since
from repro.data.views import ViewDataset
from repro.volume.datasets import VolumeSpec
from repro.volume.isosurface import extract_isosurface_points


@dataclasses.dataclass
class TimestepReport:
    """What happened while absorbing one stream timestep."""

    t_index: int
    name: str
    mode: str                 # "cold" | "warm"
    steps: int
    n_extracted: int          # isosurface points pulled from this timestep
    n_reseeded: int           # dead slots re-seeded from them
    psnr_before: float        # eval view, before this timestep's training
    psnr_after: float
    loss_final: float
    wall_s: float             # extraction + GT render + train + eval
    train_s: float            # optimization only
    n_traces: int             # cumulative train-step jit traces (must stay 1)
    psnr_curve: list = dataclasses.field(default_factory=list)  # [(step, psnr)]
    # Gaussian slots this timestep rewrote (reseeded + optimizer-moved rows),
    # diffed host-side against the previous timestep's params. None means
    # unknown/everything (cold start) — exactly what a serving tier should
    # assume. Feeds RenderServer.add_timestep(..., changed=...) so the
    # trainer->server handoff needs no caller-side row math.
    changed_slots: list | None = None


def fixed_capacity_init(
    points: np.ndarray,
    colors: np.ndarray,
    capacity: int,
    *,
    sh_degree: int = 0,
    init_scale: float = 0.05,
) -> G.GaussianModel:
    """Init a model at exactly ``capacity`` slots; extra slots are dead."""
    n0 = points.shape[0]
    assert n0 <= capacity, (n0, capacity)
    pad = capacity - n0
    pts = np.concatenate([np.asarray(points, np.float32), np.full((pad, 3), 1e6, np.float32)])
    cols = np.concatenate([np.asarray(colors, np.float32), np.zeros((pad, 3), np.float32)])
    g = G.init_from_points(jnp.asarray(pts), jnp.asarray(cols), sh_degree=sh_degree, init_scale=init_scale)
    return g._replace(opacity_logit=g.opacity_logit.at[n0:].set(DEAD_LOGIT))


def reseed_dead_slots(
    state: GSTrainState,
    points: np.ndarray,
    colors: np.ndarray,
    *,
    init_scale: float = 0.05,
    init_opacity: float = 0.1,
    opacity_thresh: float = 0.005,
    max_fraction: float = 1.0,
    rng: np.random.Generator | None = None,
) -> tuple[GSTrainState, int, np.ndarray]:
    """Re-seed dead capacity from a fresh isosurface extraction (host-side).

    Dead = opacity below ``opacity_thresh`` (covers both padding at
    ``DEAD_LOGIT`` and Gaussians the optimizer pruned to transparency). Up to
    ``max_fraction`` of the dead slots are refilled with randomly sampled new
    surface points; their Adam moments and densify stats are zeroed so the
    optimizer treats them as newborn. Shapes are untouched — the caller's
    jitted train step keeps its trace. Returns ``(state, n_fill, slots)``
    where ``slots`` are the refilled row indices (empty when nothing was
    reseeded) — the world-space invalidation path wants them without
    re-diffing the params.
    """
    rng = rng or np.random.default_rng(0)
    p = jax.tree_util.tree_map(np.asarray, state.params)
    opac = 1.0 / (1.0 + np.exp(-np.clip(p.opacity_logit, -60, 60)))
    dead = np.nonzero(opac < opacity_thresh)[0]
    points = np.asarray(points, np.float32)
    colors = np.asarray(colors, np.float32)
    n_fill = min(int(len(dead) * max_fraction), points.shape[0])
    if n_fill == 0:
        return state, 0, np.zeros(0, np.int64)
    slots = dead[rng.choice(len(dead), n_fill, replace=False)] if n_fill < len(dead) else dead
    pick = rng.choice(points.shape[0], n_fill, replace=False)

    seed = fixed_capacity_init(points[pick], colors[pick], n_fill, sh_degree=p.sh_degree, init_scale=init_scale)
    seed = seed._replace(
        opacity_logit=jnp.full((n_fill,), float(np.log(init_opacity / (1 - init_opacity))), jnp.float32)
    )
    seed = jax.tree_util.tree_map(np.asarray, seed)

    new_params = G.GaussianModel(*[a.copy() for a in p])
    for field in G.GaussianModel._fields:
        getattr(new_params, field)[slots] = getattr(seed, field)

    def zero_rows(tree):
        out = jax.tree_util.tree_map(lambda a: np.asarray(a).copy(), tree)
        for leaf in out:
            leaf[slots] = 0.0
        return out

    m = zero_rows(state.adam.m)
    v = zero_rows(state.adam.v)
    stats = []
    for s in (state.grad2d_accum, state.vis_count, state.max_radii):
        a = np.asarray(s).copy()
        a[slots] = 0.0
        stats.append(a)

    new_state = GSTrainState(
        params=G.GaussianModel(*[jnp.asarray(a) for a in new_params]),
        adam=state.adam._replace(
            m=G.GaussianModel(*[jnp.asarray(a) for a in m]),
            v=G.GaussianModel(*[jnp.asarray(a) for a in v]),
        ),
        step=state.step,
        grad2d_accum=jnp.asarray(stats[0]),
        vis_count=jnp.asarray(stats[1]),
        max_radii=jnp.asarray(stats[2]),
    )
    return new_state, n_fill, np.sort(np.asarray(slots, np.int64))


class InsituTrainer:
    """Tracks an evolving isosurface with one fixed-shape Gaussian model.

    ``start(vol)`` cold-starts on the first timestep; ``advance(vol)``
    warm-starts every following one; ``run(stream)`` drives a whole
    ``VolumeStream`` (optionally appending params to a
    ``TemporalCheckpointStore`` after each timestep).
    """

    def __init__(
        self,
        cfg: GSConfig,
        mesh,
        *,
        capacity: int | None = None,
        capacity_factor: float = 1.5,
        cold_steps: int = 200,
        warm_steps: int = 40,
        n_views: int = 8,
        radius: float = 3.0,
        max_points: int | None = 4000,
        n_steps_raymarch: int = 64,
        init_scale: float = 0.05,
        eval_view: int = 0,
        eval_every: int = 0,
        seed: int = 0,
        verbose: bool = False,
        obs: Obs | None = None,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.n_shards = mesh.shape["model"]
        self.capacity = capacity
        self.capacity_factor = capacity_factor
        self.cold_steps = cold_steps
        self.warm_steps = warm_steps
        self.n_views = n_views
        self.radius = radius
        self.max_points = max_points
        self.n_steps_raymarch = n_steps_raymarch
        self.init_scale = init_scale
        self.eval_view = eval_view
        self.eval_every = eval_every
        self.rng = np.random.default_rng(seed)
        self.verbose = verbose
        # the observability bundle this trainer reports through: share one
        # with a serving stack (run(server=...)) and training spans land on
        # the same clock/ring as the request spans; standalone trainers get
        # a private bundle so instrumentation never needs a None check
        self.obs = obs if obs is not None else Obs()

        self.state: GSTrainState | None = None
        self.t_index = 0
        self.reports: list[TimestepReport] = []
        self._step_fn = None
        self._eval_fn = None
        self._rid = 0  # request id of the timestep currently being absorbed

    # ------------------------------------------------------------- plumbing
    @property
    def n_traces(self) -> int:
        """Jit-trace count of the train step (the recompile counter)."""
        if self._step_fn is None:
            return 0
        try:
            return int(self._step_fn._cache_size())
        except (AttributeError, TypeError):  # pragma: no cover - cache introspection API drift
            return -1

    def _dataset(self, vol: VolumeSpec) -> ViewDataset:
        # view-sampling seed derived from the timestep content, not from this
        # trainer's rng position: a warm pipeline and a cold baseline handed
        # the same timestep then draw identical batch orders (fair
        # steps-to-target comparisons in benchmarks/insitu_throughput.py)
        return ViewDataset(
            vol,
            n_views=self.n_views,
            img_h=self.cfg.img_h,
            img_w=self.cfg.img_w,
            radius=self.radius,
            cache_dir=None,
            n_steps_raymarch=self.n_steps_raymarch,
            seed=zlib.crc32(vol.name.encode()) & 0x7FFFFFFF,
        )

    def _eval_psnr(self, data: ViewDataset) -> float:
        rec = self.obs.trace
        with rec.span(self._rid, "eval") if rec else NO_SPAN as sp:
            cam, gt = data.view(self.eval_view % self.n_views)
            img, _ = self._eval_fn(self.state.params, cam)
            p = float(psnr(img, gt))
            if rec:
                sp.meta["psnr"] = round(p, 3)
        self.obs.metrics.gauge("train.psnr").set(round(p, 4))
        return p

    def _fit(self, data: ViewDataset, steps: int, *, psnr0: float) -> tuple[float, list]:
        """The optimization loop of one timestep, instrumented per step:
        ``batch`` (host view assembly) -> ``dispatch`` (jitted call returns
        under async dispatch) -> ``device`` (bounded by block_until_ready,
        traced runs only — an untraced run keeps jax's dispatch overlap and
        the step stays bitwise identical either way). Wall per step always
        lands in the ``train.step_ms`` histogram; device seconds land in
        ``train.device_ms`` when tracing bounds them."""
        m = self.obs.metrics
        step_ms = m.histogram("train.step_ms")
        device_ms = m.histogram("train.device_ms")
        loss_gauge = m.gauge("train.loss")
        steps_total = m.counter("train.steps")
        curve = []
        loss = float("nan")
        if self.eval_every > 0:
            curve.append((0, psnr0))  # already measured by the caller
        rid = self._rid
        batches = iter(data.batches(self.cfg.batch_size, steps=steps))
        for i in range(steps):
            rec = self.obs.trace  # re-read: tracing may toggle mid-fit
            with rec.span(rid, "batch", step=i) if rec else NO_SPAN as sp:
                got = next(batches, None)
                if got is None:
                    sp.drop()  # the stream ended early: no batch was assembled
            if got is None:
                break
            cams, gt = got
            t_batch = now()
            with rec.span(rid, "dispatch", step=i) if rec else NO_SPAN:
                self.state, metrics = self._step_fn(self.state, cams, gt)
            if rec:
                t_disp = now()
                jax.block_until_ready(self.state)
                t_dev = now()
                rec.record(rid, "device", t_disp, t_dev, step=i)
                device_ms.observe((t_dev - t_disp) * 1e3)
            loss = float(metrics["loss"])  # blocks on the step either way
            loss_gauge.set(loss)
            steps_total.inc()
            step_ms.observe(since(t_batch) * 1e3)
            if self.eval_every > 0 and (i + 1) % self.eval_every == 0:
                curve.append((i + 1, self._eval_psnr(data)))
        return loss, curve

    def reset(self) -> None:
        """Forget the model but keep the jitted fns: the next ``start()`` at
        the same capacity is compile-free. Lets warm-vs-cold baselines
        (``benchmarks/insitu_throughput.py``) cold-start many timesteps
        without re-tracing identical shapes."""
        self.state = None
        self.t_index = 0
        self.reports = []

    def shard_balance(self, *, record: bool = True) -> dict:
        """Per-model-shard load stats of the current state (see
        :func:`repro.core.train.shard_balance`); lands them on the registry
        (``train.shard_*`` gauges) unless ``record=False``."""
        assert self.state is not None, "no model yet"
        bal = shard_balance(self.state, opacity_thresh=self.cfg.prune_opacity_thresh)
        if record:
            record_shard_balance(self.obs.metrics, bal)
        return bal

    # ------------------------------------------------------------ timesteps
    def start(self, vol: VolumeSpec, *, steps: int | None = None) -> TimestepReport:
        assert self.state is None, "start() already called; use advance()"
        t0 = now()
        self._rid = new_request_id()
        rec = self.obs.trace
        pts, _, cols = extract_isosurface_points(vol, max_points=self.max_points)
        if rec:
            rec.record(self._rid, "extract", t0, now(), t_index=self.t_index,
                       points=int(pts.shape[0]), vol=vol.name)
        if self.capacity is None:
            quantum = self.n_shards * self.cfg.pad_quantum
            want = int(pts.shape[0] * self.capacity_factor)
            self.capacity = max(-(-want // quantum) * quantum, quantum)
        assert self.capacity % (self.n_shards * self.cfg.pad_quantum) == 0
        if pts.shape[0] > self.capacity:
            keep = self.rng.choice(pts.shape[0], self.capacity, replace=False)
            pts, cols = pts[keep], cols[keep]
        g = fixed_capacity_init(pts, cols, self.capacity, sh_degree=self.cfg.sh_degree, init_scale=self.init_scale)
        self.state = jax.device_put(init_state(g), state_shardings(self.mesh))
        if self._step_fn is None:
            self._step_fn = make_train_step(self.mesh, self.cfg)
            self._eval_fn = make_eval_render(self.mesh, self.cfg)
        return self._absorb(vol, pts, cols, 0, steps or self.cold_steps, "cold", t0)

    def advance(self, vol: VolumeSpec, *, steps: int | None = None) -> TimestepReport:
        assert self.state is not None, "advance() before start()"
        t0 = now()
        self._rid = new_request_id()
        rec = self.obs.trace
        pts, _, cols = extract_isosurface_points(vol, max_points=self.max_points)
        if rec:
            rec.record(self._rid, "extract", t0, now(), t_index=self.t_index,
                       points=int(pts.shape[0]), vol=vol.name)
        # params before reseed+training: the diff baseline for changed_slots
        prev_params = jax.tree_util.tree_map(np.asarray, self.state.params)
        t_rs = now() if rec else 0.0
        self.state, n_reseeded, _ = reseed_dead_slots(
            self.state,
            pts,
            cols,
            init_scale=self.init_scale,
            opacity_thresh=self.cfg.prune_opacity_thresh,
            rng=self.rng,
        )
        self.state = jax.device_put(self.state, state_shardings(self.mesh))
        if rec:
            rec.record(self._rid, "reseed", t_rs, now(), t_index=self.t_index,
                       filled=int(n_reseeded))
        self.obs.metrics.counter("train.reseeded").inc(int(n_reseeded))
        rep = self._absorb(
            vol, pts, cols, n_reseeded, steps or self.warm_steps, "warm", t0,
            prev_params=prev_params,
        )
        return rep

    def _absorb(self, vol, pts, cols, n_reseeded, steps, mode, t0, prev_params=None) -> TimestepReport:
        m = self.obs.metrics
        data = self._dataset(vol)
        p_before = self._eval_psnr(data)
        ttrain = now()
        loss, curve = self._fit(data, steps, psnr0=p_before)
        train_s = since(ttrain)
        rec = self.obs.trace
        if rec:
            rec.record(self._rid, "fit", ttrain, now(), t_index=self.t_index,
                       mode=mode, steps=steps)
        changed = None
        if prev_params is not None:
            # one host-side diff covers reseeded slots AND optimizer-moved
            # rows: everything the serving tier must treat as dirty
            from repro.serve_gs.footprint import changed_indices

            now_params = jax.tree_util.tree_map(np.asarray, self.state.params)
            changed = [int(i) for i in changed_indices(prev_params, now_params)]
        rep = TimestepReport(
            t_index=self.t_index,
            name=vol.name,
            mode=mode,
            steps=steps,
            n_extracted=int(pts.shape[0]),
            n_reseeded=int(n_reseeded),
            psnr_before=p_before,
            psnr_after=self._eval_psnr(data),
            loss_final=loss,
            wall_s=since(t0),
            train_s=train_s,
            n_traces=self.n_traces,
            psnr_curve=curve,
            changed_slots=changed,
        )
        # per-timestep telemetry: shard balance (the rebalancing trigger
        # signal), the step's analytic all-gather payload, and the device
        # memory watermark — Miranda-scale capacity limits show up here
        # timesteps before they OOM
        self.shard_balance()
        m.counter("train.gather_bytes").inc(
            all_gather_bytes_per_step(self.cfg, self.mesh, self.state.params.n) * steps
        )
        m.counter("train.timesteps").inc()
        m.histogram("train.timestep_wall_ms").observe(rep.wall_s * 1e3)
        devmem.record(m)
        self.reports.append(rep)
        self.t_index += 1
        if self.verbose:
            print(
                f"[insitu] t={rep.t_index} {rep.mode:4s} {rep.steps:4d} steps "
                f"PSNR {rep.psnr_before:5.2f}->{rep.psnr_after:5.2f} dB "
                f"reseed {rep.n_reseeded} ({rep.wall_s:.1f}s, traces={rep.n_traces})"
            )
        return rep

    def run(self, stream, *, store=None, server=None, serve_timestep=0) -> list[TimestepReport]:
        """Consume a ``VolumeStream``; optionally append each timestep's
        params to a ``TemporalCheckpointStore`` and/or push each timestep to
        a live ``RenderServer``.

        With the store's default asynchronous writer, ``append`` only pulls
        params to host and enqueues the encode+write — delta quantization and
        compression overlap with the *next* timestep's training instead of
        stalling the stream. The store is flushed before returning, so every
        appended timestep is durable when ``run`` hands back its reports.

        ``server`` wires the live-viewing loop with **no caller-side row
        math**: after each timestep the model is re-registered on the
        server's ``serve_timestep`` timeline slot with this timestep's
        ``changed_slots``, so the server computes per-pose dirty tile rows
        itself from the changed Gaussians' projected bounds (cold start
        passes no ``changed`` and drops everything, which is vacuous on the
        first registration).
        """
        out = []
        for vol in stream:
            rep = self.start(vol) if self.state is None else self.advance(vol)
            out.append(rep)
            rec = self.obs.trace
            if store is not None:
                with rec.span(self._rid, "ckpt", t_index=rep.t_index) if rec else NO_SPAN:
                    store.append(rep.t_index, self.state.params)
            if server is not None:
                t0 = now() if rec else 0.0
                params = jax.tree_util.tree_map(np.asarray, self.state.params)
                if rep.changed_slots is None:
                    server.add_timestep(int(serve_timestep), params)
                else:
                    server.add_timestep(
                        int(serve_timestep), params,
                        changed=np.asarray(rep.changed_slots, np.int64),
                    )
                if rec:
                    rec.record(
                        self._rid, "serve", t0, now(), t_index=rep.t_index,
                        changed=(len(rep.changed_slots)
                                 if rep.changed_slots is not None else -1),
                    )
        if store is not None:
            store.flush()
        return out
