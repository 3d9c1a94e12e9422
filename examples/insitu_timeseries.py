"""In-situ-style streaming reconstruction (the paper's future-work item).

A simulation produces a time-evolving volume; instead of writing full volume
dumps (the I/O burden the paper wants to avoid), each timestep is absorbed
into one fixed-capacity Gaussian model WARM-STARTED from the previous step —
few optimization steps per timestep, one jit trace for the whole sequence.
This is the ``repro.insitu`` subsystem end-to-end: an in-situ callback stream,
the incremental trainer, temporal (keyframe + quantized delta) checkpoints,
and a time-scrubbing render across the stored sequence.

  PYTHONPATH=src python examples/insitu_timeseries.py
"""
import os
import tempfile

from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.insitu import InsituTrainer, TemporalCheckpointStore, build_timeline_server, scrub
from repro.serve_gs import front_camera
from repro.volume.timevary import synthetic_stream


def main():
    H = 48
    mesh = make_mesh((1, 1))
    cfg = GSConfig(
        img_h=H, img_w=H, batch_size=2, k_per_tile=128, max_steps=200,
        densify_from=10**9, opacity_reset_interval=10**9,
    )

    # the "simulation": a Miranda-like mixing layer growing over 4 timesteps
    stream = synthetic_stream("miranda", 4, res=32, t1=0.2)
    store = TemporalCheckpointStore(
        os.path.join(tempfile.mkdtemp(prefix="insitu_example_"), "seq"), keyframe_interval=4
    )
    trainer = InsituTrainer(
        cfg, mesh, cold_steps=60, warm_steps=15, n_views=6,
        max_points=800, n_steps_raymarch=48, init_scale=0.06, verbose=True,
    )
    trainer.run(stream, store=store)
    print(f"train-step traces across the sequence: {trainer.n_traces} (fixed capacity -> 1)")
    print(f"temporal store: {store.stats()}")

    # post hoc time-scrub: one camera, every stored timestep
    server = build_timeline_server(store, cfg, n_levels=2, max_batch=2)
    cam = front_camera(server.pyramid, img_h=H, img_w=H)
    frames = scrub(server, cam, store.timesteps())
    for t, frame in frames.items():
        print(f"  t={t}: frame {frame.shape}, surface pixels {(frame.sum(-1) > 0.01).mean():.1%}")


if __name__ == "__main__":
    main()
