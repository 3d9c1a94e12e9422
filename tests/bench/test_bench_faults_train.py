"""The training cell's comparison, driven through a whole run at a tiny
size: a sound program passes it, and a broken timed path or the control
(the reference rendering in bfloat16) does not."""
from __future__ import annotations

import dataclasses

from tiny import R, run_tiny, tiny_cell

CELL = "miranda574k-1024px.train-b4"
MIX = {"batch": 2}


def test_sound_run_is_correct(tmp_path):
    out = run_tiny(tiny_cell(CELL, traffic=MIX), tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0 and out["attempted"] % 2 == 0
    assert out["metrics"]["train_views_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_a_step_that_returns_its_state_unchanged_fails(tmp_path, monkeypatch):
    from repro.launch import train as T

    real = T.GSTrainer.step_fn

    def frozen(self):
        fn = real.fget(self)
        return lambda state, cams, gt: (state, fn(state, cams, gt)[1])

    monkeypatch.setattr(T.GSTrainer, "step_fn", property(frozen))
    out = run_tiny(tiny_cell(CELL, traffic=MIX), tmp_path)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] > out["checks"]["change_gap"]["limit"]


def test_half_the_batch_left_out_fails(tmp_path, monkeypatch):
    from repro.core.projection import Camera
    from repro.core.train import make_train_step
    from repro.launch import train as T

    def half(self):
        cfg = dataclasses.replace(self.cfg, batch_size=self.cfg.batch_size // 2)
        fn = self.__dict__.setdefault("_half", make_train_step(self.mesh, cfg))

        def step(state, cams, gt):
            h = gt.shape[0] // 2
            return fn(state, Camera(*[x[:h] for x in cams]), gt[:h])

        return step

    monkeypatch.setattr(T.GSTrainer, "step_fn", property(half))
    out = run_tiny(tiny_cell(CELL, traffic=MIX), tmp_path)
    assert out["correct"] is False
    assert out["checks"]["loss_gap"]["value"] > out["checks"]["loss_gap"]["limit"]


def test_control_fails_the_limits(tmp_path):
    import jax

    cell = tiny_cell(CELL, traffic=MIX)
    ctx = R.RunContext(cell, seed=2**31 + 3, seconds=1.0, trace=False, devices=jax.devices(),
                       cache=tmp_path)
    checks = cell.kind.control(ctx)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
