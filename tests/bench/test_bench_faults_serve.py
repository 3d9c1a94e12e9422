"""The serving cell's comparison, driven through a whole run at a tiny
size: a sound server passes it; a frame altered where it is rendered, or
the control (the reference rendering in bfloat16), does not."""
from __future__ import annotations

from tiny import R, run_tiny, tiny_cell

CELL = "miranda574k-1024px.serve-novel"
MIX = {"rate_per_s": 3.0, "checked_frames": 3}


def test_sound_run_is_correct(tmp_path):
    out = run_tiny(tiny_cell(CELL, traffic=MIX), tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 6 and out["failed"] == 0
    assert out["metrics"]["frame_p95_ms"]["value"] > 0


def test_a_frame_altered_where_it_is_rendered_fails(tmp_path, monkeypatch):
    from repro.serve_gs import server as S

    real = S.make_batched_eval_render

    def altered(*a, **kw):
        fn = real(*a, **kw)
        return lambda params, cams: fn(params, cams).at[:, :16, :16, :].add(0.25)

    monkeypatch.setattr(S, "make_batched_eval_render", altered)
    out = run_tiny(tiny_cell(CELL, traffic=MIX), tmp_path)
    assert out["correct"] is False
    assert out["checks"]["frame_max_gap"]["value"] > out["checks"]["frame_max_gap"]["limit"]


def test_control_fails_the_limits(tmp_path):
    import jax

    cell = tiny_cell(CELL, traffic=MIX)
    ctx = R.RunContext(cell, seed=2**31 + 3, seconds=2.0, trace=False, devices=jax.devices(),
                       cache=tmp_path)
    checks = cell.kind.control(ctx)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
