"""Device time per named scope (``bench/scopes.py``) and the per-layer
metrics that read it."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest
from tiny import ROOT
from xspace import xspace

import scopes as S
import trace_reduce as T
from harness import load_module

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_train_serve.xplane.pb.gz"
JIT = "jit(local_step)"


@pytest.mark.parametrize("path, scope", [
    (f"{JIT}/jvp(vmap(project))/sub:", "project"),
    (f"{JIT}/transpose(jvp(vmap(tile_gather)))/scatter-add", "tile_gather"),
    (f"{JIT}/transpose(jvp(vmap(depth_sort)))/add", "depth_sort"),
    (f"{JIT}/jvp(vmap(jit(build_tile_lists_hier)))/binning/jit(build_tile_lists)/binning/while",
     "binning"),
    (f"{JIT}/transpose(jvp(loss))/vmap()/transpose:", "loss"),
    ("jit(local)/raster/tile_raster_fwd", "raster"),
    (f"{JIT}/grad_reduce/psum", "grad_reduce"),
    ("adam", "adam"),
    (f"{JIT}/add", None),
    ("", None),
    ("jit(projection_helper)/mul", None),
])
def test_scope_is_the_innermost_known_name_of_the_path(path, scope):
    assert S.scope_of(path) == scope


def write(tmp_path: Path, data: bytes, name: str = "t.xplane.pb") -> str:
    path = tmp_path / name
    path.write_bytes(data)
    return str(path)


def test_union_per_scope_counts_an_op_nested_in_a_while_once(tmp_path):
    ops = [
        ("%while.1 = while()", "jit(f)/binning/while", 0, 100_000),
        ("%sort.2 = sort()", "jit(f)/binning/while/body/sort", 10_000, 90_000),
        ("%fusion.3 = fusion()", "jit(f)/transpose(jvp(tile_gather))/scatter-add", 100_000, 150_000),
        ("%copy.4 = copy()", None, 150_000, 160_000),
    ]
    sp = S.split(S.scoped_ops(write(tmp_path, xspace(ops))), (0, 200_000))
    assert sp.seconds["binning"] == pytest.approx(100e-6)
    assert sp.seconds["tile_gather"] == pytest.approx(50e-6)
    assert sp.busy_s == pytest.approx(160e-6)
    assert sp.unscoped_s == pytest.approx(10e-6)
    assert sp.unscoped_share == pytest.approx(10 / 160)


def test_ops_are_clipped_to_the_window_and_averaged_over_chips(tmp_path):
    a = [("%sort.1 = sort()", "jit(f)/depth_sort/sort", 0, 100_000)]
    b = [("%sort.1 = sort()", "jit(f)/depth_sort/sort", 50_000, 150_000),
         ("%fusion.2 = fusion()", "jit(f)/jvp(loss)/mul", 150_000, 350_000)]
    path = write(tmp_path, xspace(a, chip=0) + xspace(b, chip=1))
    sp = S.split(S.scoped_ops(path), (50_000, 250_000))
    assert sp.chips == 2
    assert sp.seconds["depth_sort"] == pytest.approx(75e-6)
    assert sp.seconds["loss"] == pytest.approx(50e-6)
    assert sp.busy_s == pytest.approx(125e-6)


def test_wire_format_reads_what_the_profiler_reads(tmp_path):
    """The decoder's device operations are ProfileData's, time for time
    (ProfileData floors each start and duration to whole nanoseconds)."""
    from jax.profiler import ProfileData

    path = write(tmp_path, gzip.decompress(FIXTURE.read_bytes()))
    got = S.read_device_planes(path)
    want = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes if plane.name == "/device:TPU:0"
            for line in plane.lines if line.name == "XLA Ops" for ev in line.events]
    assert len(got) == len(want) > 2000
    for (_, text, _, s, e), (name, ws, we) in zip(got, want):
        assert text == name
        assert ws <= s < ws + 1.0 and we <= e < we + 2.0


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A TPU v5e trace of the train step (256 px, 32,768 Gaussians, batch 4)
    and of served frames, device plane and HLO modules only."""
    path = tmp_path_factory.mktemp("v5e") / "v5e.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    ops = S.scoped_ops(str(path))
    return path, ops


def test_recorded_trace_scopes_the_backward_scatters_and_loops(recorded):
    """Operations the compiler leaves without ``tf_op`` are resolved in the
    program's HLO: the gathers' backward scatter-adds, the sorts in front of
    them, and the binning loops."""
    _, ops = recorded
    by = {}
    for o in ops:
        if o.name.startswith("%fusion.4 = f32[11,131072]"):
            by["tile_gather backward"] = S.scope_of(o.path)
        if o.name.startswith("%fusion.5 = f32[11,131072]"):
            by["depth_sort backward"] = S.scope_of(o.path)
        if o.name.startswith("%while."):
            by.setdefault("loops", set()).add(S.scope_of(o.path))
    assert by == {"tile_gather backward": "tile_gather", "depth_sort backward": "depth_sort",
                  "loops": {"binning"}}


def test_recorded_trace_is_almost_all_scoped(recorded):
    _, ops = recorded
    window = (min(o.start_ns for o in ops), max(o.end_ns for o in ops))
    sp = S.split(ops, window)
    assert 0.05 < sp.busy_s < 0.07
    assert sp.unscoped_share < 0.01
    assert all(sp.seconds[k] > 0 for k in S.SCOPES)
    # the binning scans' bodies run inside their loops: counted once, so the
    # scope's time is well under the sum of its operations' times
    binning = [o for o in ops if S.scope_of(o.path) == "binning"]
    assert sp.seconds["binning"] < 0.8 * sum(o.end_ns - o.start_ns for o in binning) * 1e-9


class _Reduced:
    def __init__(self, window_ns):
        self.window_ns = window_ns


@pytest.mark.parametrize("metric, scope, per", [
    ("gather_ms.train", "tile_gather", "steps"),
    ("depth_sort_ms.train", "depth_sort", "steps"),
    ("binning_ms.train", "binning", "steps"),
    ("binning_ms.serve", "binning", "frames"),
])
def test_scope_metrics_read_their_scope_per_step_or_frame(tmp_path, monkeypatch, metric, scope, per):
    ops = [("%while.1 = while()", "jit(f)/binning/while", 0, 4_000_000),
           ("%fusion.2 = fusion()", "jit(f)/transpose(jvp(tile_gather))/x", 4_000_000, 10_000_000),
           ("%sort.3 = sort()", "jit(f)/depth_sort/sort", 10_000_000, 12_000_000)]
    (tmp_path / "cell").mkdir()
    write(tmp_path / "cell", xspace(ops))
    monkeypatch.setattr(S, "TRACES", tmp_path)
    reader = load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    run = {"workload": "cell", "trace": _Reduced((0, 20_000_000)), per: 2}
    want = {"binning": 4.0, "tile_gather": 6.0, "depth_sort": 2.0}[scope] / 2
    assert reader.read(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["gather_ms.train", "depth_sort_ms.train", "binning_ms.train",
                                    "binning_ms.serve"])
def test_scope_metrics_find_nothing_in_a_program_without_scopes(tmp_path, monkeypatch, metric):
    ops = [("%fusion.1 = fusion()", None, 0, 1_000_000), ("%sort.2 = sort()", "jit(f)/sort", 0, 10)]
    (tmp_path / "cell").mkdir()
    write(tmp_path / "cell", xspace(ops))
    monkeypatch.setattr(S, "TRACES", tmp_path)
    reader = load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    run = {"workload": "cell", "trace": _Reduced((0, 2_000_000)), "steps": 3, "frames": 3}
    assert reader.read(run) is None
    assert reader.read({**run, "workload": "no-trace-here"}) is None


def test_main_prints_the_split(tmp_path, capsys):
    ops = [("%while.1 = while()", "jit(f)/binning/while", 0, 4_000_000),
           ("%copy.2 = copy()", None, 4_000_000, 5_000_000)]
    write(tmp_path, xspace(ops))
    assert S.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert '"binning": 0.004' in out and '"scoped_share_of_busy": 0.8' in out
    assert T.find_xplane(tmp_path).endswith(".xplane.pb")
