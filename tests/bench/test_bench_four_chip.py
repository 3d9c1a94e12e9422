"""The four-chip training cell: its readers of the splat exchange and of
the collectives, and a whole run of the cell on four CPU devices at a tiny
size, compared with the plain reference as the chip run is."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest
from tiny import ROOT
from xspace import xspace

import trace_reduce as T
from harness import load_module

CELL = "miranda574k-2048px-4chip.train-b4"
JIT = "jit(local_step)"
MS = 1_000_000

# operation names as the TPU compiler gives them for the (1, 4) step (cut short)
ALL_GATHER = ("%all-gather.4 = f32[4,574464,11]{1,2,0:T(8,128)} all-gather(%broadcast_add_fusion.1), "
              "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}")
ALL_REDUCE = ("%all-reduce.25 = f32[4,574464,11]{1,2,0:T(8,128)} all-reduce(%copy.320), "
              "channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_17.18.clone")
SCALAR_PSUM = ("%all-reduce.26 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce("
               "%get-tuple-element.364, %get-tuple-element.405), channel_id=1")
PERMUTE_START = ("%collective-permute-start = (f32[4,5,2048,15]{2,0,3,1:T(4,128)S(1)}, "
                 "f32[4,5,2048,15]{2,0,3,1:T(4,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
                 "collective-permute-start(%copy.298), source_target_pairs={{0,1},{1,2},{2,3}}")
PERMUTE_DONE = ("%collective-permute-done = f32[4,5,2048,15]{2,0,3,1:T(4,128)S(1)} "
                "collective-permute-done(%collective-permute-start)")
FUSION = ("%fusion.46 = f32[4,143616]{1,0:T(4,128)S(1)} fusion(%all-reduce.25, %multiply.24), "
          "kind=kLoop, calls=%fused_computation.135")
SORT = "%sort.3 = (s32[4,574464]{1,0}, s32[4,574464]{1,0}) sort(%copy_bitcast_fusion.2, %iota.19)"


def reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def as_op(name, chip=0, start=0.0, end=1.0):
    return T.Op(chip, name, start, end)


@pytest.mark.parametrize("name, want", [
    (ALL_GATHER, True), (ALL_REDUCE, True), (SCALAR_PSUM, True), (PERMUTE_START, True),
    (PERMUTE_DONE, True),
    ("%all-gather-start.1 = (f32[8]{0}, f32[32]{0}) all-gather-start(%p), dimensions={0}", True),
    ("%all-gather-done.1 = f32[32]{0} all-gather-done(%all-gather-start.1)", True),
    ("%all-reduce-start.2 = f32[8]{0} all-reduce-start(%p), to_apply=%add", True),
    ("%all-reduce-done.2 = f32[8]{0} all-reduce-done(%all-reduce-start.2)", True),
    ("%reduce-scatter.3 = f32[8]{0} reduce-scatter(%p), dimensions={0}, to_apply=%add", True),
    ("%collective-permute.4 = f32[8]{0} collective-permute(%p), source_target_pairs={{0,1}}", True),
    (FUSION, False), (SORT, False),
])
def test_collectives_are_matched_by_opcode(name, want):
    import collectives

    assert collectives.is_collective(as_op(name)) is want


def test_collective_ms_is_collective_time_per_step_mean_over_chips():
    ops = [as_op(ALL_GATHER, 0, 0, 3 * MS), as_op(ALL_REDUCE, 0, 3 * MS, 7 * MS),
           as_op(PERMUTE_DONE, 0, 7 * MS, 8 * MS), as_op(FUSION, 0, 8 * MS, 20 * MS),
           as_op(ALL_GATHER, 1, 0, 5 * MS), as_op(ALL_REDUCE, 1, 5 * MS, 9 * MS),
           as_op(SORT, 1, 9 * MS, 30 * MS)]
    r = T.reduce(ops, [T.Span("bench.window", 0, 40 * MS)])
    read = reader("collective_ms.train4").read
    assert read({"trace": r, "steps": 2}) == pytest.approx((8 + 9) / 2 / 2)
    quiet = T.reduce([as_op(FUSION, 0, 0, MS)], [T.Span("bench.window", 0, 40 * MS)])
    assert read({"trace": quiet, "steps": 2}) is None


class _Reduced:
    def __init__(self, window_ns):
        self.window_ns = window_ns


def _trace(tmp_path, ops) -> Path:
    (tmp_path / "cell").mkdir()
    (tmp_path / "cell" / "t.xplane.pb").write_bytes(xspace(ops))
    return tmp_path


def test_exchange_ms_reads_the_splat_exchange_scope_forward_and_backward(tmp_path, monkeypatch):
    """The all-gather in the scope, and its backward (psum_scatter, which the
    TPU compiler may turn into an all-reduce) under ``transpose(jvp(...))``."""
    ops = [(ALL_GATHER, f"{JIT}/splat_exchange/all_gather", 0, 3 * MS),
           ("%sort.2 = sort()", f"{JIT}/depth_sort/sort", 3 * MS, 5 * MS),
           (ALL_REDUCE, f"{JIT}/transpose(jvp(splat_exchange))/reduce_scatter", 5 * MS, 9 * MS),
           (SCALAR_PSUM, f"{JIT}/jvp(loss)/psum", 9 * MS, 10 * MS)]
    m = reader("exchange_ms.train4")
    monkeypatch.setattr(m.scopes, "TRACES", _trace(tmp_path, ops))
    run = {"workload": "cell", "trace": _Reduced((0, 20 * MS)), "steps": 2}
    assert m.read(run) == pytest.approx(7 / 2)
    split = m.scopes.for_run(run)
    assert split.seconds["depth_sort"] == pytest.approx(2e-3)
    assert split.seconds["loss"] == pytest.approx(1e-3)


def test_exchange_ms_finds_nothing_in_a_program_without_the_scope(tmp_path, monkeypatch):
    ops = [(ALL_GATHER, f"{JIT}/all_gather", 0, 3 * MS),
           (ALL_REDUCE, f"{JIT}/transpose(jvp(jvp(loss)))/psum", 3 * MS, 7 * MS)]
    m = reader("exchange_ms.train4")
    monkeypatch.setattr(m.scopes, "TRACES", _trace(tmp_path, ops))
    run = {"workload": "cell", "trace": _Reduced((0, 10 * MS)), "steps": 2}
    assert m.read(run) is None
    assert m.read({**run, "workload": "no-trace-here"}) is None


def test_exchange_reader_leaves_the_shared_scope_list_alone():
    import scopes

    m = reader("exchange_ms.train4")
    assert "splat_exchange" in m.scopes.SCOPES and "splat_exchange" not in scopes.SCOPES


RUN_TINY = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from pathlib import Path
    from tiny import run_tiny, tiny_cell

    cell = tiny_cell(sys.argv[1], traffic={"batch": 4})
    print(json.dumps(run_tiny(cell, Path(sys.argv[2]))))
    """
)


def test_four_chip_cell_is_correct_on_four_devices(tmp_path):
    """The (1, 4)-mesh step at 64 px (one 16-row strip per device) against the
    plain reference: a step that scaled the gradient by the number of
    shards reads ``grad_gap`` 3."""
    out = subprocess.run(
        [sys.executable, "-c", RUN_TINY, CELL, str(tmp_path)],
        cwd=Path(__file__).resolve().parent, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"] is True, res["checks"]
