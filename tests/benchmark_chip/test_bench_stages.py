"""Per-stage device time (`benchmarks/chip/stages.py`) and the readers
built on it, on a small synthetic trace and a hand-written HLO snippet,
and `windows_read_pct` on hand-made outputs."""
from __future__ import annotations

import types

import numpy as np
import pytest

from benchmarks.chip import devtrace, harness, stages

HLO = """\
HloModule jit__episode, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(_episode)/while/body/closed_call/crop_to_tokens/jit(_crop_patchify)/tanh"}
}

%body.3 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.9 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_episode)/while/body/closed_call/crop_to_tokens/jit(_crop_patchify)/tanh" source_file="ops.py" source_line=52}
  %convolution.4 = f32[8]{0} convolution(%fusion.9, %p), metadata={op_type="conv" op_name="jit(_episode)/while/body/closed_call/backbone/while/body/dot_general"}
  %while.5 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_episode)/while/body/closed_call/backbone/while"}
  %sort.6 = f32[8]{0} sort(%x), metadata={op_name="jit(_episode)/while/body/closed_call/jit(fleet_step)/controller/jit(_walk_one)/heads/sort"}
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%a, %sort.6)
}

ENTRY %main.7 () -> f32[] {
  %copy.8 = f32[8]{0} copy(%y)
  ROOT %while.7 = (s32[], f32[8]) while(%t0), condition=%cond.2, body=%body.3, metadata={op_name="jit(_episode)/while"}
}
"""

# two devices, times in ns; the host traced calls from 100 to 1100
HOST = [["bench/call", 100, 1000], ["ExecuteHelper", 150, 900]]
LOOP = "%while.7 = (s32[], f32[8]) while((s32[], f32[8]) %t0), body=%body.3"
DEVICES = [
    [[LOOP, 100, 1000],                       # holds everything below
     ["%fusion.9 = f32[8]{0} fusion(f32[8] %gte.1)", 150, 300],
     ["while.5", 500, 300],                   # the ViT's layer loop ...
     ["convolution.4", 550, 200],             # ... and its body
     ["sort.6", 850, 50],
     ["copy.8", 950, 100],
     ["custom-call.99", 1150, 50]],           # after the window
    [["fusion.9", 100, 400], ["sort.6", 600, 200]],
]


def test_hlo_instructions_map_to_their_first_stage():
    m = stages.op_stages(HLO)
    assert m["fusion.9"] == "crop_to_tokens"
    assert m["tanh.1"] == "crop_to_tokens"          # inside the fusion
    assert m["convolution.4"] == "backbone"         # op_type before op_name
    assert m["while.5"] == "backbone"
    # the first stage on the path wins over a later one
    assert m["sort.6"] == "controller"
    assert m["copy.8"] == "unstaged"                # no metadata
    assert m["while.7"] == "unstaged"               # the episode scan
    assert m["param_0"] == "unstaged"
    assert "fused_computation.1" not in m and "body.3" not in m


def test_stage_of_and_instruction_names():
    assert stages.stage_of("jit(f)/while/body/shortlist/top_k") == "shortlist"
    assert stages.stage_of("jit(f)/while/body/add") == "unstaged"
    assert stages.stage_of("jit(f)/controllerish/add") == "unstaged"
    assert stages.instruction(
        "%fusion.1390 = bf16[28901376,3]{1,0} fusion(...)") == "fusion.1390"
    assert stages.instruction("copy.1747") == "copy.1747"
    assert stages.instruction("%while.653") == "while.653"


def test_stage_grouping_counts_nested_ops_once():
    red = stages.reduce_stages(DEVICES, HOST, stages.op_stages(HLO))
    assert red["window_s"] == pytest.approx(1000e-9)
    d0 = {"unstaged": (1000 - 300 - 300 - 50 - 100) + 100,   # loop + copy
          "crop_to_tokens": 300,
          "backbone": (300 - 200) + 200,                    # loop + body
          "controller": 50}
    d1 = {"crop_to_tokens": 400, "controller": 200}
    for name in stages.STAGES + (stages.UNSTAGED,):
        want = (d0.get(name, 0) + d1.get(name, 0)) / 2 * 1e-9
        assert red["stages"][name] == pytest.approx(want), name
    # stages plus `unstaged` are the op self time, the same op_s as the
    # devtrace reduction gives, mean over the devices
    assert sum(red["stages"].values()) == pytest.approx(red["op_s"])
    base = devtrace.reduce_events(DEVICES, HOST)
    assert red["op_s"] == pytest.approx(
        np.mean([d["op_s"] for d in base["devices"]]))
    # the longest ops of each stage on the fullest device, by self time
    assert red["top"]["backbone"] == [
        ["convolution.4", pytest.approx(200e-9)],
        ["while.5", pytest.approx(100e-9)]]
    assert red["top"]["crop_to_tokens"] == [
        ["%fusion.9 f32[8]", pytest.approx(300e-9)]]


def test_devtrace_reduction_is_unchanged_by_stage_grouping():
    before = devtrace.reduce_events(DEVICES, HOST)
    stages.reduce_stages(DEVICES, HOST, stages.op_stages(HLO))
    assert devtrace.reduce_events(DEVICES, HOST) == before
    assert set(before) == {"window_s", "busy_s", "devices", "top_ops",
                           "idle_gaps"}


def test_stage_names_are_the_programs():
    from repro.obs.trace import STAGES

    assert stages.STAGES == STAGES


def _run(trace, **kw):
    return types.SimpleNamespace(trace=trace, **kw)


def test_stage_readers():
    secs = dict.fromkeys(stages.STAGES + (stages.UNSTAGED,), 0.0)
    secs.update(crop_to_tokens=0.96, backbone=0.18, unstaged=0.02)
    run = _run({"busy_s": 1.0})
    run.__dict__["_stage_times"] = {"stages": secs, "op_s": 1.16,
                                    "steps": 2}
    read = {n: harness.load_module("metrics", n).read for n in (
        "crop_ms_per_step", "backbone_ms_per_step", "heads_ms_per_step",
        "unstaged_pct")}
    assert read["crop_ms_per_step"](run) == pytest.approx(480.0)
    assert read["backbone_ms_per_step"](run) == pytest.approx(90.0)
    assert read["heads_ms_per_step"](run) is None     # ran no op
    assert read["unstaged_pct"](run) == pytest.approx(100 * 0.02 / 1.16)
    # an untraced run, and a program that names no stage, read nothing
    untraced, unnamed = _run(None), _run({"busy_s": 1.0})
    unnamed.__dict__["_stage_times"] = None
    for r in (untraced, unnamed):
        assert all(f(r) is None for f in read.values())


def test_windows_read_pct_by_hand():
    read = harness.load_module("metrics", "windows_read_pct").read
    grid = types.SimpleNamespace(n_orientations=75)
    spec = types.SimpleNamespace(grid_obj=lambda: grid)
    n_explored = np.array([[1, 1, 2, 0], [1, 3, 1, 1]], np.int32)  # [E, F]
    run = _run({}, config={"detector": {}}, spec=spec,
               traffic={"shortlist_k": 18},
               outputs={"out": {"n_explored": n_explored}})
    assert read(run) == pytest.approx(100 * 10 / (8 * 18))
    run.traffic = {"shortlist_k": None}                 # every window
    assert read(run) == pytest.approx(100 * 10 / (8 * 75))
    run.traffic = {"shortlist_k": 90}                   # K above N x Z
    assert read(run) == pytest.approx(100 * 10 / (8 * 75))
    run.config = {}                                     # no detector
    assert read(run) is None
