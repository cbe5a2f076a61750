"""Device time of the backbone's substages (`benchmarks/chip/substages.py`)
and the Swin cell's readers, on a hand-written HLO snippet and a small
synthetic trace."""
from __future__ import annotations

import types

import pytest

from benchmarks.chip import harness, stages, substages

EPISODE = "jit(_episode)/while/body/closed_call/jit(detector_scores_tokens)"
HLO = f"""\
HloModule jit__episode, entry_computation_layout={{()->f32[]}}

%body.3 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %fusion.9 = f32[8]{{0}} fusion(%a), kind=kLoop, metadata={{op_name="{EPISODE}/backbone/while/body/closed_call/window_attention/dot_general"}}
  %fusion.10 = f32[8]{{0}} fusion(%a), kind=kLoop, metadata={{op_name="{EPISODE}/backbone/while/body/closed_call/patch_merge/dot_general"}}
  %fusion.11 = f32[8]{{0}} fusion(%a), kind=kLoop, metadata={{op_name="{EPISODE}/backbone/while/body/closed_call/dot_general"}}
  %custom-call.12 = f32[8]{{0}} custom-call(%a), metadata={{op_name="jit(_episode)/while/body/closed_call/crop_to_tokens/window_attention/pallas_call"}}
  %add.13 = f32[] add(%x, %y), metadata={{op_name="window_attention/reduce_sum"}}
  ROOT %tuple.2 = (s32[], f32[8]) tuple(%a, %fusion.9)
}}
"""
HOST = [["bench/call", 100, 1000]]
DEVICES = [
    [["fusion.9", 100, 300], ["fusion.10", 400, 100],
     ["fusion.11", 500, 200], ["custom-call.12", 700, 100],
     ["fusion.9", 1150, 50]],                  # after the window
    [["fusion.9", 100, 100], ["fusion.10", 300, 300]],
]


def test_substage_of_needs_the_backbone_before_it():
    f = substages.substage_of
    assert f(f"{EPISODE}/backbone/window_attention/add") == \
        "window_attention"
    assert f(f"{EPISODE}/backbone/while/body/patch_merge/dot") == \
        "patch_merge"
    assert f(f"{EPISODE}/backbone/dot_general") is None
    assert f("jit(f)/crop_to_tokens/window_attention/add") is None
    assert f("window_attention/reduce_sum") is None
    assert substages.SUBSTAGES == ("window_attention", "patch_merge")


def test_hlo_instructions_map_to_their_substage():
    m = substages.op_substages(HLO)
    assert m == {"fusion.9": "window_attention", "fusion.10": "patch_merge"}
    # the stage readers still see all three backbone ops as `backbone`
    st = stages.op_stages(HLO)
    assert {st[k] for k in ("fusion.9", "fusion.10", "fusion.11")} == {
        "backbone"}


def test_substage_times_are_means_over_devices_inside_the_window():
    red = substages.reduce_substages(DEVICES, HOST,
                                     substages.op_substages(HLO))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["substages"]["window_attention"] == pytest.approx(
        (300 + 100) / 2 * 1e-9)
    assert red["substages"]["patch_merge"] == pytest.approx(
        (100 + 300) / 2 * 1e-9)


def test_readers_of_the_swin_cell():
    """Each reader turns its window into ms a step, and reads nothing
    from an untraced run or a window that ran no such op."""
    run = types.SimpleNamespace(trace={"calls": 2})
    run.__dict__["_substage_times"] = {
        "steps": 32, "substages": {"window_attention": 0.64,
                                   "patch_merge": 0.0}}
    run.__dict__["_stage_times"] = {
        "steps": 32, "stages": dict.fromkeys(
            stages.STAGES + (stages.UNSTAGED,), 0.0) | {
                "backbone": 3.2, "crop_to_tokens": 0.16}}
    read = {name: harness.load_module("metrics", name).read
            for name in ("window_attention_ms_per_step",
                         "swin_backbone_ms_per_step",
                         "swin_crop_ms_per_step")}
    assert read["window_attention_ms_per_step"](run) == pytest.approx(20.0)
    assert read["swin_backbone_ms_per_step"](run) == pytest.approx(100.0)
    assert read["swin_crop_ms_per_step"](run) == pytest.approx(5.0)
    assert substages.ms_per_step(run, "patch_merge") is None
    untraced = types.SimpleNamespace(trace=None)
    assert all(r(untraced) is None for r in read.values())
