"""The chip benchmark's harness pieces that need no chip: finding cells by
name, the trace reduction, the FLOP count, the peak table, and the
refusal to run anywhere but on a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.chip import devtrace, flops, harness, peaks

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"


def test_every_cell_finds_its_files_by_name():
    bench = harness.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        config = harness.load_json("configs", cell["config"])
        traffic = harness.load_json("traffic", cell["traffic"])
        assert config["name"] == cell["config"]
        assert traffic["n_steps"] > 0
        harness.load_module("reference", config["reference"])
        if "detector" in config:
            harness.load_backbone(config.get("backbone"))
        for trace in (False, True):
            for m in harness.cell_metrics(bench, cell, trace):
                assert callable(harness.load_module("metrics",
                                                    m["name"]).read)


def test_config_files_match_benchmark_json():
    bench = harness.load_benchmark(ROOT)
    for c in bench["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]


def test_new_traffic_file_is_picked_up_without_an_edit(tmp_path):
    """A new cell is a workloads entry plus a data file."""
    shutil.copytree(BENCH / "configs", tmp_path / "configs")
    shutil.copytree(BENCH / "traffic", tmp_path / "traffic")
    mix = json.loads((BENCH / "traffic" / "k18-f15.json").read_text())
    mix.update(fps=3, why="a new mix")
    (tmp_path / "traffic" / "k18-f3.json").write_text(json.dumps(mix))
    bench = harness.load_benchmark(ROOT)
    bench["workloads"].append({"name": "approx-k18-f3",
                               "config": "madeye-approx",
                               "traffic": "k18-f3", "chips": 1,
                               "why": "test"})
    cell = harness.find_cell(bench, "approx-k18-f3")
    traffic = harness.load_json("traffic", cell["traffic"], base=tmp_path)
    assert traffic["fps"] == 3
    reported = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    assert reported == {"camera_steps_per_s", "hbm_gib", "setup_s"}
    with pytest.raises(harness.BenchError):
        harness.load_json("traffic", "no-such-mix", base=tmp_path)
    with pytest.raises(harness.BenchError):
        harness.find_cell(bench, "no-such-cell")


def test_seeds_are_drawn_from_the_seed_alone():
    a = harness.draw_seeds(2 ** 31 + 77, 8)
    assert a == harness.draw_seeds(2 ** 31 + 77, 8)
    assert a != harness.draw_seeds(2 ** 31 + 78, 8)
    assert all(0 <= s < 2 ** 31 for s in a["scene_seeds"])


# ---------------------------------------------------------------------------
# trace reduction, on a small recorded trace
# ---------------------------------------------------------------------------

# two devices; times in ns. The host traced one call from 100 to 1100.
TRACE = {
    "host": [["bench/call", 100, 1000], ["ExecuteHelper", 150, 900],
             ["PjitFunction(_episode)", 800, 200]],
    "devices": [
        [["fusion.1", 50, 100],            # starts before the window
         ["fusion.2", 200, 100],
         ["all-reduce-start.3", 250, 100],  # overlaps fusion.2
         ["fusion.1", 700, 100],
         ["convolution.4", 1050, 200]],    # runs past the window
        [["fusion.1", 100, 500]],
    ],
}


def test_trace_reduction():
    red = devtrace.reduce_events(TRACE["devices"], TRACE["host"])
    assert red["window_s"] == pytest.approx(1000e-9)
    d0, d1 = red["devices"]
    # union inside [100, 1100): [100,150) [200,350) [700,800) [1050,1100)
    assert d0["busy_s"] == pytest.approx(350e-9)
    assert d1["busy_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(425e-9)
    assert d0["n_ops"] == 5 and d1["n_ops"] == 1
    assert d0["op_s"] == pytest.approx(600e-9)
    assert d0["collective_s"] == pytest.approx(100e-9)
    # the fullest device by op time is device 0
    top = dict(red["top_ops"])
    assert list(top)[:2] == ["fusion.1", "convolution.4"]
    assert top["fusion.1"] == pytest.approx(200e-9)
    assert top["all-reduce-start.3"] == pytest.approx(100e-9)
    # gaps of device 0: [150,200) [350,700) [800,1050); the longest first,
    # named by the innermost host event over its middle
    names = [g[0] for g in red["idle_gaps"]]
    lengths = [g[1] for g in red["idle_gaps"]]
    assert lengths == pytest.approx([350e-9, 250e-9, 50e-9])
    assert names == ["ExecuteHelper", "PjitFunction(_episode)",
                     "ExecuteHelper"]


def test_top_ops_by_self_time_and_short_names():
    """A while loop's time less its body's, and HLO text cut to the name
    and result type."""
    loop = ("%while.7 = (s32[], f32[64,25]{0,1:T(8,128)}) while((s32[], "
            "f32[64,25]) %tuple.1), condition=%cond.2, body=%body.3")
    body = ("%fusion.9 = bf16[576,224,224,3]{0,2,3,1:T(8,128)(2,1)} "
            "fusion(f32[64,22,2]{0,1,2:T(8,128)} %p.1), kind=kLoop")
    ops = [[loop, 0, 1000], [body, 100, 300], ["copy.2", 500, 100],
           [body, 1200, 300]]
    red = devtrace.reduce_events([ops], [["bench/call", 0, 1500]])
    top = dict(red["top_ops"])
    assert set(top) == {"%fusion.9 bf16[576,224,224,3]", "%while.7 (...)",
                        "copy.2"}
    assert top["%fusion.9 bf16[576,224,224,3]"] == pytest.approx(600e-9)
    assert top["%while.7 (...)"] == pytest.approx(600e-9)
    assert top["copy.2"] == pytest.approx(100e-9)
    assert red["devices"][0]["op_s"] == pytest.approx(1300e-9)
    assert red["busy_s"] == pytest.approx(1300e-9)


def test_trace_reduction_without_device_ops():
    red = devtrace.reduce_events([], TRACE["host"])
    assert red["busy_s"] == 0 and red["devices"] == []
    assert red["top_ops"] == [] and red["idle_gaps"] == []


def test_union_merges_overlaps():
    assert devtrace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]


# ---------------------------------------------------------------------------
# operations and peaks
# ---------------------------------------------------------------------------

def test_detector_flops_at_full_width_by_hand():
    """The count through the ViT backbone (`backbones/vit.py`, which the
    configuration names by leaving `backbone` out) and the shared neck
    and heads."""
    d = json.loads((BENCH / "configs" / "madeye-approx.json").read_text())
    assert "backbone" not in d
    # DeiT-Ti: 196 patches + CLS, d 192, MLP 768, 12 blocks; FPN 128,
    # 2 classes
    patch_embed = 2 * 196 * 768 * 192
    block = (2 * 197 * 192 * 768          # q, k, v and out projections
             + 4 * 197 * 197 * 192        # scores and mixing
             + 4 * 197 * 192 * 768)       # MLP up and down
    neck = 2 * 196 * 192 * 128 + 2 * 196 * 9 * 128 * 128
    heads = 2 * 196 * 9 * 128 * 7
    hand = patch_embed + 12 * block + neck + heads
    assert hand == 2_577_580_032
    assert flops.detector_window_flops(d) == hand
    assert flops.detector_window_flops(dict(d, backbone="vit")) == hand
    assert flops.detector_step_flops(d, 64, 18) == hand * 64 * 18


def test_peak_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    for kind in ("cpu", "TPU v4", "TPU v5"):
        with pytest.raises(KeyError):
            peaks.peak(kind, "bf16_flops")


# ---------------------------------------------------------------------------
# the platform gate
# ---------------------------------------------------------------------------

def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "approx-k18-f15", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_run_refuses_a_tree_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "approx-k18-f15", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
