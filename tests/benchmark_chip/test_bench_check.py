"""`correct` of the chip benchmark, driven through a whole run on the CPU at
a size a test can hold: the program passes, and the control (the
reference in bfloat16 in the program's place, or its detector alone) and
each planted fault (a chosen answer altered, half the fleet left out,
the search exploring the wrong cells) come out not correct. The platform
gate of run.py is skipped; everything after it runs as on the chip.

The test detector is a one-block ViT on 32 px crops; its operating point
is set from its weights as in the benchmark's runs. At 3 fps each camera
explores several cells a step, so counts are compared between cells and
not only against zero."""
from __future__ import annotations

import time

import pytest

from benchmarks.chip import harness
from benchmarks.chip.reference import controller, faults

SEED = 2 ** 31 + 4242
TINY_DETECTOR = {"img_res": 32, "patch": 16, "n_layers": 1, "d_model": 32,
                 "n_heads": 2, "d_ff": 64, "n_classes": 2, "max_boxes": 4,
                 "fpn_dim": 16}


def small_run(cameras: int, steps: int, fps: float):
    bench = harness.load_benchmark()
    cell = {"name": "approx-small", "config": "madeye-approx",
            "traffic": "k18-f15", "chips": 1}
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    config["cameras_per_chip"] = cameras
    config["detector"] = dict(TINY_DETECTOR)
    traffic.update(n_steps=steps, fps=fps)
    return harness.run_cell(bench, cell, seed=SEED, seconds=0.0,
                            trace=False, t_start=time.perf_counter(),
                            log=lambda _: None, config=config,
                            traffic=traffic)


def correct(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)


@pytest.fixture(scope="module", params=[15.0, 3.0], ids=["f15", "f3"])
def small(request):
    return small_run(cameras=4, steps=6, fps=request.param)


def test_program_is_correct(small):
    run, res = small
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 4 * 6 and res["failed"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in harness.load_benchmark()["end_to_end"]}


def test_reference_controller_follows_the_program(small):
    """Teacher-forced, the plain controller re-derives every decision."""
    run, _ = small
    ref = harness.load_module("reference", run.config["reference"])
    r = ref.readings(run)
    assert r["controller_mismatch"] == 0.0 and r["select_mismatch"] == 0.0


@pytest.fixture(scope="module")
def small_f3():
    return small_run(cameras=4, steps=6, fps=3.0)


@pytest.mark.parametrize("control", ["reference", "detector"])
def test_control_is_not_correct(small_f3, control):
    run, _ = small_f3
    ref = harness.load_module("reference", run.config["reference"])
    low = ref.readings(run, control=control)
    limits = run.config["limits"]
    assert any(low[k] > limits[k] for k in limits), low


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(small, fault):
    run, _ = small
    ref = harness.load_module("reference", run.config["reference"])
    clean = run.outputs
    try:
        run.outputs = dict(clean, out=faults.FAULTS[fault](clean["out"]))
        assert not correct(ref.check(run))
    finally:
        run.outputs = clean


def test_path_walk_visits_nearest_first():
    """The rotation path: a preorder walk of the spanning tree restricted
    to the shape, children nearest first, ties to the lower cell id."""
    geo = controller.geometry({"grid": {}})
    mask = controller.onehot(6, 25) | controller.onehot(7, 25) \
        | controller.onehot(11, 25) | controller.onehot(12, 25)
    order, deg = controller.walk(geo, mask, 12)
    assert order[0] == 12 and sorted(order) == [6, 7, 11, 12]
    assert deg == sum(geo["dist"][a, b] for a, b in zip([12] + order[:-1],
                                                        order))
    # a start outside the shape walks from the member nearest it
    order, _ = controller.walk(geo, mask, 0)
    assert order[0] == 6


def test_budget_plan_at_15_and_3_fps():
    """At 15 fps a 24 Mbps / 20 ms link leaves one frame and one cell a
    step. At 3 fps: risk 0.15 + 0.25 allows 3 frames, which leave
    0.333 - 0.045 - 0.030 = 0.258 s, room for 1 + 3 more cells."""
    c = harness.load_json("configs", "madeye-approx")["controller"]
    f32 = controller.f32
    k, _, cells = controller.plan(c, 1 / 15, f32(24.0), f32(0.02),
                                  f32(0.85), f32(0.25))
    assert (k, cells) == (1, 1)
    k, _, cells = controller.plan(c, 1 / 3, f32(24.0), f32(0.02),
                                  f32(0.85), f32(0.25))
    assert (k, cells) == (3, 4)
