"""The detector's backbone as a named part of the chip benchmark.

`backbones/vit.py` gives, bit for bit, the weights and the reference
forward that the benchmark had before backbones were named: the digests
below were taken from the code in which `reference/weights.py` built
the ViT tree and `reference/vision.detect` ran the ViT itself. A
backbone of another feature width, added as one file to a copy of the
benchmark, is what the weights, the reference forward and the FLOP
count use; an unknown name is refused."""
from __future__ import annotations

import hashlib
import json
import shutil
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops, harness
from benchmarks.chip.reference import detector, vision, weights

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmarks" / "chip"
CONFIG = json.loads((BENCH / "configs" / "madeye-approx.json").read_text())
SIZES = {
    "smoke": {"img_res": 32, "patch": 16, "n_layers": 1, "d_model": 32,
              "n_heads": 2, "d_ff": 64, "n_classes": 2, "max_boxes": 4,
              "fpn_dim": 16},
    "cell": CONFIG["detector"],
}
SEEDS = (7, 2_000_000_011)
CROP_KEY = 11
# sha256 of every leaf (path, dtype, shape, bytes), on the CPU
PARENT_WEIGHTS = {
    ("smoke", 7): "783688bcde144c2bb90532380defc62f"
                  "274e8d7a24a546aa95e5e75a5d969175",
    ("smoke", 2_000_000_011): "acbb43ccf0f8a03b8bbbf7356a980eb3"
                              "f107f610a967e2fbe848631412fc1734",
    ("cell", 7): "a7824dfe0b5bcef59d46caf6f03c774f"
                 "4aa6b43e4813a702695f4c6281264b27",
    ("cell", 2_000_000_011): "ece944be4185e9e3ed4d6e1222be939f"
                             "f53dd6dc5cfc2cb0b8f67e318252e57f",
}
# vision.detect of the seed-7 weights on a uniform crop from CROP_KEY,
# jitted (as the reference runs it) and op by op
PARENT_DETECT = {
    ("smoke", "jit"): "3861569c6db2ab31b4a724ef25fdc341"
                      "478db328836858861f543e288a88604c",
    ("smoke", "eager"): "98df7bd773adec2ba1151c9c14e39bea"
                        "c128e50d72ed5ecc217c6b0bc04ac6f6",
    ("cell", "jit"): "6d3c7cb7b9449a33b1c265f2b7fe7b2b"
                     "a6bf4c7a985d1acccbce25e32223b443",
    ("cell", "eager"): "34da791e41e7f3853e9742b25791de62"
                       "764f19f741820ef248f84cebb6ce111a",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the ViT backbone is the parent's, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("size", sorted(SIZES))
def test_vit_weights_match_the_parent(size, seed):
    """A configuration without a `backbone` key gets `backbones/vit.py`,
    whose keys are drawn in the parent's order."""
    params = weights.init({"detector": SIZES[size]}, seed)
    assert digest(params) == PARENT_WEIGHTS[(size, seed)]
    named = weights.init({"detector": SIZES[size], "backbone": "vit"}, seed)
    assert digest(named) == PARENT_WEIGHTS[(size, seed)]


@pytest.mark.parametrize("mode", ["jit", "eager"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_vit_forward_matches_the_parent(size, mode):
    sizes = SIZES[size]
    params = weights.init({"detector": sizes}, SEEDS[0])
    res = sizes["img_res"]
    crop = jax.random.uniform(jax.random.PRNGKey(CROP_KEY), (3, res, res),
                              jnp.float32)
    fwd = partial(vision.detect, backbone=harness.load_backbone(None),
                  sizes=sizes)
    out = (jax.jit(fwd) if mode == "jit" else fwd)(params, crop)
    assert digest(out) == PARENT_DETECT[(size, mode)]


def test_vit_feature_shape_is_the_patch_grid_at_d_model():
    vit = harness.load_backbone("vit")
    for sizes in SIZES.values():
        g = sizes["img_res"] // sizes["patch"]
        assert vit.feature_shape(sizes) == (g, g, sizes["d_model"])


# ---------------------------------------------------------------------------
# another backbone is one new file
# ---------------------------------------------------------------------------

@pytest.fixture
def patchwide(tmp_path, monkeypatch):
    """A copy of the benchmark with `backbones/patchwide.py` added and
    nothing else changed, as the harness's directory; and a smoke-size
    configuration that names it."""
    tree = tmp_path / "chip"
    shutil.copytree(BENCH, tree,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE / "fixture_backbone.py",
                tree / "backbones" / "patchwide.py")
    monkeypatch.setattr(harness, "HERE", tree)
    config = dict(CONFIG, backbone="patchwide", detector=SIZES["smoke"])
    return config


def test_fixture_backbone_makes_the_weights(patchwide):
    d = patchwide["detector"]
    params = weights.init(patchwide, SEEDS[1])
    assert set(params["backbone"]) == {"patchwide", "neck"}
    width = 2 * d["d_model"]
    assert params["backbone"]["patchwide"]["embed"]["w"].shape == (
        d["patch"], d["patch"], 3, width)
    assert params["backbone"]["neck"]["lateral"]["w"].shape == (
        1, 1, width, d["fpn_dim"])
    # the neck and heads draw their keys after the backbone's one
    vit = weights.init(dict(patchwide, backbone=None), SEEDS[1])
    assert not np.array_equal(params["heads"]["cls"]["w"],
                              vit["heads"]["cls"]["w"])


def test_fixture_backbone_runs_the_reference_forward(patchwide):
    """`detector.detections`, the reference's own path, renders crops and
    runs them through the backbone the configuration names: the weights
    hold no ViT, so no other backbone could run on them."""
    d = patchwide["detector"]
    traffic = harness.load_json("traffic", "k18-f15")
    traffic["n_steps"] = 2
    seeds = harness.draw_seeds(SEEDS[1], 2)
    run = harness.Run(cell={}, config=patchwide, traffic=traffic, chips=1,
                      seed=SEEDS[1], seeds=seeds,
                      weights=weights.init(patchwide, seeds["weight_seed"]))
    snap = detector.reference_world(run)["snap"]
    items = np.array([[0, 0, 0], [1, 1, 7], [1, 0, 40]])
    scores, boxes, probs = detector.detections(run, snap, items,
                                               jnp.float32)
    cells = (d["img_res"] // d["patch"]) ** 2
    assert scores.shape == (3, cells) and boxes.shape == (3, cells, 4)
    assert probs.shape == (3, cells, d["n_classes"])
    assert np.isfinite(scores).all() and np.isfinite(boxes).all()
    assert (np.diff(scores, axis=-1) <= 0).all()        # best first


def test_fixture_backbone_sets_the_flop_count(patchwide):
    d = patchwide["detector"]
    g, p, f = 2, 16, d["fpn_dim"]
    width = 2 * d["d_model"]
    hand = (2 * g * g * p * p * 3 * width            # patch embed
            + 2 * g * g * width * f                  # lateral 1x1
            + 2 * g * g * 9 * f * f                  # smooth 3x3
            + 2 * g * g * 9 * f * (d["n_classes"] + 5))
    assert flops.detector_window_flops(patchwide) == hand
    assert flops.detector_step_flops(patchwide, 64, 18) == hand * 64 * 18


@pytest.mark.parametrize("caller", ["harness", "weights", "flops"])
def test_unknown_backbone_is_refused(caller):
    config = dict(CONFIG, backbone="no-such-backbone",
                  detector=SIZES["smoke"])
    call = {"harness": lambda: harness.load_backbone(config["backbone"]),
            "weights": lambda: weights.init(config, 1),
            "flops": lambda: flops.detector_window_flops(config)}[caller]
    with pytest.raises(harness.BenchError,
                       match="backbones/no-such-backbone.py"):
        call()
