"""The Swin-T configuration: the program against `backbones/swin.py`.

On the CPU at a smoke size, on seeded random weights made by the
benchmark (`reference/weights.py`), at HIGHEST precision: the program's
`swin_features_tokens` and the whole detector's scores agree with the
plain reference (shifted and unshifted blocks, the patch merges, the
merge of the last two stages); a reference with the shift or the
relative bias left out does not. The concatenated map through the
neck's 1 x 1 lateral is FPN's top-down sum; the FLOP count is the hand
count; the weights are pinned by digest. A small cell run through the
harness is correct, and its bfloat16 control is not."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops, harness
from benchmarks.chip.reference import vision, weights

from repro.configs.base import DetectorConfig
from repro.models import swin
from repro.models.detector import detector_forward_tokens, detector_init
from repro.models.layers import conv2d

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmarks" / "chip"
CONFIG = json.loads(
    (BENCH / "configs" / "madeye-approx-swin-t.json").read_text())
# 64 px, patch 4, C 16, maps 16/8/4/2 at window 2: stage 4's map is one
# window, so it takes no shift, as Swin-T's 7 x 7 stage 4 does
SMOKE = {"img_res": 64, "patch": 4, "n_layers": 8, "d_model": 16,
         "n_heads": 1, "d_ff": 64, "n_classes": 2, "max_boxes": 8,
         "fpn_dim": 32, "backbone": "swin", "depths": [2, 2, 2, 2],
         "window": 2}
SIZES = {"smoke": SMOKE, "cell": CONFIG["detector"]}
SEEDS = (7, 2_000_000_011)
# sha256 of every leaf (path, dtype, shape, bytes), on the CPU
WEIGHTS = {
    ("smoke", 7): "c7c224f65ce9560ce929f64888f77dc3"
                  "929cea9d0669e51506fe1e02c1b4390a",
    ("smoke", 2_000_000_011): "b6feed42d7b37c00923d0aa2918d2554"
                              "02a0cc769d7d0f87bb23a7d93cd0812b",
    ("cell", 7): "7a71fd9dbefbf52e10af03894f6e0210"
                 "57606ffc2dc0985f4bf42ef38dee94ef",
    ("cell", 2_000_000_011): "052e3611d9aab5dd6d194f6693253dba"
                             "a7df806fe3e59ba03c88ca037d2d2663",
}
FEATURE_TOL = 2e-5          # abs, on maps of magnitude ~3
SCORE_TOL = 1e-6


def digest(tree) -> str:
    import hashlib

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def config(sizes: dict) -> dict:
    return dict(CONFIG, detector=sizes)


def program_cfg(sizes: dict) -> DetectorConfig:
    return DetectorConfig(name="swin-test", **sizes)


@pytest.fixture(scope="module")
def smoke():
    """Benchmark-made weights, two crops and their patch tokens."""
    params = weights.init(config(SMOKE), SEEDS[0])
    res, p, c = SMOKE["img_res"], SMOKE["patch"], SMOKE["d_model"]
    crops = jax.random.uniform(jax.random.PRNGKey(3), (2, 3, res, res))
    with jax.default_matmul_precision("highest"):
        tokens = conv2d(params["backbone"]["swin"]["patch_embed"],
                        crops.transpose(0, 2, 3, 1), stride=p,
                        padding="VALID").reshape(2, -1, c)
    return params, crops, tokens


def reference_features(params, crops, bb=None):
    bb = bb or harness.load_backbone("swin")
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda c: bb.features(
            params["backbone"], c, SMOKE, "highest"))(crops)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_program_and_reference_share_the_params_layout():
    """The harness's weights are the program's params: same tree, same
    shapes as `detector_init` makes, at both sizes."""
    for sizes in SIZES.values():
        made = jax.eval_shape(lambda s=sizes: weights.init(config(s), 1))
        prog = jax.eval_shape(lambda k, s=sizes: detector_init(
            k, program_cfg(s)), jax.random.PRNGKey(0))
        assert jax.tree.structure(made) == jax.tree.structure(prog)
        assert all(jax.tree.leaves(jax.tree.map(
            lambda a, b: a.shape == b.shape, made, prog)))
        rel = made["backbone"]["swin"]["stages"][0]["blocks"]["rel_bias"]
        assert rel.shape[1] == (2 * sizes["window"] - 1) ** 2


def test_features_match_the_reference(smoke):
    params, crops, tokens = smoke
    want = reference_features(params, crops)
    with jax.default_matmul_precision("highest"):
        got = swin.swin_features_tokens(params["backbone"]["swin"],
                                        program_cfg(SMOKE), tokens)
    assert got.shape == want.shape == (2, 4, 4, 16 * 4 + 16 * 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=FEATURE_TOL, rtol=0)


@pytest.mark.parametrize("odd", [False, True], ids=["even", "odd"])
def test_block_matches_the_reference(smoke, odd):
    """One block of stage 1 (16 x 16 map, windows of 2): the even block
    attends in plain windows, the odd one in windows shifted by one with
    the region mask."""
    params, _, tokens = smoke
    bb = harness.load_backbone("swin")
    sw = params["backbone"]["swin"]
    x = tokens[0].reshape(16, 16, 16)
    p = jax.tree.map(lambda a: a[int(odd)], sw["stages"][0]["blocks"])
    with jax.default_matmul_precision("highest"):
        want = bb._block(p, x, 1, 2, 2, 1 if odd else 0, "highest")
        got = swin.swin_block(p, x[None], n_heads=1, window=2, odd=odd)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=FEATURE_TOL, rtol=0)


@pytest.mark.parametrize("broken", ["no_shift", "no_bias"])
def test_a_reference_without_the_shift_or_bias_disagrees(smoke, broken,
                                                         monkeypatch):
    """The comparison sees the shift and the relative bias: a reference
    that leaves either out is far outside the tolerance."""
    params, crops, tokens = smoke
    bb = harness.load_backbone("swin")
    block = bb._block

    def without(p, x, heads, m, table, shift, pr):
        if broken == "no_shift":
            shift = 0
        else:
            p = dict(p, rel_bias=jnp.zeros_like(p["rel_bias"]))
        return block(p, x, heads, m, table, shift, pr)

    monkeypatch.setattr(bb, "_block", without)
    bad = reference_features(params, crops, bb)
    with jax.default_matmul_precision("highest"):
        got = swin.swin_features_tokens(params["backbone"]["swin"],
                                        program_cfg(SMOKE), tokens)
    assert float(jnp.abs(got - bad).max()) > 100 * FEATURE_TOL


def test_detector_scores_match_the_reference(smoke):
    """The whole detector: the program's token forward against
    `vision.detect` (the reference backbone, then the shared neck, heads
    and decode), kept boxes best first."""
    params, crops, tokens = smoke
    bb = harness.load_backbone("swin")
    k = SMOKE["max_boxes"]
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda c: vision.detect(
            params, c, bb, SMOKE, "highest"))(crops)
        got = detector_forward_tokens(params, program_cfg(SMOKE), tokens)
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want[0][:, :k]),
                               atol=SCORE_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(got.boxes),
                               np.asarray(want[1][:, :k]),
                               atol=SCORE_TOL, rtol=0)


def test_concatenated_map_through_the_lateral_is_the_fpn_sum(smoke):
    """lateral(concat(C3, up(C4))) = lateral3(C3) + up(lateral4(C4)):
    the 1 x 1 conv is linear and commutes with nearest upsampling."""
    params, _, tokens = smoke
    with jax.default_matmul_precision("highest"):
        fmap = swin.swin_features_tokens(params["backbone"]["swin"],
                                         program_cfg(SMOKE), tokens)
        lat = params["backbone"]["neck"]["lateral"]
        fused = conv2d(lat, fmap)
        c3w = 4 * SMOKE["d_model"]
        c3, c4 = fmap[..., :c3w], fmap[..., c3w:][:, ::2, ::2]
        l3 = conv2d({"w": lat["w"][:, :, :c3w], "b": lat["b"]}, c3)
        l4 = conv2d({"w": lat["w"][:, :, c3w:]}, c4)
        fpn = l3 + jnp.repeat(jnp.repeat(l4, 2, axis=1), 2, axis=2)
    assert c4.shape[1:3] == (2, 2)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(fpn),
                               atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# FLOPs and weights
# ---------------------------------------------------------------------------

def test_window_flops_by_hand():
    """Swin-T over one 224 px window; a multiply-add counts 2."""
    bb = harness.load_backbone("swin")
    d = CONFIG["detector"]
    embed = 2 * 3136 * 48 * 96

    def stage(n, c, t, depth):
        # q, k, v, out projections; QK^T and AV; MLP at ratio 4
        return depth * (8 * n * c * c + 4 * n * t * c + 16 * n * c * c)

    blocks = (stage(3136, 96, 49, 2) + stage(784, 192, 49, 2)
              + stage(196, 384, 49, 6) + stage(49, 768, 49, 2))
    merges = sum(2 * n * 4 * c * 2 * c
                 for n, c in ((784, 96), (196, 192), (49, 384)))
    assert (stage(3136, 96, 49, 2), stage(784, 192, 49, 2),
            stage(196, 384, 49, 6), stage(49, 768, 49, 2)) == (
        1_505_280_000, 1_446_273_024, 4_250_308_608, 1_402_017_792)
    assert bb.window_flops(d) == embed + blocks + merges == 8_979_597_312
    assert bb.feature_shape(d) == (14, 14, 1152)
    neck = 2 * 196 * 1152 * 128 + 2 * 196 * 9 * 128 * 128
    heads = 2 * 196 * 9 * 128 * 7
    assert flops.detector_window_flops(CONFIG) == (
        8_979_597_312 + neck + heads)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("size", sorted(SIZES))
def test_weights_are_pinned(size, seed):
    params = weights.init(config(SIZES[size]), seed)
    assert digest(params) == WEIGHTS[(size, seed)]


# ---------------------------------------------------------------------------
# a small cell through the harness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    """Four cameras, six steps at 3 fps (several cells explored a step)
    on a three-stage Swin of 32 px crops."""
    tiny = {"img_res": 32, "patch": 4, "n_layers": 4, "d_model": 16,
            "n_heads": 1, "d_ff": 64, "n_classes": 2, "max_boxes": 4,
            "fpn_dim": 16, "backbone": "swin", "depths": [2, 1, 1],
            "window": 2}
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, "swin-t-k18-f15")
    cfg = dict(config(tiny), cameras_per_chip=4)
    traffic = dict(harness.load_json("traffic", cell["traffic"]),
                   n_steps=6, fps=3.0)
    return harness.run_cell(bench, cell, seed=2 ** 31 + 4242, seconds=0.0,
                            trace=False, t_start=time.perf_counter(),
                            log=lambda _: None, config=cfg,
                            traffic=traffic)


def test_small_cell_is_correct(small):
    run, res = small
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 4 * 6 and res["failed"] == 0


def test_small_cell_control_is_not_correct(small):
    run, _ = small
    ref = harness.load_module("reference", run.config["reference"])
    low = ref.readings(run, control="reference")
    limits = run.config["limits"]
    assert any(low[k] > limits[k] for k in limits), low
