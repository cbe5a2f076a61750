"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.box_iou.ops import box_iou, match_boxes, nms_mask
from repro.kernels.box_iou.ref import box_iou_ref
from repro.kernels.cell_rasterize.ops import cell_rasterize, window_arrays
from repro.kernels.crop_patchify.ops import crop_patchify
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.frame_delta.ops import apply_delta, frame_delta
from repro.kernels.frame_delta.ref import frame_delta_ref
from repro.kernels.neighbor_score.ops import geometry_arrays, neighbor_scores
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Sk, Hq, Hkv, D, causal, dtype)
    (1, 64, 64, 2, 2, 32, False, jnp.float32),
    (2, 128, 128, 4, 2, 64, True, jnp.float32),
    (1, 100, 100, 2, 1, 24, True, jnp.float32),     # ragged + MQA
    (1, 1, 96, 4, 4, 16, False, jnp.float32),       # decode shape
    (2, 72, 136, 3, 1, 48, False, jnp.float32),     # Sq != Sk
    (1, 64, 64, 2, 2, 32, False, jnp.bfloat16),
    (1, 256, 256, 2, 2, 128, True, jnp.float32),    # full MXU tile dims
]


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=[str(c) for c in FLASH_CASES])
def test_flash_attention_matches_ref(case):
    B, Sq, Sk, Hq, Hkv, D, causal, dtype = case
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, hash(case) % 997), 3)
    q = jax.random.normal(kq, (B, Sq, Hq, D), dtype)
    k = jax.random.normal(kk, (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, Sk, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)

    g = Hq // Hkv
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), g, 1)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), g, 1)
    ref = attention_ref(q.transpose(0, 2, 1, 3), kr, vr,
                        causal=causal).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_q_offset():
    """Decode with a cache: q_offset shifts the causal mask."""
    q = jax.random.normal(KEY, (1, 8, 2, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 32, 2, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 32, 2, 16))
    out = flash_attention(q, k, v, causal=True, q_offset=24,
                          block_q=8, block_k=8)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True,
                        q_offset=24).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# box IoU + NMS + matching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(8, 8), (37, 13), (128, 256), (5, 300),
                                 (1, 1)])
def test_box_iou_matches_ref(n, m):
    ka, kb = jax.random.split(jax.random.fold_in(KEY, n * 1000 + m))
    a = jnp.abs(jax.random.normal(ka, (n, 4))) * 0.3 + 0.05
    b = jnp.abs(jax.random.normal(kb, (m, 4))) * 0.3 + 0.05
    np.testing.assert_allclose(np.asarray(box_iou(a, b)),
                               np.asarray(box_iou_ref(a, b)), atol=1e-6)


def test_iou_identity():
    boxes = jnp.abs(jax.random.normal(KEY, (16, 4))) * 0.2 + 0.1
    iou = box_iou(boxes, boxes)
    np.testing.assert_allclose(np.asarray(jnp.diag(iou)), 1.0, atol=1e-5)


def test_nms_suppresses_overlaps():
    boxes = jnp.array([[0.5, 0.5, 0.2, 0.2], [0.51, 0.5, 0.2, 0.2],
                       [0.9, 0.9, 0.1, 0.1]])
    keep = nms_mask(boxes, jnp.array([0.9, 0.8, 0.7]), jnp.ones(3, bool))
    assert bool(keep[0]) and not bool(keep[1]) and bool(keep[2])


def test_nms_respects_validity():
    boxes = jnp.array([[0.5, 0.5, 0.2, 0.2], [0.9, 0.9, 0.1, 0.1]])
    keep = nms_mask(boxes, jnp.array([0.9, 0.8]),
                    jnp.array([False, True]))
    assert not bool(keep[0]) and bool(keep[1])


def test_match_boxes_one_to_one():
    pred = jnp.array([[0.5, 0.5, 0.2, 0.2], [0.5, 0.5, 0.2, 0.2]])
    gt = jnp.array([[0.5, 0.5, 0.2, 0.2]])
    tp, m = match_boxes(pred, gt, jnp.ones(1, bool))
    # only the first (higher-ranked) pred claims the single GT
    assert bool(tp[0]) and not bool(tp[1])
    assert int(m[0]) == 0 and int(m[1]) == -1


# ---------------------------------------------------------------------------
# neighbor score (fleet shape-search inner loop)
# ---------------------------------------------------------------------------

def _neighbor_inputs(b, seed=0):
    from repro.core.grid import DEFAULT_GRID
    rng = np.random.default_rng(seed)
    n = DEFAULT_GRID.n_cells
    mask = rng.random((b, n)) < 0.3
    mask[:, 0] |= ~mask.any(1)                  # at least one member
    has = rng.random((b, n)) < 0.7
    cents = rng.uniform(0.0, [150.0, 75.0], (b, n, 2)).astype(np.float32)
    heads = np.array([rng.choice(np.flatnonzero(m)) for m in mask],
                     np.int32)
    geo = geometry_arrays(DEFAULT_GRID)
    args = (jnp.asarray(mask), jnp.asarray(has), jnp.asarray(cents),
            jnp.asarray(heads), jnp.asarray(geo["d_center"]),
            jnp.asarray(geo["overlap"]), jnp.asarray(geo["cell_x"]),
            jnp.asarray(geo["cell_y"]), jnp.asarray(geo["neighbor8"]))
    return mask, has, cents, heads, args


@pytest.mark.parametrize("b", [1, 7, 64, 130])
def test_neighbor_score_kernel_matches_ref(b):
    """Pallas kernel path (padded to lanes) == fused-jnp reference path."""
    _, _, _, _, args = _neighbor_inputs(b, seed=b)
    s_ref, cand_ref = neighbor_scores(*args, use_kernel=False)
    s_ker, cand_ker = neighbor_scores(*args, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(cand_ref),
                                  np.asarray(cand_ker))
    np.testing.assert_allclose(np.asarray(s_ker), np.asarray(s_ref),
                               atol=1e-5, rtol=1e-5)


def test_neighbor_score_matches_core_neighbor():
    """Both dispatch paths reproduce core/neighbor.score_candidates."""
    from repro.core import neighbor as nb
    from repro.core.grid import DEFAULT_GRID
    mask, has, cents, heads, args = _neighbor_inputs(16, seed=3)
    for use_kernel in (False, True):
        s, cand = neighbor_scores(*args, use_kernel=use_kernel)
        s, cand = np.asarray(s), np.asarray(cand)
        for b in range(mask.shape[0]):
            cands_np, scores_np = nb.score_candidates(
                DEFAULT_GRID, mask[b], int(heads[b]), cents[b], has[b])
            assert set(cands_np.tolist()) == \
                set(np.flatnonzero(cand[b]).tolist())
            for c, sc in zip(cands_np, scores_np):
                np.testing.assert_allclose(s[b, c], sc, atol=1e-5)


# ---------------------------------------------------------------------------
# cell rasterize (scene substrate boxes -> cells x zooms)
# ---------------------------------------------------------------------------

def _rasterize_inputs(b, m, p, seed=0):
    from repro.core.grid import DEFAULT_GRID
    rng = np.random.default_rng(seed)
    ox = rng.uniform(-10, 160, (b, m)).astype(np.float32)
    oy = rng.uniform(-10, 85, (b, m)).astype(np.float32)
    ow = rng.uniform(0, 9, (b, m)).astype(np.float32)
    oh = rng.uniform(0, 9, (b, m)).astype(np.float32)
    ow[:, -2:] = 0.0                                # "disabled" slots
    draw = rng.uniform(0, 1.2, (b, p, m)).astype(np.float32)
    draw[:, :, -1] = 2.0                            # never-detect mask
    a0 = rng.uniform(0.02, 0.1, p).astype(np.float32)
    a1 = (a0 + rng.uniform(0.05, 0.2, p)).astype(np.float32)
    win = jnp.asarray(window_arrays(DEFAULT_GRID))
    return (ox, oy, ow, oh, draw, a0, a1), \
        tuple(jnp.asarray(x) for x in (ox, oy, ow, oh, draw, a0, a1)) \
        + (win,)


@pytest.mark.parametrize("b,m,p", [(1, 22, 4), (7, 22, 5), (16, 40, 8),
                                   (11, 3, 1)])
@pytest.mark.parametrize("moment_frac", [None, 0.5])
def test_cell_rasterize_kernel_matches_ref(b, m, p, moment_frac):
    """Pallas kernel path (padded to tiles) == pure-jnp reference path,
    including n_moment < P (the stacked student+teacher layout
    observe_all_cells uses, where only leading channels feed geometry)."""
    n_moment = None if moment_frac is None else max(1, int(p * moment_frac))
    _, args = _rasterize_inputs(b, m, p, seed=b * 100 + m)
    ref = cell_rasterize(*args, use_kernel=False, n_moment=n_moment)
    ker = cell_rasterize(*args, use_kernel=True, n_moment=n_moment)
    for name, r, k in zip(("cnt", "area", "wcx", "wcy", "wc2", "ext"),
                          ref, ker):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    if n_moment is not None and n_moment < p:
        # gating matters: full-moment geometry must differ somewhere
        full = cell_rasterize(*args, use_kernel=False)
        assert any(not np.allclose(np.asarray(a), np.asarray(c))
                   for a, c in zip(ref[2:], full[2:]))


def test_cell_rasterize_ref_matches_gt_boxes():
    """The reference visibility/clipping rule reproduces data/render
    .gt_boxes counts and normalized areas for an always-detect teacher."""
    from repro.core.grid import DEFAULT_GRID
    from repro.data.render import gt_boxes

    (ox, oy, ow, oh, _, _, _), _ = _rasterize_inputs(3, 22, 1, seed=5)
    # always detect any visible object: draw = -1 < clip(...) >= 0 needs
    # apparent > a0, so use a0 = -1 (every visible box passes the ramp)
    draw = np.full((3, 1, 22), -1.0, np.float32)
    a0 = np.array([-1.0], np.float32)
    a1 = np.array([-0.5], np.float32)
    win = jnp.asarray(window_arrays(DEFAULT_GRID))
    cnt, area, _, _, _, _ = cell_rasterize(
        *(jnp.asarray(x) for x in (ox, oy, ow, oh, draw, a0, a1)), win)
    cnt, area = np.asarray(cnt), np.asarray(area)
    zooms = (1.0, 2.0, 3.0)
    for b in range(3):
        snap = {"pos": np.stack([ox[b], oy[b]], -1),
                "size": np.stack([ow[b], oh[b]], -1),
                "kind": np.zeros(22, int), "oid": np.arange(22), "t": 0}
        for cell in (0, 7, 12, 24):
            for zi, z in enumerate(zooms):
                gt = gt_boxes(snap, DEFAULT_GRID, cell, z)
                c = cell * len(zooms) + zi
                assert cnt[b, 0, c] == len(gt["boxes"]), (b, cell, zi)
                np.testing.assert_allclose(
                    area[b, 0, c],
                    float((gt["boxes"][:, 2] * gt["boxes"][:, 3]).sum()),
                    atol=1e-4)


# ---------------------------------------------------------------------------
# crop patchify (fused rasterize -> ViT patch-embed, detector fast path)
# ---------------------------------------------------------------------------

def _patchify_inputs(f, m, k, d, seed=0, *, shared=False, with_noise=True,
                     patch=16):
    """Random scene boxes + per-camera window subsets + patch-embed
    params, shaped like the detector provider's fast path."""
    from repro.core.grid import DEFAULT_GRID
    from repro.models.layers import conv_init

    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.uniform([0, 0], [150, 75], (f, m, 2)), jnp.float32)
    size = jnp.asarray(rng.uniform(1.5, 9.0, (f, m, 2)), jnp.float32)
    size = size.at[:, -2:].set(0.0)                 # disabled slots
    kind = jnp.asarray(rng.integers(0, 2, m), jnp.int32)
    oid = jnp.asarray(rng.integers(0, 4000, (f, m)), jnp.int32)
    wins_all = jnp.asarray(window_arrays(DEFAULT_GRID))
    if shared:
        wins = wins_all[: k]
    else:
        widx = np.stack([rng.choice(wins_all.shape[0], k, replace=False)
                         for _ in range(f)])
        wins = wins_all[jnp.asarray(widx)]
    pe = conv_init(jax.random.fold_in(KEY, seed), patch, patch, 3, d)
    noise = (0.05 * jax.random.normal(jax.random.fold_in(KEY, seed + 1),
                                      (f, 64, 64, 3))
             if with_noise else None)
    return pos, size, kind, oid, wins, pe, noise


def _render_embed(pos, size, kind, oid, wins, pe, noise, *, operands=None,
                  patch=16):
    """render_fleet_crops + the conv patch-embed at HIGHEST precision,
    with the crops and weights first rounded to `operands` if given."""
    from repro.models.layers import conv2d
    from repro.scene_jax.render import render_fleet_crops

    crops = render_fleet_crops(pos, size, kind, oid, wins, res=64,
                               noise=noise)
    f, k = crops.shape[:2]
    crops, w = crops.reshape(f * k, 64, 64, 3), pe["w"]
    if operands is not None:
        crops = crops.astype(operands).astype(jnp.float32)
        w = w.astype(operands).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = conv2d(dict(pe, w=w), crops, stride=patch, padding="VALID")
    return x.reshape(f, k, -1, x.shape[-1])


_CASES = [(1, 6, 3, 8, False), (3, 22, 5, 24, False), (2, 22, 4, 16, True)]
# patch 16 (the ViT's; the ids these cases always had) and patch 4 at
# Swin-T's width 96, where the kernel paints 8 patches a plane row
PATCHIFY_CASES = (
    [pytest.param(16, *c, id="-".join(map(str, c))) for c in _CASES]
    + [pytest.param(4, f, m, k, 96, sh, id=f"p4-{f}-{m}-{k}-96-{sh}")
       for f, m, k, _, sh in _CASES])


@pytest.mark.parametrize("patch,f,m,k,d,shared", PATCHIFY_CASES)
def test_crop_patchify_kernel_matches_ref(patch, f, m, k, d, shared):
    """Pallas kernel (rasterize fused into the patch contraction, pixels
    never materialized) at the default matmul precision == render + conv
    patch-embed with the same arithmetic (pixels and weights rounded to
    bfloat16, products summed in float32), within fp32 tolerance —
    per-camera and fleet-shared window sets, at patch 16 and 4."""
    pos, size, kind, oid, wins, pe, noise = _patchify_inputs(
        f, m, k, d, seed=f * 100 + k, shared=shared, patch=patch)
    ref = _render_embed(pos, size, kind, oid, wins, pe, noise,
                        operands=jnp.bfloat16, patch=patch)
    ker = crop_patchify(pos, size, kind, oid, wins, pe, patch=patch,
                        res=64, noise=noise, use_kernel=True)
    assert ref.shape == (f, k, (64 // patch) ** 2, d)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("patch,f,m,k,d,shared", PATCHIFY_CASES)
def test_crop_patchify_kernel_matches_ref_at_highest(patch, f, m, k, d,
                                                     shared):
    """Under `jax.default_matmul_precision("highest")` the kernel
    contracts float32 pixels and weights at HIGHEST, as the reference
    path's conv does: both paths agree within fp32 tolerance."""
    pos, size, kind, oid, wins, pe, noise = _patchify_inputs(
        f, m, k, d, seed=f * 100 + k, shared=shared, patch=patch)
    with jax.default_matmul_precision("highest"):
        ref = crop_patchify(pos, size, kind, oid, wins, pe, patch=patch,
                            res=64, noise=noise, use_kernel=False)
        ker = crop_patchify(pos, size, kind, oid, wins, pe, patch=patch,
                            res=64, noise=noise, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(
        _render_embed(pos, size, kind, oid, wins, pe, noise, patch=patch)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_crop_patchify_packs_small_patches_only(monkeypatch):
    """Patches share a plane row only where one leaves lanes empty: at
    patch 16 (256 pixels) the kernel gets the [gg, p*p] planes it always
    got; at patch 4 eight 16-pixel patches fill 128 lanes."""
    import repro.kernels.crop_patchify.ops as ops_mod

    assert ops_mod.pack_factor(196, 256) == 1
    assert ops_mod.pack_factor(16, 256) == 1
    assert ops_mod.pack_factor(3136, 16) == 8
    assert ops_mod.pack_factor(256, 16) == 8
    assert ops_mod.pack_factor(784, 64) == 2
    assert ops_mod.pack_factor(9, 16) == 3

    seen = []
    batch = ops_mod.crop_patchify_batch

    def spy(rows, *a, **kw):
        seen.append(rows.shape)
        return batch(rows, *a, **kw)

    monkeypatch.setattr(ops_mod, "crop_patchify_batch", spy)
    for patch, d in ((16, 12), (4, 96)):
        pos, size, kind, oid, wins, pe, noise = _patchify_inputs(
            2, 10, 3, d, seed=5, patch=patch)
        got = crop_patchify(pos, size, kind, oid, wins, pe, patch=patch,
                            res=64, noise=noise, use_kernel=True)
        assert got.shape == (2, 3, (64 // patch) ** 2, d)
    assert seen == [(16, 256), (32, 128)]


def test_crop_patchify_ref_is_render_plus_embed():
    """The reference path IS the unfused pixel pipeline: rendering the
    same windows and running the backbone conv embed (vit.vit_embed
    layout) reproduces it bit-for-bit — the contract that makes the
    fast path's exhaustive mode decision-identical to the pre-shortlist
    detector provider."""
    from repro.scene_jax.render import render_fleet_crops

    pos, size, kind, oid, wins, pe, noise = _patchify_inputs(
        2, 10, 4, 12, seed=7)
    got = crop_patchify(pos, size, kind, oid, wins, pe, patch=16, res=64,
                        noise=noise, use_kernel=False)
    from repro.models.layers import conv2d

    crops = render_fleet_crops(pos, size, kind, oid, wins, res=64,
                               noise=noise)
    want = conv2d(pe, crops.reshape(8, 64, 64, 3), stride=16,
                  padding="VALID").reshape(2, 4, 16, 12)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_crop_patchify_default_is_the_reference_on_cpu():
    """Left to the platform, crop_patchify lowered for the CPU is the jnp
    reference: bit-identical to render_fleet_crops + conv patch-embed,
    and no kernel call in the lowered program."""
    from repro.models.layers import conv2d
    from repro.scene_jax.render import render_fleet_crops

    pos, size, kind, oid, wins, pe, noise = _patchify_inputs(
        2, 10, 4, 12, seed=7)

    def default(*a):
        return crop_patchify(*a, noise=noise, patch=16, res=64)

    args = (pos, size, kind, oid, wins, pe)
    assert "tpu_custom_call" not in jax.jit(default).lower(*args).as_text()
    crops = render_fleet_crops(pos, size, kind, oid, wins, res=64,
                               noise=noise)
    want = conv2d(pe, crops.reshape(8, 64, 64, 3), stride=16,
                  padding="VALID").reshape(2, 4, 16, 12)
    np.testing.assert_array_equal(np.asarray(default(*args)),
                                  np.asarray(want))


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 16, 64), (2, 100, 256), (7, 33),
                                   (1, 1, 8), (512, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (shape[-1],)) + 1.0
    out = rmsnorm(x, w)
    ref = rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# frame delta
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hw", [(128, 128), (224, 224), (64, 256)])
def test_frame_delta_matches_ref(hw):
    H, W = hw
    cur = jax.random.uniform(KEY, (H, W, 3))
    prev = jnp.clip(cur.at[: H // 2, : W // 2].add(0.3), 0, 1)
    dq, ch, byt = frame_delta(cur, prev, tile_h=16, tile_w=128)
    ph, pw = (-H) % 16, (-W) % 128
    curp = jnp.pad(cur, ((0, ph), (0, pw), (0, 0)))
    prevp = jnp.pad(prev, ((0, ph), (0, pw), (0, 0)))
    dq_r, ch_r = frame_delta_ref(curp, prevp, tile_h=16, tile_w=128)
    np.testing.assert_array_equal(np.asarray(dq), np.asarray(dq_r[:H, :W]))
    np.testing.assert_array_equal(np.asarray(ch), np.asarray(ch_r))
    assert int(byt) > 0


def test_frame_delta_reconstruction():
    cur = jax.random.uniform(KEY, (64, 128, 3))
    prev = jnp.clip(cur + 0.2, 0, 1)       # every tile changes
    dq, ch, _ = frame_delta(cur, prev, tile_h=16, tile_w=128)
    rec = apply_delta(prev, dq)
    assert float(jnp.max(jnp.abs(rec - cur))) < 1.0 / 127 + 1e-3


def test_frame_delta_identical_frames_send_nothing():
    cur = jax.random.uniform(KEY, (64, 128, 3))
    dq, ch, byt = frame_delta(cur, cur)
    assert int(ch.sum()) == 0
    assert not bool(jnp.any(dq != 0))
