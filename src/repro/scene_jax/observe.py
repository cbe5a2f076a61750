"""Scene state -> per-(cell, zoom, pair) observations, fully on device.

`observe_all_cells` is the device-resident analogue of what the host
pipeline assembles from `gt_boxes` + `run_teacher` + `approx_observation`
when it materializes `EpisodeTables`: for every camera it produces the
approximation-model counts/areas per (cell, zoom, pair), the box-geometry
summaries the zoom controller reads (centroid / spread / extent / nbox),
and the oracle workload accuracy used as backend feedback.

Teacher model (deterministic, like serving.teachers but hash-native JAX):
detection probability is the same saturating ramp of apparent size with
per-(model, class) quirked thresholds and the same base+bucket flicker
mix; the uniform draw is an FNV-style integer hash of (object id, pair,
bucket), so detections flicker on the paper's timescale and are exactly
reproducible. The approximation model applies an extra per-(object, step)
miss on top (`miss_rate`). Two deliberate simplifications vs the host
teachers, pinned by the scene-vs-tables parity tests rather than the
numpy-substrate ones: no localization noise / false positives (geometry
is exact), and `spread` is the RMS box-center distance (one-pass moment)
instead of the mean distance.

Oracle accuracy: per query, relative accuracy of TEACHER counts across
orientations (binary -> any-detection; count/agg/detect -> count over the
per-step max; the detect task's recall x quality score reduces to the
count ratio here because identity recall is proportional to the count and
quality is 1 without localization noise).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.cell_rasterize.ops import cell_rasterize, window_arrays
from repro.obs.trace import staged
from repro.scene_jax.scene import SceneFleetParams, SceneSpec, SceneState, \
    kind_mask
from repro.serving.teachers import TEACHERS

_MISS_SALT = 0x4D155
_BASE_SALT = 0xBA5E


class TeacherArrays(NamedTuple):
    """Per-pair teacher response constants for one workload (device)."""
    a0: jnp.ndarray         # [P] quirked apparent-size floor
    a1: jnp.ndarray         # [P] quirked saturation size
    pmax: jnp.ndarray       # [P] plateau detection probability
    flicker: jnp.ndarray    # [P] bucket-hash mix weight
    cls: jnp.ndarray        # [P] object class (PERSON/CAR)
    salt: jnp.ndarray       # [P] stable per-pair hash salt


def teacher_arrays(pairs) -> TeacherArrays:
    """pairs: WorkloadSpec.pairs — ((model, obj), ...) in table order."""
    from repro.data.dataset import OBJ_IDS

    a0, a1, pmax, flick, cls, salt = [], [], [], [], [], []
    for model, obj in pairs:
        prof = TEACHERS[model]
        c = OBJ_IDS[obj]
        q = prof.class_quirk(c)
        a0.append(prof.a_min * q)
        a1.append(prof.a_sat * q)
        pmax.append(prof.p_max)
        flick.append(prof.flicker)
        cls.append(c)
        salt.append(_fnv_host(model, obj))
    return TeacherArrays(
        a0=jnp.asarray(a0, jnp.float32), a1=jnp.asarray(a1, jnp.float32),
        pmax=jnp.asarray(pmax, jnp.float32),
        flicker=jnp.asarray(flick, jnp.float32),
        cls=jnp.asarray(cls, jnp.int32),
        salt=jnp.asarray(salt, jnp.uint32))


def _fnv_host(*keys) -> int:
    """Stable 32-bit FNV-1a of the stringified keys (host side)."""
    h = 2166136261
    for b in "|".join(map(str, keys)).encode():
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def hash01(*ints) -> jnp.ndarray:
    """Stable uniform [0, 1) from broadcastable integer arrays — the JAX
    analogue of serving.teachers._hash01 (per-key mixing, xxhash-style
    avalanche), shared by the flicker draws and the approx-miss draws."""
    h = jnp.uint32(0x811C9DC5)
    for x in ints:
        h = h ^ jnp.asarray(x).astype(jnp.uint32)
        h = h * jnp.uint32(0x9E3779B1)
        h = h ^ (h >> 15)
        h = h * jnp.uint32(0x85EBCA77)
        h = h ^ (h >> 13)
    return h.astype(jnp.float32) * jnp.float32(2.0 ** -32)


class SceneObs(NamedTuple):
    """Per-camera observation tables; leaves lead with [F, N, Z]."""
    counts: jnp.ndarray     # [F, N, Z, P]
    areas: jnp.ndarray      # [F, N, Z, P]
    centroid: jnp.ndarray   # [F, N, Z, 2]
    spread: jnp.ndarray     # [F, N, Z]
    extent: jnp.ndarray     # [F, N, Z]
    nbox: jnp.ndarray       # [F, N, Z] int32
    acc_true: jnp.ndarray   # [F, N, Z]


def grid_windows(grid, zoom_levels=(1.0, 2.0, 3.0)) -> jnp.ndarray:
    """Device copy of the flattened (cell x zoom) FOV windows."""
    return jnp.asarray(window_arrays(grid, zoom_levels))


@staged("heads")
def detections_obs(dets, windows: jnp.ndarray, pair_cls: jnp.ndarray,
                   thresh: jnp.ndarray, geo_thresh: jnp.ndarray,
                   acc_true: jnp.ndarray, *, n_zoom: int = 3) -> SceneObs:
    """Distilled-detector outputs -> the same observation tables the
    oracle pass produces, so `fleet_step` consumes either interchangeably.

    dets: models.detector.Detections with leaves [F, C, K, ...] — one row
    per (camera, flattened cell x zoom window); windows [C, 4] the
    matching FOV windows (cell-major, kernels.cell_rasterize
    .window_arrays layout); pair_cls [P] object class per workload pair;
    thresh [P] per-pair score threshold (a detection counts for pair p
    when its score clears thresh[p] AND its argmax class is pair p's
    object); geo_thresh [] score floor for the zoom-geometry statistics.
    acc_true [F, N, Z] rides through untouched — backend feedback stays
    the oracle's judgment of what the camera chose, only the camera-side
    ranking signal switches to the approximation model (paper §3.4).

    Boxes arrive in normalized image coordinates; geometry converts to
    scene degrees through the per-window FOV transform (data/render
    .boxes_to_scene) because the zoom controller compares centroids and
    spreads against cell centers in degrees. Counts are float32 like the
    rasterized tables; `spread` is the same one-pass RMS moment.
    """
    f, c, k = dets.scores.shape
    n = c // n_zoom
    x0 = windows[:, 0][None, :, None]           # [1, C, 1]
    y0 = windows[:, 1][None, :, None]
    fw = windows[:, 2][None, :, None]
    fh = windows[:, 3][None, :, None]
    deg_x = x0 + dets.boxes[..., 0] * fw        # [F, C, K]
    deg_y = y0 + dets.boxes[..., 1] * fh
    w_img, h_img = dets.boxes[..., 2], dets.boxes[..., 3]

    cls_id = jnp.argmax(dets.class_probs, axis=-1)          # [F, C, K]
    keep_p = ((dets.scores[:, :, None, :] >= thresh[None, None, :, None])
              & (cls_id[:, :, None, :]
                 == pair_cls[None, None, :, None]))         # [F, C, P, K]
    kf = keep_p.astype(jnp.float32)
    counts = jnp.sum(kf, axis=-1)                           # [F, C, P]
    areas = jnp.sum(kf * (w_img * h_img)[:, :, None, :], axis=-1)

    geo = (dets.scores >= geo_thresh).astype(jnp.float32)   # [F, C, K]
    nbox = jnp.sum(geo, axis=-1)                            # [F, C]
    nb = jnp.maximum(nbox, 1e-9)
    cx = jnp.sum(geo * deg_x, axis=-1) / nb
    cy = jnp.sum(geo * deg_y, axis=-1) / nb
    c2 = jnp.sum(geo * (deg_x * deg_x + deg_y * deg_y), axis=-1) / nb
    has = nbox > 0
    centroid = jnp.where(has[..., None], jnp.stack([cx, cy], -1), 0.0)
    spread = jnp.where(has, jnp.sqrt(jnp.maximum(
        c2 - cx * cx - cy * cy, 0.0)), 0.0)
    side = jnp.maximum(w_img * fw, h_img * fh)
    extent = jnp.max(jnp.where(geo > 0, side, 0.0), axis=-1)

    def to_nz(x):           # [F, C, ...] -> [F, N, Z, ...]
        return x.reshape((f, n, n_zoom) + x.shape[2:])

    return SceneObs(counts=to_nz(counts), areas=to_nz(areas),
                    centroid=to_nz(centroid), spread=to_nz(spread),
                    extent=to_nz(extent),
                    nbox=to_nz(nbox).astype(jnp.int32), acc_true=acc_true)


@partial(jax.jit, static_argnames=("spec", "task_id", "pair_idx", "n_zoom"))
@staged("oracle_grade")
def observe_all_cells(spec: SceneSpec, teach: TeacherArrays,
                      params: SceneFleetParams, state: SceneState,
                      t: jnp.ndarray, windows: jnp.ndarray, *,
                      task_id: tuple, pair_idx: tuple, n_zoom: int = 3,
                      cam_salt: jnp.ndarray | None = None) -> SceneObs:
    """One observation pass for the whole fleet at controller frame `t`
    ([F] int32, the flicker/miss clock). windows [N*Z, 4] from
    `grid_windows`; task_id/pair_idx from WorkloadSpec. cam_salt [F]
    (any stable per-camera int, e.g. a word of the camera's key)
    decorrelates detection/miss noise across cameras — without it,
    object slot k draws identical teacher noise on every camera."""
    f, m = state.oid.shape
    p = teach.a0.shape[0]
    kinds = jnp.asarray(kind_mask(spec))
    cls_match = (teach.cls[:, None] == kinds[None, :])     # [P, M]

    if cam_salt is None:
        cam_salt = jnp.zeros(f, jnp.uint32)
    cam = cam_salt[:, None, None]                          # [F, 1, 1]
    oid = state.oid[:, None, :]                            # [F, 1, M]
    salt = teach.salt[None, :, None]                       # [1, P, 1]
    bucket = (t // spec.flicker_bucket)[:, None, None]     # [F, 1, 1]
    draw = ((1.0 - teach.flicker[None, :, None])
            * hash01(oid, salt, cam, jnp.uint32(_BASE_SALT))
            + teach.flicker[None, :, None] * hash01(oid, salt, cam, bucket))
    # normalize by the plateau so the kernel's ramp test draw < resp
    # reproduces draw < p_max * resp
    draw = draw / jnp.maximum(teach.pmax[None, :, None], 1e-6)
    live = params.enabled[:, None, :] & cls_match[None]    # [F, P, M]
    keep = hash01(state.oid, t[:, None], cam_salt[:, None],
                  jnp.uint32(_MISS_SALT)) >= spec.miss_rate  # [F, M]
    draw_student = jnp.where(live & keep[:, None, :], draw, 2.0)
    draw_teacher = jnp.where(live, draw, 2.0)

    ox, oy = state.pos[..., 0], state.pos[..., 1]
    ow, oh = state.size[..., 0], state.size[..., 1]
    # one rasterization pass: teacher draws stack as extra count-only
    # channels [F, 2P, M] (n_moment=P keeps the geometry student-driven),
    # so the per-(object, window) clipping/visibility work is not doubled
    cnt2, area2, wcx, wcy, wc2, ext = cell_rasterize(
        ox, oy, ow, oh, jnp.concatenate([draw_student, draw_teacher], 1),
        a0=jnp.tile(teach.a0, 2), a1=jnp.tile(teach.a1, 2),
        windows=windows, min_visible=spec.min_visible, n_moment=p,
        use_kernel=spec.use_kernel)
    cnt, area = cnt2[:, :p], area2[:, :p]
    cnt_t = cnt2[:, p:]

    n = windows.shape[0] // n_zoom

    def to_nz(x):           # [F, P, C] -> [F, N, Z, P]
        return jnp.transpose(x.reshape(f, p, n, n_zoom), (0, 2, 3, 1))

    counts = to_nz(cnt)
    areas = to_nz(area)
    nbox = jnp.sum(cnt, axis=1).reshape(f, n, n_zoom)
    nb = jnp.maximum(nbox, 1e-9)
    cx = (wcx / nb.reshape(f, -1)).reshape(f, n, n_zoom)
    cy = (wcy / nb.reshape(f, -1)).reshape(f, n, n_zoom)
    has = nbox > 0
    centroid = jnp.where(has[..., None],
                         jnp.stack([cx, cy], -1), 0.0)
    spread = jnp.where(has, jnp.sqrt(jnp.maximum(
        wc2.reshape(f, n, n_zoom) / nb - cx * cx - cy * cy, 0.0)), 0.0)
    extent = ext.reshape(f, n, n_zoom)

    # oracle workload accuracy from teacher counts (relative per step)
    acc = None
    for q in range(len(pair_idx)):
        c_q = cnt_t[:, pair_idx[q], :]                     # [F, C]
        mx = jnp.max(c_q, axis=-1, keepdims=True)
        if task_id[q] == 0:       # binary: correct "no" when scene empty
            a = jnp.where(mx > 0, (c_q > 0).astype(jnp.float32), 1.0)
        else:                     # count / detect / agg_count
            a = jnp.where(mx > 0, c_q / jnp.maximum(mx, 1e-9), 1.0)
        acc = a if acc is None else acc + a
    acc_true = (acc / len(pair_idx)).reshape(f, n, n_zoom)

    return SceneObs(counts=counts, areas=areas, centroid=centroid,
                    spread=spread, extent=extent,
                    nbox=nbox.astype(jnp.int32), acc_true=acc_true)
