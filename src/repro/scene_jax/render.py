"""Fixed-shape jnp rasterizer: SceneState boxes -> orientation crops.

Device port of `data/render.render_image` so the distilled approximation
model (models/detector) can score the *actual pixels* of every candidate
orientation inside the jit'd episode scan — the paper's camera-side
knowledge-distillation loop (§3.4) — instead of reading precomputed
teacher tables. Same image model as the numpy renderer: class-colored
object rectangles painted in slot order over a textured gradient
background, the FOV projection an axis-aligned crop in scene degrees.

Parity with `data/render.render_image` is exact at `noise=0` (pinned by
tests/test_render_jax.py): identical visibility rule (clipped area /
object area >= min_visible), identical pixel-bound rounding, identical
last-painter-wins overlap semantics, and the same multiplicative oid
shade computed in modular arithmetic so int32 never overflows. Noise is
the one deliberate divergence: the numpy path draws from a host
Generator, the device path from `jax.random` keyed as
fold_in(fold_in(camera_key, salt), frame) — per-camera decorrelated,
reproducible, and independent of fleet size or shard layout (the same
key discipline as the scene dynamics).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.data.scene import PERSON
from repro.obs.trace import staged

_RENDER_SALT = 0x9E4DE
# (oid * 2654435761) % 97 without the 64-bit product: reduce both factors
# mod 97 first (2654435761 % 97 == 75), exact for any non-negative oid
_SHADE_MULT_97 = 2654435761 % 97

_PERSON_COLOR = (0.9, 0.3, 0.2)
_CAR_COLOR = (0.2, 0.4, 0.9)


def render_background(res: int) -> jnp.ndarray:
    """[res, res, 3] textured gradient, identical to the numpy renderer."""
    yy, xx = jnp.meshgrid(jnp.arange(res, dtype=jnp.float32) / res,
                          jnp.arange(res, dtype=jnp.float32) / res,
                          indexing="ij")
    return jnp.stack([0.35 + 0.15 * yy, 0.4 + 0.1 * xx,
                      0.35 + 0.05 * (xx + yy)], axis=-1)


def render_noise(rng: jnp.ndarray, frame, res: int) -> jnp.ndarray:
    """Per-camera standard-normal noise images [F, res, res, 3] for one
    frame. rng [F, 2] camera keys; the render stream is salted so it
    never collides with the scene-dynamics stream derived from the same
    camera keys."""
    keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(rng, _RENDER_SALT)
    keys = jax.vmap(jax.random.fold_in)(keys, jnp.broadcast_to(
        frame, (rng.shape[0],)))
    return jax.vmap(lambda k: jax.random.normal(k, (res, res, 3)))(keys)


def object_colors(kind, oid) -> jnp.ndarray:
    """Per-object paint colors [..., M, 3]: class base color times the
    multiplicative oid shade, in modular arithmetic (identical to
    data/render.render_image). kind [M] (or broadcastable), oid [..., M].
    Shared by the jnp renderer and the fused kernels/crop_patchify path
    so the paint model has one definition."""
    shade = 0.7 + 0.3 * ((oid % 97) * _SHADE_MULT_97 % 97) / 97.0
    return jnp.where((kind == PERSON)[..., None],
                     jnp.asarray(_PERSON_COLOR),
                     jnp.asarray(_CAR_COLOR)) * shade[..., None]


def render_crop(pos, size, kind, oid, window, *, res: int = 64,
                min_visible: float = 0.25,
                noise_img: jnp.ndarray | None = None) -> jnp.ndarray:
    """One camera, one FOV window -> [res, res, 3] float32 in [0, 1].

    pos/size [M, 2] (scene degrees), kind/oid [M]; window (x0, y0, fw, fh)
    as in kernels.cell_rasterize.window_arrays. Disabled slots (size 0)
    have zero visibility and never paint. Boxes paint in slot order, so
    overlap resolution matches the numpy renderer's paint loop.
    """
    x0, y0, fw, fh = window[0], window[1], window[2], window[3]
    ox0 = pos[:, 0] - size[:, 0] / 2
    ox1 = pos[:, 0] + size[:, 0] / 2
    oy0 = pos[:, 1] - size[:, 1] / 2
    oy1 = pos[:, 1] + size[:, 1] / 2

    ix0 = jnp.maximum(ox0, x0)
    ix1 = jnp.minimum(ox1, x0 + fw)
    iy0 = jnp.maximum(oy0, y0)
    iy1 = jnp.minimum(oy1, y0 + fh)
    inter = jnp.maximum(ix1 - ix0, 0.0) * jnp.maximum(iy1 - iy0, 0.0)
    area = (ox1 - ox0) * (oy1 - oy0)
    keep = inter / jnp.maximum(area, 1e-9) >= min_visible

    # normalized clipped box -> pixel bounds, data/render's rounding:
    # clip first, then truncate (all values non-negative -> floor)
    bx0 = (ix0 - x0) / fw
    bx1 = (ix1 - x0) / fw
    by0 = (iy0 - y0) / fh
    by1 = (iy1 - y0) / fh
    px0 = jnp.clip(bx0 * res, 0, res - 1).astype(jnp.int32)
    px1 = jnp.clip(bx1 * res + 1, 1, res).astype(jnp.int32)
    py0 = jnp.clip(by0 * res, 0, res - 1).astype(jnp.int32)
    py1 = jnp.clip(by1 * res + 1, 1, res).astype(jnp.int32)

    color = object_colors(kind, oid)                              # [M, 3]

    img = render_background(res)
    if noise_img is not None:
        img = img + noise_img
    rr = jnp.arange(res)[None, :, None]         # rows (y)
    cc = jnp.arange(res)[None, None, :]         # cols (x)

    # the numpy renderer paints boxes sequentially in slot order, so the
    # highest-index covering box owns each pixel — one masked argmax
    # instead of M sequential paints
    hit = (keep[:, None, None]
           & (rr >= py0[:, None, None]) & (rr < py1[:, None, None])
           & (cc >= px0[:, None, None]) & (cc < px1[:, None, None]))
    m_idx = jnp.arange(pos.shape[0])[:, None, None]
    m_best = jnp.max(jnp.where(hit, m_idx, -1), axis=0)      # [res, res]
    img = jnp.where((m_best >= 0)[..., None],
                    color[jnp.maximum(m_best, 0)], img)
    return jnp.clip(img, 0.0, 1.0)


@partial(jax.jit,
         static_argnames=("res", "min_visible"))
@staged("crop_to_tokens")
def render_fleet_crops(pos, size, kind, oid, windows, *, res: int = 64,
                       min_visible: float = 0.25,
                       noise: jnp.ndarray | None = None) -> jnp.ndarray:
    """The whole fleet's candidate-orientation crops in one pass.

    pos/size [F, M, 2], kind [M] (slot layout is fleet-wide: scene_jax
    .kind_mask), oid [F, M], windows [C, 4] fleet-shared or [F, C, 4]
    per camera (the candidate-sparse shortlist gathers a different
    window set per camera), noise [F, res, res, 3] or None (one noise
    image per camera per frame, shared across windows — data/render
    seeds its Generator per frame, so its noise is likewise shared
    across the crops of one snapshot). Returns [F, C, res, res, 3].
    """
    per_window = jax.vmap(
        lambda p, s, o, w, nz: render_crop(
            p, s, kind, o, w, res=res, min_visible=min_visible,
            noise_img=nz),
        in_axes=(None, None, None, 0, None))
    win_ax = None if windows.ndim == 2 else 0
    per_cam = jax.vmap(per_window, in_axes=(0, 0, 0, win_ax, 0))
    if noise is None:
        noise = jnp.zeros((pos.shape[0], res, res, 3))
    return per_cam(pos, size, oid, windows, noise)
