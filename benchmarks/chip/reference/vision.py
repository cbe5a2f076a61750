"""Plain reference of the approximation detector and of the crops it sees.

A crop is what a camera at one orientation window would capture: a
textured gradient, per-camera per-frame sensor noise, and every object
at least `min_visible` inside the window painted as a class-coloured
rectangle (later slots over earlier ones), clipped to [0, 1].

The detector (paper sec. 3.4: a backbone with an FPN-lite neck and
anchor-free heads): the backbone the configuration names
(`backbones/<name>.py`, passed in as a module; the benchmark's cell
runs `backbones/vit.py`) turns the crop into a [g, g, D] map; the
shared part below puts it through a 1x1 and a 3x3 conv (GELU), then
three 3x3 heads give class logits, box offsets and objectness per
cell. A cell's score is sigmoid(objectness) x its top class
probability; the best `max_boxes` cells are the detections. Written per
crop, in the crop's dtype and at the matmul `precision` the
configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference.world import RENDER_SALT

_PERSON_RGB = (0.9, 0.3, 0.2)
_CAR_RGB = (0.2, 0.4, 0.9)
_SHADE_MULT_97 = 2654435761 % 97


def noise_image(cam_key, frame, res: int, scale, dtype):
    """[3, res, res] sensor noise of one camera at one frame."""
    k = jax.random.fold_in(jax.random.fold_in(cam_key, RENDER_SALT), frame)
    return (jax.random.normal(k, (res, res, 3), dtype) * scale).transpose(
        2, 0, 1)


def render(pos, size, oid, max_people: int, window, noise, *, res: int,
           min_visible: float, dtype):
    """One crop [3, res, res] (channel planes) of one camera at one
    window."""
    m = pos.shape[0]
    x0, y0, fw, fh = window[0], window[1], window[2], window[3]
    ox0 = pos[:, 0] - size[:, 0] / 2
    ox1 = pos[:, 0] + size[:, 0] / 2
    oy0 = pos[:, 1] - size[:, 1] / 2
    oy1 = pos[:, 1] + size[:, 1] / 2
    ix0, ix1 = jnp.maximum(ox0, x0), jnp.minimum(ox1, x0 + fw)
    iy0, iy1 = jnp.maximum(oy0, y0), jnp.minimum(oy1, y0 + fh)
    inter = jnp.maximum(ix1 - ix0, 0.0) * jnp.maximum(iy1 - iy0, 0.0)
    keep = inter / jnp.maximum((ox1 - ox0) * (oy1 - oy0), 1e-9) \
        >= min_visible
    px0 = jnp.clip((ix0 - x0) / fw * res, 0, res - 1).astype(jnp.int32)
    px1 = jnp.clip((ix1 - x0) / fw * res + 1, 1, res).astype(jnp.int32)
    py0 = jnp.clip((iy0 - y0) / fh * res, 0, res - 1).astype(jnp.int32)
    py1 = jnp.clip((iy1 - y0) / fh * res + 1, 1, res).astype(jnp.int32)

    shade = 0.7 + 0.3 * ((oid % 97) * _SHADE_MULT_97 % 97) / 97.0
    person = jnp.arange(m) < max_people
    colour = jnp.where(person[:, None], jnp.asarray(_PERSON_RGB),
                       jnp.asarray(_CAR_RGB)) * shade[:, None]

    r = jnp.arange(res, dtype=jnp.float32) / res
    yy = jnp.broadcast_to(r[:, None], (res, res))
    xx = jnp.broadcast_to(r[None, :], (res, res))
    img = jnp.stack([0.35 + 0.15 * yy, 0.4 + 0.1 * xx,
                     0.35 + 0.05 * (xx + yy)]) + noise
    rows = jnp.arange(res)
    owner = jnp.full((res, res), -1, jnp.int32)
    for i in range(m):                      # slot order: later paints over
        hit = (keep[i] & (rows[:, None] >= py0[i]) & (rows[:, None] < py1[i])
               & (rows[None, :] >= px0[i]) & (rows[None, :] < px1[i]))
        owner = jnp.where(hit, i, owner)
    img = jnp.where((owner >= 0)[None],
                    colour[jnp.maximum(owner, 0)].transpose(2, 0, 1), img)
    return jnp.clip(img, 0.0, 1.0).astype(dtype)


def layer_norm(p, x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(p, x, prec):
    return jnp.matmul(x, p["w"], precision=prec) + p["b"]


def conv(p, x, prec):
    """SAME conv, stride 1, of a [g, g, C] map."""
    y = jax.lax.conv_general_dilated(
        x[None], p["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)[0]
    return y + p["b"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def detect(params, crop, backbone, sizes: dict,
           precision: str | None = None):
    """One crop -> every cell's (score, box cxcywh, class probs), best
    score first; the detector keeps the first `max_boxes`. `backbone` is
    the configuration's backbone module, `sizes` its `detector` sizes,
    `precision` the matmul precision (None: the platform's default)."""
    pr = None if precision in (None, "default") else precision
    dt = crop.dtype
    params = jax.tree.map(lambda a: a.astype(dt), params)
    fmap = backbone.features(params["backbone"], crop, sizes, pr)
    g = fmap.shape[0]
    neck = params["backbone"]["neck"]
    f = gelu(conv(neck["smooth"], conv(neck["lateral"], fmap, pr), pr))
    heads = params["heads"]
    cls = conv(heads["cls"], f, pr).reshape(g * g, -1).astype(jnp.float32)
    box = conv(heads["box"], f, pr).reshape(g * g, 4).astype(jnp.float32)
    obj = conv(heads["obj"], f, pr).reshape(g * g).astype(jnp.float32)

    ys, xs = jnp.meshgrid(jnp.arange(g), jnp.arange(g), indexing="ij")
    off = jax.nn.sigmoid(box[:, :2])
    wh = jax.nn.sigmoid(box[:, 2:])
    boxes = jnp.stack([(xs.reshape(-1) + off[:, 0]) / g,
                       (ys.reshape(-1) + off[:, 1]) / g,
                       wh[:, 0], wh[:, 1]], -1)
    probs = jax.nn.softmax(cls, axis=-1)
    scores = jax.nn.sigmoid(obj) * probs.max(-1)
    order = jnp.argsort(-scores, stable=True)
    return scores[order], boxes[order], probs[order]
