"""A backbone for the tests, in the layout of `benchmarks/chip/backbones/`:
a linear patch embedding to twice the detector's `d_model`, then a norm.
Its feature map is wider than `d_model`, so that a test can see the
shared neck, the reference forward and the FLOP count take the width
from the backbone. The tests copy it into a benchmark tree as
`backbones/patchwide.py`."""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks.chip.reference import vision, weights


def _width(sizes: dict) -> int:
    return 2 * sizes["d_model"]


def init(keys, sizes: dict) -> dict:
    p, w = sizes["patch"], _width(sizes)
    return {"patchwide": {"embed": weights.conv(next(keys), p, p, 3, w),
                          "norm": weights.norm((), w)}}


def feature_shape(sizes: dict) -> tuple[int, int, int]:
    g = sizes["img_res"] // sizes["patch"]
    return g, g, _width(sizes)


def features(bb, crop, sizes: dict, pr):
    p = bb["patchwide"]
    patch, w = sizes["patch"], _width(sizes)
    g = crop.shape[-1] // patch
    x = crop.reshape(3, g, patch, g, patch).transpose(1, 3, 2, 4, 0)
    x = jnp.matmul(x.reshape(g * g, patch * patch * 3),
                   p["embed"]["w"].reshape(-1, w), precision=pr)
    return vision.layer_norm(p["norm"], x + p["embed"]["b"]).reshape(g, g, w)


def window_flops(sizes: dict) -> int:
    g = sizes["img_res"] // sizes["patch"]
    return 2 * g * g * sizes["patch"] ** 2 * 3 * _width(sizes)
