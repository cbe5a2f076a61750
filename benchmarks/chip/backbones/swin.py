"""Swin backbone of the approximation detector (Swin-T's layout), plainly.

Written from the published description (Liu et al., "Swin Transformer",
arXiv 2103.14030, sec. 3.1-3.2 and Table 7), one crop at a time, in the
crop's dtype at the matmul precision given; nothing of the program is
imported. The functions are those every backbone module has (see
`backbones/vit.py`); they read the configuration's `detector` sizes:
`patch`, `d_model` (C), `n_heads` and `d_ff` of the first stage (each
later stage doubles all three), `depths`, `window` (M).

  patch embed    `patch` px patches, linearly to C, then a norm
  stage s        `depths[s]` pre-norm blocks at width C * 2^s: windowed
                 multi-head attention over M x M windows with a learned
                 relative-position bias ((2M-1)^2 rows a head, looked up
                 by the offset of key from query); on odd blocks the
                 map is first rolled by -M/2 in both axes, tokens of
                 different regions of a window are kept apart by a
                 -100 logit, and the map is rolled back after; a map
                 no larger than the window takes one window and no
                 shift. Then a GELU MLP
  patch merge    after every stage but the last: each 2 x 2 patch's
                 four tokens concatenated (row, col) = (0, 0), (1, 0),
                 (0, 1), (1, 1), normed, and reduced 4C -> 2C with no
                 bias
  the map        the last two stages, each normed; the last upsampled x2
                 by nearest neighbour and set beside the one before:
                 [g, g, 12C] for four stages, g = res / patch / 4

Weights in `params["backbone"]["swin"]`: patch conv He-normal, linear
layers LeCun-normal, relative-position tables 0.02 (all truncated at 2
sigma), norms at identity, biases zero; each stage's blocks stacked on
a leading axis. Keys: one from the shared stream for the patch embed
and one for each stage; a stage's weights fold in their block and role.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import vision, weights

MASK = -100.0           # the logit between regions of a shifted window


def _stages(sizes: dict):
    """(side, width, heads, MLP width, blocks) of each stage."""
    g = sizes["img_res"] // sizes["patch"]
    return [(g >> s, sizes["d_model"] << s, sizes["n_heads"] << s,
             sizes["d_ff"] << s, depth)
            for s, depth in enumerate(sizes["depths"])]


def _window(side: int, window: int) -> int:
    return min(window, side)


def init(keys, sizes: dict) -> dict:
    m = sizes["window"]
    p = sizes["patch"]
    stages = _stages(sizes)
    embed = weights.conv(next(keys), p, p, 3, sizes["d_model"])
    out = []
    for s, (_, d, heads, ff, depth) in enumerate(stages):
        key = next(keys)

        def k(block, role):
            return jax.random.fold_in(jax.random.fold_in(key, block), role)

        lead = (depth,)
        blocks = {
            "norm1": weights.norm(lead, d),
            "attn": {w: jax.tree.map(
                lambda *a: jnp.stack(a),
                *[weights.linear(k(b, r), (d, d)) for b in range(depth)])
                for r, w in enumerate(("wq", "wk", "wv", "wo"))},
            "rel_bias": jnp.stack([weights.trunc_normal(
                k(b, 4), ((2 * m - 1) ** 2, heads), 0.02)
                for b in range(depth)]),
            "norm2": weights.norm(lead, d),
            "mlp": {"up": jax.tree.map(
                        lambda *a: jnp.stack(a),
                        *[weights.linear(k(b, 5), (d, ff))
                          for b in range(depth)]),
                    "down": jax.tree.map(
                        lambda *a: jnp.stack(a),
                        *[weights.linear(k(b, 6), (ff, d))
                          for b in range(depth)])},
        }
        stage = {"blocks": blocks}
        if s < len(stages) - 1:
            stage["merge"] = {
                "norm": weights.norm((), 4 * d),
                "reduce": {"w": weights.linear(k(depth, 7),
                                               (4 * d, 2 * d))["w"]}}
        if s >= len(stages) - 2:
            stage["out_norm"] = weights.norm((), d)
        out.append(stage)
    return {"swin": {"patch_embed": embed,
                     "patch_norm": weights.norm((), sizes["d_model"]),
                     "stages": out}}


def feature_shape(sizes: dict) -> tuple[int, int, int]:
    stages = _stages(sizes)
    side = stages[-2][0]
    return side, side, stages[-2][1] + stages[-1][1]


def relative_index(m: int, table: int) -> np.ndarray:
    """[m*m, m*m]: the row of a (2t-1)^2 table (t = `table`) for each
    (query, key) pair of an m x m window, by their offset (dy, dx)."""
    ys, xs = np.divmod(np.arange(m * m), m)
    dy = ys[:, None] - ys[None, :] + table - 1
    dx = xs[:, None] - xs[None, :] + table - 1
    return dy * (2 * table - 1) + dx


def region_mask(side: int, m: int, shift: int) -> np.ndarray:
    """[n_windows, m*m, m*m]: 0 between tokens of one region of the
    rolled map, MASK between tokens of different ones."""
    label = np.zeros((side, side), np.int32)
    cuts = ((0, side - m), (side - m, side - shift), (side - shift, side))
    for i, (y0, y1) in enumerate(cuts):
        for j, (x0, x1) in enumerate(cuts):
            label[y0:y1, x0:x1] = 3 * i + j
    win = label.reshape(side // m, m, side // m, m).transpose(
        0, 2, 1, 3).reshape(-1, m * m)
    return np.where(win[:, :, None] == win[:, None, :], 0.0, MASK)


def _block(p, x, heads: int, m: int, table: int, shift: int, pr):
    side, _, d = x.shape
    dh = d // heads
    h = vision.layer_norm(p["norm1"], x)
    if shift:
        h = jnp.roll(h, (-shift, -shift), axis=(0, 1))
    n = side // m
    win = h.reshape(n, m, n, m, d).transpose(0, 2, 1, 3, 4).reshape(
        n * n, m * m, d)
    q, k, v = (vision.dense(p["attn"][w], win, pr).reshape(
        n * n, m * m, heads, dh) for w in ("wq", "wk", "wv"))
    logits = jnp.einsum("wqhd,wkhd->whqk", q, k, precision=pr) \
        / jnp.sqrt(jnp.asarray(dh, x.dtype))
    bias = p["rel_bias"][relative_index(m, table)]        # [T, T, heads]
    logits = logits + bias.transpose(2, 0, 1)[None]
    if shift:
        logits = logits + jnp.asarray(region_mask(side, m, shift),
                                      x.dtype)[:, None]
    a = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("whqk,wkhd->wqhd", a, v, precision=pr).reshape(
        n * n, m * m, d)
    o = vision.dense(p["attn"]["wo"], o, pr)
    o = o.reshape(n, n, m, m, d).transpose(0, 2, 1, 3, 4).reshape(
        side, side, d)
    if shift:
        o = jnp.roll(o, (shift, shift), axis=(0, 1))
    x = x + o
    h = vision.layer_norm(p["norm2"], x)
    up = vision.dense(p["mlp"]["up"], h, pr)
    return x + vision.dense(p["mlp"]["down"], vision.gelu(up), pr)


def _merge(p, x, pr):
    x = jnp.concatenate([x[0::2, 0::2], x[1::2, 0::2], x[0::2, 1::2],
                         x[1::2, 1::2]], -1)
    return jnp.matmul(vision.layer_norm(p["norm"], x), p["reduce"]["w"],
                      precision=pr)


def features(bb, crop, sizes: dict, pr):
    sw = bb["swin"]
    patch, table = sizes["patch"], sizes["window"]
    res = crop.shape[-1]
    g = res // patch
    c = sizes["d_model"]
    x = crop.reshape(3, g, patch, g, patch).transpose(1, 3, 2, 4, 0)
    x = jnp.matmul(x.reshape(g * g, patch * patch * 3),
                   sw["patch_embed"]["w"].reshape(-1, c), precision=pr)
    x = vision.layer_norm(sw["patch_norm"], x + sw["patch_embed"]["b"])
    x = x.reshape(g, g, c)
    outs = []
    for (side, _, heads, _, depth), st in zip(_stages(sizes), sw["stages"]):
        m = _window(side, table)
        for i in range(depth):
            p = jax.tree.map(lambda a: a[i], st["blocks"])
            shift = m // 2 if i % 2 == 1 and side > m else 0
            x = _block(p, x, heads, m, table, shift, pr)
        if "out_norm" in st:
            outs.append(vision.layer_norm(st["out_norm"], x))
        if "merge" in st:
            x = _merge(st["merge"], x, pr)
    fine, coarse = outs
    up = fine.shape[0] // coarse.shape[0]
    coarse = jnp.repeat(jnp.repeat(coarse, up, axis=0), up, axis=1)
    return jnp.concatenate([fine, coarse], -1)


def window_flops(sizes: dict) -> int:
    """The patch embed, every block (q, k, v and output projections, the
    window mix QK^T and AV, the MLP) and the patch merges, over one
    window; a multiply-add counts 2, and softmax, norms, activations,
    rolls and the bias lookup are left out (O(tokens x width))."""
    g = sizes["img_res"] // sizes["patch"]
    total = 2 * g * g * sizes["patch"] ** 2 * 3 * sizes["d_model"]
    stages = _stages(sizes)
    for s, (side, d, _, ff, depth) in enumerate(stages):
        n = side * side                             # tokens
        t = _window(side, sizes["window"]) ** 2     # keys a query sees
        block = 2 * n * d * 4 * d + 2 * 2 * n * t * d + 2 * 2 * n * d * ff
        total += depth * block
        if s < len(stages) - 1:
            total += 2 * (n // 4) * 4 * d * 2 * d
    return total
