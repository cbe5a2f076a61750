"""ViT backbone of the approximation detector (DeiT's layout), plainly.

A backbone module owns everything from a crop to the feature map the
shared neck takes; `reference/weights.py`, `reference/vision.py` and
`flops.py` add the neck, heads and decode around it. A configuration
names it by its top-level `"backbone"` key (this one where it names
none); `harness.load_backbone` finds `backbones/<name>.py`. Each
function takes the configuration's `detector` sizes:

  init(keys, sizes)      its entries of `params["backbone"]`, drawing
                         keys from the shared iterator in a fixed order
  feature_shape(sizes)   (g, g, D) of the map `features` returns
  features(bb, crop, sizes, pr)
                         one crop [3, res, res] -> the map [g, g, D];
                         `bb` is `params["backbone"]` in the crop's dtype
  window_flops(sizes)    FLOPs of `features` over one window

This one: `patch` px patches embedded linearly, a CLS token and learned
positions, `n_layers` pre-norm blocks (multi-head attention, GELU MLP),
a final norm; the patch tokens are the map, D = `d_model`. Weights:
LeCun-normal linear layers, He-normal patch conv, CLS and positions
0.02, norms at identity, biases zero; `params["backbone"]["vit"]`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import vision, weights


def init(keys, sizes: dict) -> dict:
    dm, ff, nl = sizes["d_model"], sizes["d_ff"], sizes["n_layers"]
    g = sizes["img_res"] // sizes["patch"]
    lead = (nl,)
    return {"vit": {
        "patch_embed": weights.conv(next(keys), sizes["patch"],
                                    sizes["patch"], 3, dm),
        "cls_token": weights.trunc_normal(next(keys), (1, 1, dm), 0.02),
        "pos_embed": weights.trunc_normal(next(keys), (1, g * g + 1, dm),
                                          0.02),
        "layers": {
            "norm1": weights.norm(lead, dm),
            "attn": {w: weights.linear(next(keys), lead + (dm, dm))
                     for w in ("wq", "wk", "wv", "wo")},
            "norm2": weights.norm(lead, dm),
            "mlp": {"up": weights.linear(next(keys), lead + (dm, ff)),
                    "down": weights.linear(next(keys), lead + (ff, dm))},
        },
        "final_norm": weights.norm((), dm),
        "head": weights.linear(next(keys), (dm, 2)),
    }}


def feature_shape(sizes: dict) -> tuple[int, int, int]:
    g = sizes["img_res"] // sizes["patch"]
    return g, g, sizes["d_model"]


def features(bb, crop, sizes: dict, pr):
    vit = bb["vit"]
    patch, n_heads = sizes["patch"], sizes["n_heads"]
    dt = crop.dtype
    res = crop.shape[-1]
    g = res // patch
    d = vit["patch_embed"]["w"].shape[-1]
    x = crop.reshape(3, g, patch, g, patch).transpose(1, 3, 2, 4, 0)
    x = jnp.matmul(x.reshape(g * g, patch * patch * 3),
                   vit["patch_embed"]["w"].reshape(-1, d), precision=pr)
    x = x + vit["patch_embed"]["b"]
    x = jnp.concatenate([vit["cls_token"][0], x], 0) + vit["pos_embed"][0]
    t, dh = x.shape[0], d // n_heads
    lay = vit["layers"]
    for i in range(lay["norm1"]["scale"].shape[0]):
        p = jax.tree.map(lambda a: a[i], lay)
        h = vision.layer_norm(p["norm1"], x)
        q, k, v = (vision.dense(p["attn"][w], h, pr).reshape(t, n_heads, dh)
                   for w in ("wq", "wk", "wv"))
        logits = jnp.einsum("qhd,khd->hqk", q, k, precision=pr) \
            / jnp.sqrt(jnp.asarray(dh, dt))
        a = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v, precision=pr).reshape(t, d)
        x = x + vision.dense(p["attn"]["wo"], o, pr)
        h = vision.layer_norm(p["norm2"], x)
        up = vision.dense(p["mlp"]["up"], h, pr)
        x = x + vision.dense(p["mlp"]["down"], vision.gelu(up), pr)
    return vision.layer_norm(vit["final_norm"], x)[1:].reshape(g, g, d)


def window_flops(sizes: dict) -> int:
    """The patch embed (a conv of stride `patch`) and `n_layers` blocks
    over the CLS token plus the patches; a multiply-add counts 2, and
    softmax, norms and activations are left out (O(tokens x width))."""
    g = sizes["img_res"] // sizes["patch"]
    p = g * g                                   # patch tokens
    t = p + 1                                   # + CLS
    dm, ff = sizes["d_model"], sizes["d_ff"]
    patch_embed = 2 * p * (sizes["patch"] ** 2 * 3) * dm
    attn_proj = 2 * t * dm * 4 * dm             # q, k, v, out
    attn_mix = 2 * 2 * t * t * dm               # scores and weights x V
    mlp = 2 * 2 * t * dm * ff
    return patch_embed + sizes["n_layers"] * (attn_proj + attn_mix + mlp)
