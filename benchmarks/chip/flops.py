"""Operations the approximation detector needs, counted from its shapes.

One window is one crop of `img_res` pixels: a patch embed (a conv of
stride `patch`), `n_layers` pre-norm ViT blocks over the CLS token plus
the patches, a 1x1 lateral and a 3x3 smooth conv (the neck) and three
3x3 head convs. A multiply-add counts 2. Softmax, norms, activations
and the box decode are left out: they are O(tokens x width), below 1%.
"""
from __future__ import annotations


def detector_window_flops(d: dict) -> float:
    """FLOPs of one forward over one window; `d` holds the config's
    detector sizes (img_res, patch, n_layers, d_model, d_ff, fpn_dim,
    n_classes)."""
    g = d["img_res"] // d["patch"]
    p = g * g                                   # patch tokens
    t = p + 1                                   # + CLS
    dm, ff, f = d["d_model"], d["d_ff"], d["fpn_dim"]
    patch_embed = 2 * p * (d["patch"] ** 2 * 3) * dm
    attn_proj = 2 * t * dm * 4 * dm             # q, k, v, out
    attn_mix = 2 * 2 * t * t * dm               # scores and weights x V
    mlp = 2 * 2 * t * dm * ff
    blocks = d["n_layers"] * (attn_proj + attn_mix + mlp)
    neck = 2 * p * dm * f + 2 * p * 9 * f * f
    heads = 2 * p * 9 * f * (d["n_classes"] + 4 + 1)
    return float(patch_embed + blocks + neck + heads)


def detector_step_flops(d: dict, n_cameras: int, windows: int) -> float:
    """FLOPs of one fleet step: every camera scores `windows` windows."""
    return detector_window_flops(d) * n_cameras * windows
