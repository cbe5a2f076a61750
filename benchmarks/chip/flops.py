"""Operations the approximation detector needs, counted from its shapes.

One window is one crop of `img_res` pixels: the backbone the
configuration names (`backbones/<name>.py`, its `window_flops`: for
ViT, a patch embed and `n_layers` pre-norm blocks), then the shared
part at the backbone's feature map [g, g, D] (`feature_shape`): a 1x1
lateral and a 3x3 smooth conv (the neck) and three 3x3 head convs. A
multiply-add counts 2. Softmax, norms, activations and the box decode
are left out: they are O(tokens x width), below 1%.
"""
from __future__ import annotations

from benchmarks.chip import harness


def detector_window_flops(config: dict) -> float:
    """FLOPs of one forward over one window of a configuration: its
    `detector` sizes (n_classes, fpn_dim and what its backbone reads)
    and the backbone its `backbone` key names."""
    d = config["detector"]
    bb = harness.load_backbone(config.get("backbone"))
    g, _, width = bb.feature_shape(d)
    p = g * g                                   # map cells
    f = d["fpn_dim"]
    neck = 2 * p * width * f + 2 * p * 9 * f * f
    heads = 2 * p * 9 * f * (d["n_classes"] + 4 + 1)
    return float(bb.window_flops(d) + neck + heads)


def detector_step_flops(config: dict, n_cameras: int, windows: int
                        ) -> float:
    """FLOPs of one fleet step: every camera scores `windows` windows."""
    return detector_window_flops(config) * n_cameras * windows
