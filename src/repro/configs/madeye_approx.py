"""MadEye approximation model (the paper's EfficientDet-D0 analogue,
TPU-native).

ViT-S-class backbone (frozen across queries, cached on cameras) + FPN-lite neck
+ anchor-free center/box/class heads (fine-tuned per query). ~4M
params to match
EfficientDet-D0's 3.9M budget.

`madeye-approx-swin-t` puts Swin-T (arXiv 2103.14030 sec. 3, Table 7:
patch 4, C 96, depths 2-2-6-2, heads 3-6-12-24, window 7, MLP ratio 4)
in the backbone's place; its smoke size keeps the 4 x 4 head grid of
the ViT smoke (64 px, stage maps 16/8/4/2, window 2).
"""
from repro.configs.base import DetectorConfig, register

FULL = DetectorConfig(
    name="madeye-approx",
    img_res=224,
    patch=16,
    n_layers=6,
    d_model=192,
    n_heads=6,
    d_ff=768,
    n_classes=2,
    max_boxes=32,
    fpn_dim=128,
)

SMOKE = DetectorConfig(
    name="madeye-approx-smoke",
    img_res=64,
    patch=16,
    n_layers=2,
    d_model=48,
    n_heads=3,
    d_ff=96,
    n_classes=2,
    max_boxes=8,
    fpn_dim=32,
)

SWIN_T = DetectorConfig(
    name="madeye-approx-swin-t",
    img_res=224,
    patch=4,
    n_layers=12,
    d_model=96,
    n_heads=3,
    d_ff=384,
    n_classes=2,
    max_boxes=32,
    fpn_dim=128,
    backbone="swin",
    depths=(2, 2, 6, 2),
    window=7,
)

SWIN_T_SMOKE = DetectorConfig(
    name="madeye-approx-swin-t-smoke",
    img_res=64,
    patch=4,
    n_layers=8,
    d_model=16,
    n_heads=1,
    d_ff=64,
    n_classes=2,
    max_boxes=8,
    fpn_dim=32,
    backbone="swin",
    depths=(2, 2, 2, 2),
    window=2,
)

register(FULL, SMOKE)
register(SWIN_T, SWIN_T_SMOKE)
