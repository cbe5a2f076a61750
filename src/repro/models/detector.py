"""MadEye approximation model — TPU-native EfficientDet-D0 analogue.

Paper §3.1: an ultra-lightweight detector for objects of interest, one per
query, used ONLY to rank orientations. Design choices mirrored here:

  * frozen feature extractor shared across queries (paper: EfficientDet
    backbone + BiFPN frozen, pre-trained on VOC) -> here: a small
    transformer backbone (ViT, or Swin's last two stages on one grid;
    `DetectorConfig.backbone`) + FPN-lite neck whose params sit under
    ``params["backbone"]``
    and are excluded from fine-tuning via `lax.stop_gradient` + optimizer
    masking (train/optim.py);
  * only the final box/class/centerness heads are per-query fine-tuned
    (paper: "only weights for the final 3 bounding box and class prediction
    layers");
  * static box budget (max_boxes) — no dynamic shapes on TPU; outputs carry
    a validity score instead of being pruned by NMS-with-dynamic-output.

Output format (per image): boxes [max_boxes, 4] in [0,1] cxcywh,
scores [max_boxes], class_probs [max_boxes, n_classes].
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import DetectorConfig, VisionConfig
from repro.models import swin, vit
from repro.models.layers import Params, conv2d, conv_init
from repro.obs.trace import stage, staged


class Detections(NamedTuple):
    boxes: jnp.ndarray        # [..., max_boxes, 4] cxcywh in [0, 1]
    scores: jnp.ndarray       # [..., max_boxes] objectness * class prob
    class_probs: jnp.ndarray  # [..., max_boxes, n_classes]


def _backbone_cfg(cfg: DetectorConfig) -> VisionConfig:
    return VisionConfig(
        name=f"{cfg.name}-backbone", img_res=cfg.img_res, patch=cfg.patch,
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        d_ff=cfg.d_ff, n_classes=2, dtype=cfg.dtype)


# ---------------------------------------------------------------------------
# The backbone, chosen here and nowhere else: everything from the neck's
# input on is one code path
# ---------------------------------------------------------------------------

def _backbone_init(key, cfg: DetectorConfig) -> Params:
    if cfg.backbone == "swin":
        return swin.swin_backbone_init(key, cfg)
    return vit.vit_init(key, _backbone_cfg(cfg))


def _feature_width(cfg: DetectorConfig) -> int:
    if cfg.backbone == "swin":
        return swin.swin_feature_width(cfg)
    return cfg.d_model


def _features_tokens(bb: Params, cfg: DetectorConfig,
                     tokens: jnp.ndarray) -> jnp.ndarray:
    """patch tokens [B, P, D] -> the backbone's map [B, g, g, width]
    (g = cfg.feature_grid). Callers open the `backbone` stage."""
    if cfg.backbone == "swin":
        return swin.swin_features_tokens(bb["swin"], cfg, tokens)
    return vit.vit_features_tokens(bb["vit"], _backbone_cfg(cfg), tokens)


def backbone_name(params: Params) -> str:
    """The backbone's entry of `params["backbone"]` (the one beside the
    neck): "vit" or "swin"."""
    (name,) = (k for k in params["backbone"] if k != "neck")
    return name


def patch_embed(params: Params) -> Params:
    """The token path's patch-embedding params ({"w": [p, p, 3, D],
    "b": [D]}), whichever backbone the detector has: what
    kernels/crop_patchify contracts the crops with."""
    return params["backbone"][backbone_name(params)]["patch_embed"]


def detector_init(key, cfg: DetectorConfig) -> Params:
    kb, kn, kh1, kh2, kh3 = jax.random.split(key, 5)
    F = cfg.fpn_dim
    return {
        # ---- frozen across queries (cached on cameras) ----
        "backbone": {
            cfg.backbone: _backbone_init(kb, cfg),
            "neck": {
                "lateral": conv_init(jax.random.fold_in(kn, 0), 1, 1,
                                     _feature_width(cfg), F,
                                     dtype=cfg.dtype),
                "smooth": conv_init(jax.random.fold_in(kn, 1), 3, 3, F, F,
                                    dtype=cfg.dtype),
            },
        },
        # ---- fine-tuned per query (paper: final 3 prediction layers) ----
        "heads": {
            "cls": conv_init(kh1, 3, 3, F, cfg.n_classes, dtype=cfg.dtype),
            "box": conv_init(kh2, 3, 3, F, 4, dtype=cfg.dtype),
            "obj": conv_init(kh3, 3, 3, F, 1, dtype=cfg.dtype),
        },
    }


@staged("heads")
def neck_features(bb: Params, feats: jnp.ndarray) -> jnp.ndarray:
    """backbone feature map [B, g, g, D] -> post-neck map [B, g, g, F].

    The frozen end of the network: everything up to (and including) this
    is masked out of fine-tuning, which is what lets the in-scan learner
    (repro.learn) stage these features once from the inference forward
    and train the heads on them without re-running the backbone."""
    f = conv2d(bb["neck"]["lateral"], feats)
    return jax.nn.gelu(conv2d(bb["neck"]["smooth"], f))     # [B, g, g, F]


@staged("heads")
def head_outputs(heads: Params, f: jnp.ndarray):
    """post-neck features [B, g, g, F] -> raw head outputs — the
    per-query fine-tuned slice of the forward (paper: the final 3
    prediction layers)."""
    cls_logits = conv2d(heads["cls"], f)
    box_raw = conv2d(heads["box"], f)
    obj_logits = conv2d(heads["obj"], f)[..., 0]
    return cls_logits, box_raw, obj_logits


def _neck_and_heads(params: Params, bb: Params, feats: jnp.ndarray):
    """backbone feature map [B, g, g, D] -> raw head outputs."""
    return head_outputs(params["heads"], neck_features(bb, feats))


def detector_raw(params: Params, cfg: DetectorConfig, images: jnp.ndarray, *,
                 freeze_backbone: bool = False):
    """images [B,H,W,3] -> (cls_logits [B,g,g,K], box [B,g,g,4], obj [B,g,g]).

    Box parametrization: sigmoid(dx,dy) = center offset inside the cell,
    sigmoid(w,h) = size relative to the whole image.
    """
    bb = params["backbone"]
    if freeze_backbone:
        bb = jax.lax.stop_gradient(bb)
    with stage("backbone"):
        tokens = vit.vit_embed(bb[cfg.backbone], _backbone_cfg(cfg), images)
        feats = _features_tokens(bb, cfg, tokens)    # [B, g, g, D]
    return _neck_and_heads(params, bb, feats)


def detector_raw_tokens(params: Params, cfg: DetectorConfig,
                        tokens: jnp.ndarray, *,
                        freeze_backbone: bool = False):
    """Patch-embedding tokens [B, P, D] (vit.vit_embed layout — e.g. the
    fused kernels/crop_patchify output) -> the same raw head outputs as
    `detector_raw` on the images those tokens embed."""
    bb = params["backbone"]
    if freeze_backbone:
        bb = jax.lax.stop_gradient(bb)
    with stage("backbone"):
        feats = _features_tokens(bb, cfg, tokens)
    return _neck_and_heads(params, bb, feats)


def detector_neck_feats_tokens(params: Params, cfg: DetectorConfig,
                               tokens: jnp.ndarray) -> jnp.ndarray:
    """Patch tokens [B, P, D] -> post-neck feature map [B, g, g, F].

    The frozen half of the fused fast path when the heads are trained
    per camera (repro.learn): the shared backbone+neck run once over
    the [F*K] shortlist, per-camera heads consume the result, and the
    same features are staged as the training payload — head-only
    distillation re-runs zero backbone compute."""
    bb = params["backbone"]
    with stage("backbone"):
        feats = _features_tokens(bb, cfg, tokens)
    return neck_features(bb, feats)


def detections_from_feats(cfg: DetectorConfig, heads: Params,
                          feats: jnp.ndarray) -> Detections:
    """Post-neck features [B, g, g, F] + head params -> Detections.
    Completes `detector_neck_feats_tokens` with (possibly per-camera
    fine-tuned) heads."""
    return _decode_detections(cfg, *head_outputs(heads, feats))


def decode_boxes(box_raw: jnp.ndarray) -> jnp.ndarray:
    """[B,g,g,4] raw -> cxcywh in [0,1] (cell-relative center + global
    size)."""
    B, g = box_raw.shape[0], box_raw.shape[1]
    ys, xs = jnp.meshgrid(jnp.arange(g), jnp.arange(g), indexing="ij")
    off = jax.nn.sigmoid(box_raw[..., :2])
    cx = (xs[None] + off[..., 0]) / g
    cy = (ys[None] + off[..., 1]) / g
    wh = jax.nn.sigmoid(box_raw[..., 2:])
    return jnp.stack([cx, cy, wh[..., 0], wh[..., 1]], axis=-1)


def detector_forward(params: Params, cfg: DetectorConfig,
                     images: jnp.ndarray) -> Detections:
    """images [B,H,W,3] -> top-`max_boxes` Detections per image."""
    return _decode_detections(cfg, *detector_raw(params, cfg, images))


def detector_forward_tokens(params: Params, cfg: DetectorConfig,
                            tokens: jnp.ndarray) -> Detections:
    """Patch tokens [B, P, D] -> top-`max_boxes` Detections per crop —
    the single batched forward of the candidate-sparse fast path
    (fleet.DetectorProvider flattens [F, K] -> [F*K] rows)."""
    return _decode_detections(cfg,
                              *detector_raw_tokens(params, cfg, tokens))


@staged("heads")
def _decode_detections(cfg: DetectorConfig, cls_logits, box_raw,
                       obj_logits) -> Detections:
    B, g = cls_logits.shape[0], cls_logits.shape[1]
    boxes = decode_boxes(box_raw).reshape(B, g * g, 4)
    cls_probs = jax.nn.softmax(
        cls_logits.reshape(B, g * g, -1).astype(jnp.float32), axis=-1)
    obj = jax.nn.sigmoid(obj_logits.reshape(B, g * g).astype(jnp.float32))
    scores = obj * jnp.max(cls_probs, axis=-1)

    k = min(cfg.max_boxes, g * g)
    top_scores, idx = jax.lax.top_k(scores, k)
    top_boxes = jnp.take_along_axis(boxes, idx[..., None], axis=1)
    top_probs = jnp.take_along_axis(cls_probs, idx[..., None], axis=1)
    pad = cfg.max_boxes - k
    if pad > 0:
        top_scores = jnp.pad(top_scores, ((0, 0), (0, pad)))
        top_boxes = jnp.pad(top_boxes, ((0, 0), (0, pad), (0, 0)))
        top_probs = jnp.pad(top_probs, ((0, 0), (0, pad), (0, 0)))
    return Detections(top_boxes, top_scores, top_probs)


# ---------------------------------------------------------------------------
# Training loss (distillation target = teacher boxes; see core/distill.py)
# ---------------------------------------------------------------------------

def detector_loss_from_outputs(cls_logits: jnp.ndarray, box_raw: jnp.ndarray,
                               obj_logits: jnp.ndarray,
                               gt_boxes: jnp.ndarray, gt_classes: jnp.ndarray,
                               gt_valid: jnp.ndarray,
                               weight: jnp.ndarray | None = None):
    """The anchor-free single-level loss on raw head outputs.

    The ONE loss definition: `detector_loss` (full forward) and the
    in-scan distillation objective (repro.learn.loss, staged post-neck
    features) both reduce to this. `weight` [B] is an optional per-sample
    weight — the pair-buffer path weighs empty ring slots 0 so idle
    buffer rows contribute nothing; weight=None is the exact unweighted
    math (bit-identical to the pre-refactor loss).
    """
    B, g = cls_logits.shape[0], cls_logits.shape[1]
    K = cls_logits.shape[-1]

    # Assign GT to cells: cell index of each GT center
    cx, cy = gt_boxes[..., 0], gt_boxes[..., 1]
    ci = jnp.clip((cx * g).astype(jnp.int32), 0, g - 1)
    cj = jnp.clip((cy * g).astype(jnp.int32), 0, g - 1)
    cell = cj * g + ci                                   # [B, N]

    # Build dense targets [B, g*g, ...] via scatter (last valid GT wins).
    obj_t = jnp.zeros((B, g * g))
    cls_t = jnp.zeros((B, g * g), jnp.int32)
    box_t = jnp.zeros((B, g * g, 4))

    bidx = jnp.arange(B)[:, None].repeat(gt_boxes.shape[1], 1)
    v = gt_valid.astype(jnp.float32)
    safe_cell = jnp.where(gt_valid, cell, 0)
    obj_t = obj_t.at[bidx, safe_cell].max(v)
    cls_t = cls_t.at[bidx, safe_cell].set(
        jnp.where(gt_valid, gt_classes, cls_t[bidx, safe_cell]))
    box_t = box_t.at[bidx, safe_cell].set(
        jnp.where(gt_valid[..., None], gt_boxes, box_t[bidx, safe_cell]))

    obj_logits = obj_logits.reshape(B, g * g).astype(jnp.float32)
    cls_logits = cls_logits.reshape(B, g * g, K).astype(jnp.float32)
    pred_boxes = decode_boxes(box_raw).reshape(B, g * g, 4)

    # focal-style objectness BCE
    p = jax.nn.sigmoid(obj_logits)
    bce = -(obj_t * jnp.log(p + 1e-8) + (1 - obj_t) * jnp.log(1 - p + 1e-8))
    focal_w = jnp.where(obj_t > 0, (1 - p) ** 2, p ** 2)
    pos = obj_t                                          # [B, g*g]
    logp = jax.nn.log_softmax(cls_logits, axis=-1)
    cls_nll = -jnp.take_along_axis(logp, cls_t[..., None], axis=-1)[..., 0]
    box_l1 = jnp.abs(pred_boxes - box_t)

    if weight is None:
        obj_loss = jnp.mean(focal_w * bce)
        n_pos = jnp.maximum(jnp.sum(pos), 1.0)
        cls_loss = jnp.sum(pos * cls_nll) / n_pos
        box_loss = jnp.sum(pos[..., None] * box_l1) / n_pos
    else:
        w = weight.astype(jnp.float32)[:, None]          # [B, 1]
        obj_loss = (jnp.sum(w * focal_w * bce)
                    / jnp.maximum(jnp.sum(w) * (g * g), 1.0))
        wpos = w * pos
        n_pos = jnp.maximum(jnp.sum(wpos), 1.0)
        cls_loss = jnp.sum(wpos * cls_nll) / n_pos
        box_loss = jnp.sum(wpos[..., None] * box_l1) / n_pos

    return obj_loss + cls_loss + box_loss


def detector_loss(params: Params, cfg: DetectorConfig, images: jnp.ndarray,
                  gt_boxes: jnp.ndarray, gt_classes: jnp.ndarray,
                  gt_valid: jnp.ndarray, *, freeze_backbone: bool = True):
    """Anchor-free single-level loss over a full image forward.

    gt_boxes [B,N,4] cxcywh; gt_classes [B,N] int; gt_valid [B,N] bool.
    Each valid GT is assigned to the cell containing its center.
    """
    cls_logits, box_raw, obj_logits = detector_raw(
        params, cfg, images, freeze_backbone=freeze_backbone)
    return detector_loss_from_outputs(cls_logits, box_raw, obj_logits,
                                      gt_boxes, gt_classes, gt_valid)


def detector_loss_tokens(params: Params, cfg: DetectorConfig,
                         tokens: jnp.ndarray, gt_boxes: jnp.ndarray,
                         gt_classes: jnp.ndarray, gt_valid: jnp.ndarray, *,
                         weight: jnp.ndarray | None = None,
                         freeze_backbone: bool = False):
    """`detector_loss` starting from patch-embedding tokens [B, P, D] —
    the full-param distillation objective (the staged training payload
    is the crop_patchify token buffer, re-run through the trainable
    backbone)."""
    cls_logits, box_raw, obj_logits = detector_raw_tokens(
        params, cfg, tokens, freeze_backbone=freeze_backbone)
    return detector_loss_from_outputs(cls_logits, box_raw, obj_logits,
                                      gt_boxes, gt_classes, gt_valid,
                                      weight=weight)


def head_params_mask(params: Params) -> Params:
    """Pytree mask: True for fine-tuned (head) leaves, False for backbone."""
    return jax.tree.map(lambda _: False, params) | {
        "heads": jax.tree.map(lambda _: True, params["heads"])}
