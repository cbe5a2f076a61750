"""The in-scan learning loop: (trainable params, opt state, ring) riding
the jit'd episode carry.

`LearnState` threads through runner._episode's lax.scan inside the
DetectorProvider carry; `distill_step` is the optimizer step that runs
ENTIRELY inside the scan (no per-step host transfers) on the cadence
DistillSpec.every sets. The design constraints, in order:

  * one update-rule definition — `optimizer_apply` is the single place
    an optimizer touches params; the host-side `core/continual
    .finetune_step` delegates to `finetune_update` here, so the offline
    and in-scan paths cannot drift;
  * per-camera independence — the loss vmaps per camera, gradient
    clipping is per-camera (train/optim.adamw_update's built-in clip is
    a GLOBAL norm across all leaves, which would couple cameras through
    the fleet axis — so it is disabled and reapplied per row), and
    cameras whose ring is empty are a bit-exact no-op (a `where` on
    params AND moments: AdamW's weight decay would otherwise drift idle
    cameras' heads);
  * frozen-backbone exactness — head-only mode trains per-camera head
    convs on features the shared frozen backbone staged during the
    inference forward, so the staged features are exactly what a fresh
    backbone pass would produce and training adds only head-conv
    FLOPs.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.learn.loss import distill_full_loss, distill_head_loss
from repro.learn.pairs import PairBuffer, init_pair_buffer
from repro.learn.spec import DistillSpec
from repro.models import detector as det
from repro.train import optim


class LearnState(NamedTuple):
    """Device pytree riding the episode scan carry (distill on only).

    params: the trainable subtree with a leading fleet axis [F, ...] —
    the heads dict in head-only mode, the full detector pytree
    otherwise. staged/staged_widx hold the current step's inference
    payload between the observe and learn hooks of one scan iteration.
    """
    params: Any             # [F, ...] per-camera trainable params
    opt: Any                # AdamState | SGDState over `params`
    buf: PairBuffer
    staged: jnp.ndarray     # [F, K, ...] this step's student payload
    staged_widx: jnp.ndarray  # [F, K] int32 window ids of the payload


def trainable_mask(dspec: DistillSpec, trainable) -> Any:
    """Optimizer mask over the trainable pytree. Head-only: everything
    (the subtree IS the heads). Full: everything except the shared patch
    embedding — the staged tokens were produced by it, so its grads are
    structurally zero and Adam/decay must not drift it."""
    if dspec.head_only:
        return jax.tree.map(lambda _: True, trainable)
    m = jax.tree.map(lambda _: True, trainable)
    name = det.backbone_name(trainable)
    m["backbone"][name]["patch_embed"] = jax.tree.map(
        lambda _: False, det.patch_embed(trainable))
    return m


def init_learn(dspec: DistillSpec, det_cfg, det_params, n_cameras: int,
               shortlist_k: int) -> LearnState:
    """Broadcast the trainable subtree per camera and size the ring +
    staging buffers. Runs inside jit (init_carry)."""
    f = n_cameras
    sub = det_params["heads"] if dspec.head_only else det_params
    params = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (f,) + p.shape), sub)
    mask = trainable_mask(dspec, params)
    if dspec.optimizer == "adamw":
        opt = optim.adamw_init(params, mask)
    else:
        opt = optim.sgd_init(params)
    if dspec.head_only:
        g = det_cfg.feature_grid
        payload = (g, g, det_cfg.fpn_dim)
    else:
        payload = (det_cfg.token_grid ** 2, det_cfg.d_model)
    return LearnState(
        params=params, opt=opt,
        buf=init_pair_buffer(f, dspec.buffer, payload, det_cfg.max_boxes,
                             dtype=det_cfg.dtype),
        staged=jnp.zeros((f, shortlist_k) + payload, det_cfg.dtype),
        staged_widx=jnp.zeros((f, shortlist_k), jnp.int32))


def lr_at(dspec: DistillSpec, step) -> jnp.ndarray:
    if dspec.schedule == "constant":
        return jnp.asarray(dspec.lr, jnp.float32)
    return optim.cosine_schedule(dspec.lr, dspec.warmup,
                                 dspec.horizon)(step)


def optimizer_apply(name: str, params, grads, opt_state, *, lr,
                    mask=None, weight_decay: float = 0.0,
                    grad_clip: float | None = None):
    """THE optimizer update — every training path in the repo (in-scan
    distillation here, host-side continual fine-tuning through
    `finetune_update`) funnels into this one call, so there is exactly
    one update rule to audit. Returns (params', opt_state')."""
    if name == "adamw":
        return optim.adamw_update(params, grads, opt_state, lr=lr,
                                  mask=mask, weight_decay=weight_decay,
                                  grad_clip=grad_clip)
    if name == "sgd":
        return optim.sgd_update(params, grads, opt_state, lr=lr)
    raise ValueError(f"unknown optimizer {name!r} (adamw | sgd)")


def _per_camera_clip(grads, mask, clip: float) -> Any:
    """Per-camera global-norm clip over the trainable leaves: each
    camera's row scales by its OWN norm, so no gradient information
    crosses the fleet axis (the fleet-size-independence invariant)."""
    sq = None
    for g, keep in zip(jax.tree.leaves(grads), jax.tree.leaves(mask)):
        if not keep:
            continue
        s = jnp.sum(jnp.square(g.astype(jnp.float32)),
                    axis=tuple(range(1, g.ndim)))
        sq = s if sq is None else sq + s
    gnorm = jnp.sqrt(sq)                                    # [F]
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))

    def app(g):
        return g * scale.reshape((g.shape[0],)
                                 + (1,) * (g.ndim - 1)).astype(g.dtype)

    return jax.tree.map(app, grads)


def distill_update(dspec: DistillSpec, det_cfg, lc: LearnState
                   ) -> tuple[LearnState, jnp.ndarray]:
    """One optimizer step over every camera's ring. Returns (new state,
    per-camera loss [F] — -1.0 for cameras whose ring was empty and
    whose params/moments pass through bit-unchanged)."""
    buf = lc.buf
    f = buf.weight.shape[0]

    if dspec.head_only:
        def cam_loss(tr, x, bx, cl, vl, w):
            return distill_head_loss(tr, x, bx, cl, vl, w)
    else:
        def cam_loss(tr, x, bx, cl, vl, w):
            return distill_full_loss(tr, det_cfg, x, bx, cl, vl, w)

    def total(params):
        losses = jax.vmap(cam_loss)(params, buf.x, buf.boxes,
                                    buf.classes, buf.valid, buf.weight)
        return jnp.sum(losses), losses

    (_, losses), grads = jax.value_and_grad(total, has_aux=True)(lc.params)
    mask = trainable_mask(dspec, lc.params)
    if dspec.grad_clip is not None:
        grads = _per_camera_clip(grads, mask, dspec.grad_clip)
    lr_t = lr_at(dspec, lc.opt.step)
    new_params, new_opt = optimizer_apply(
        dspec.optimizer, lc.params, grads, lc.opt, lr=lr_t, mask=mask,
        weight_decay=dspec.weight_decay, grad_clip=None)

    # idle cameras (empty ring) are a bit-exact no-op: weight decay and
    # Adam moments must not drift params that saw no data
    has = buf.weight.sum(axis=-1) > 0                       # [F]

    def keep_new(keep, n, o):
        if not keep:
            return n                    # masked leaves never changed
        return jnp.where(has.reshape((f,) + (1,) * (n.ndim - 1)), n, o)

    new_params = jax.tree.map(keep_new, mask, new_params, lc.params)
    if dspec.optimizer == "adamw":
        new_opt = optim.AdamState(
            new_opt.step,
            jax.tree.map(keep_new, mask, new_opt.mu, lc.opt.mu),
            jax.tree.map(keep_new, mask, new_opt.nu, lc.opt.nu))
    else:
        new_opt = optim.SGDState(
            new_opt.step,
            jax.tree.map(keep_new, mask, new_opt.momentum,
                         lc.opt.momentum))
    loss_out = jnp.where(has, losses, -1.0)
    return lc._replace(params=new_params, opt=new_opt), loss_out


def distill_step(dspec: DistillSpec, det_cfg, lc: LearnState, step_idx
                 ) -> tuple[LearnState, dict]:
    """The cadence-gated update (lax.cond keeps off-steps free). step_idx
    is the post-step controller step count ([F], all equal — steps are
    1-based after fleet_step increments). Returns (state', aux) with aux
    {"loss": [F] (-1.0 on skipped/idle), "lr": [F]}."""
    f = lc.buf.weight.shape[0]
    do = (step_idx[0] % dspec.every) == 0
    lc, loss = jax.lax.cond(
        do,
        lambda s: distill_update(dspec, det_cfg, s),
        lambda s: (s, jnp.full((f,), -1.0)),
        lc)
    lr_t = lr_at(dspec, lc.opt.step)
    return lc, {"loss": loss, "lr": jnp.broadcast_to(lr_t, (f,))}


def merged_params(dspec: DistillSpec, det_params, trained, camera=None):
    """Recombine the per-camera trained subtree with the shared frozen
    rest into full detector params. camera=None keeps the leading fleet
    axis on the trained leaves (head-only mode then mixes shared
    backbone + [F, ...] heads — slice before saving); an int selects one
    camera's checkpoint, ready for `save_detector_params`."""
    take = (lambda p: p) if camera is None else (lambda p: p[camera])
    trained = jax.tree.map(take, trained)
    if dspec.head_only:
        return {"backbone": det_params["backbone"], "heads": trained}
    return trained


# ---------------------------------------------------------------------------
# host-side fine-tune (core/continual.py delegates here)
# ---------------------------------------------------------------------------

def finetune_update(params, opt_state, cfg, images, gt_boxes, gt_classes,
                    gt_valid, *, lr: float = 1e-3,
                    weight_decay: float = 1e-4):
    """One offline continual-learning gradient step — the exact update
    `core/continual.finetune_step` always ran (frozen backbone,
    heads-only AdamW, global grad clip), now expressed through the same
    `optimizer_apply` the in-scan loop uses. Returns (params', state',
    loss)."""
    def loss_fn(p):
        return det.detector_loss(p, cfg, images, gt_boxes, gt_classes,
                                 gt_valid, freeze_backbone=True)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    mask = det.head_params_mask(params)
    params, opt_state = optimizer_apply(
        "adamw", params, grads, opt_state, lr=lr, mask=mask,
        weight_decay=weight_decay, grad_clip=1.0)
    return params, opt_state, loss


def init_finetune_state(params):
    """Optimizer state sized to the heads only (97% state savings)."""
    return optim.adamw_init(params, det.head_params_mask(params))
