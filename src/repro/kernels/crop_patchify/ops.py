"""jit'd wrappers: candidate crops -> patch-embedding tokens.

`crop_patchify` accepts the provider-native layout (scene object boxes +
per-camera shortlisted FOV windows + the detector's conv patch-embed
params) and returns the [F, K, gg, D] token rows the batched detector
forward consumes. Like cell_rasterize, the pure-jnp reference is the
default inside fused fleet steps — on the reference path the pixels are
the existing `render_fleet_crops` output fed through the existing conv,
so it is bit-identical to the unfused pixel pipeline. The Pallas kernel
path (use_kernel=True) fuses rasterization into the patch contraction so
crops never round-trip through HBM as pixels. It runs in the Pallas
interpreter only on the CPU backend (repro.kernels.interpret_mode).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.crop_patchify.crop_patchify import crop_patchify_batch
from repro.kernels.crop_patchify.ref import crop_patchify_ref, pixel_bounds
from repro.obs.trace import stage


def crop_patchify(pos, size, kind, oid, windows, patch_params, *,
                  patch: int, res: int = 64, min_visible: float = 0.25,
                  noise=None, dtype=jnp.float32, block_k: int | None = None,
                  use_kernel: bool = False) -> jnp.ndarray:
    """pos/size [F, M, 2], kind [M], oid [F, M]; windows [F, K, 4] or
    [K, 4] fleet-shared; patch_params {"w": [p, p, 3, D], "b": [D]};
    noise [F, res, res, 3] or None. Returns tokens [F, K, (res/p)^2, D].

    `block_k` (reference path only; must divide K) slabs the K window
    axis so the transient pixel buffer peaks at [F, block_k, res, res,
    3] instead of all K crops at once — the jnp analogue of the
    kernel's per-block VMEM residency; tokens come out identical
    because each crop's render+embed is independent. The Pallas path
    already blocks per (camera, window) and ignores it. Its patch-embed
    contraction runs at full f32 precision whatever the ambient matmul
    precision.
    """
    if res % patch != 0:
        raise ValueError(f"res={res} must be a multiple of patch={patch}")
    k = windows.shape[-2]
    if block_k is not None and (block_k <= 0 or k % block_k != 0):
        raise ValueError(f"block_k={block_k} must divide the {k} windows")
    with stage("crop_to_tokens"):
        return _crop_patchify(pos, size, kind, oid, windows, patch_params,
                              noise, patch=patch, res=res,
                              min_visible=min_visible, dtype=dtype,
                              block_k=block_k, use_kernel=use_kernel,
                              interpret=interpret_mode())


@partial(jax.jit, static_argnames=("patch", "res", "min_visible", "dtype",
                                   "block_k", "use_kernel", "interpret"))
def _crop_patchify(pos, size, kind, oid, windows, patch_params, noise, *,
                   patch: int, res: int, min_visible: float, dtype,
                   block_k: int | None, use_kernel: bool,
                   interpret: bool) -> jnp.ndarray:
    if not use_kernel:
        ref = partial(crop_patchify_ref, pos, size, kind, oid,
                      patch_params=patch_params, patch=patch, res=res,
                      min_visible=min_visible, noise=noise, dtype=dtype)
        k = windows.shape[-2]
        if block_k is None or block_k >= k:
            return ref(windows=windows)
        # slab the window axis: the serial dimension only covers the
        # cheap render+embed; callers still batch the expensive model
        # forward over all K at once
        wblocks = jnp.moveaxis(
            windows.reshape(windows.shape[:-2]
                            + (k // block_k, block_k, 4)), -3, 0)
        tok = jax.lax.map(lambda wb: ref(windows=wb), wblocks)
        return jnp.moveaxis(tok, 0, 1).reshape(
            (tok.shape[1], k) + tok.shape[3:])
    from repro.scene_jax.render import object_colors, render_background

    f = oid.shape[0]
    g = res // patch
    keep, px0, px1, py0, py1 = pixel_bounds(
        pos, size, windows, res=res, min_visible=min_visible)
    # an invisible object gets the empty rectangle [0, 0) and never paints
    boxes = jnp.stack([jnp.where(keep, b, 0)
                       for b in (px0, px1, py0, py1)], axis=2)  # [F,K,4,M]
    colors = jnp.swapaxes(object_colors(kind, oid), 1, 2).astype(
        jnp.float32)                                            # [F, 3, M]
    bgn = render_background(res)[None]
    if noise is not None:
        bgn = bgn + noise
    bgn = jnp.broadcast_to(bgn, (f, res, res, 3)).astype(jnp.float32)
    # [F, res, res, 3] -> [F, 3, gg, p*p]: row = patch (gy, gx), column =
    # pixel (py, px) inside it — the conv's HWIO weight order per channel
    bgn = bgn.reshape(f, g, patch, g, patch, 3).transpose(
        0, 5, 1, 3, 2, 4).reshape(f, 3, g * g, patch * patch)
    t, q = np.divmod(np.arange(g * g)[:, None], g), np.divmod(
        np.arange(patch * patch)[None, :], patch)
    rows = jnp.asarray(t[0] * patch + q[0], jnp.int32)          # [gg, p*p]
    cols = jnp.asarray(t[1] * patch + q[1], jnp.int32)
    w = patch_params["w"].astype(jnp.float32)                   # [p,p,3,D]
    wsplit = jnp.moveaxis(w, 2, 0).reshape(3, patch * patch, -1)
    bias = patch_params.get("b")
    bias = (jnp.zeros((1, w.shape[-1]), jnp.float32) if bias is None
            else bias.astype(jnp.float32)[None])
    tok = crop_patchify_batch(rows, cols, boxes, colors, bgn, wsplit, bias,
                              interpret=interpret)
    return tok.astype(dtype)
