"""jit'd wrappers: candidate crops -> patch-embedding tokens.

`crop_patchify` accepts the provider-native layout (scene object boxes +
per-camera shortlisted FOV windows + the detector's conv patch-embed
params) and returns the [F, K, gg, D] token rows the batched detector
forward consumes. The platform chooses the path: the Pallas kernel
wherever the program is lowered for a TPU, the jnp reference on the CPU.
The choice is made at lowering (`jax.lax.platform_dependent`), so a
program compiled from a CPU process for a described TPU holds the
kernel, as one compiled on the chip does.

On the reference path the pixels are the existing `render_fleet_crops`
output fed through the existing conv, so it is bit-identical to the
unfused pixel pipeline. The kernel fuses rasterization into the patch
contraction so crops never round-trip through HBM as pixels. Forcing
one path (use_kernel=True/False) is for tests and the chip smoke run
that compare the two; forced on the CPU, the kernel runs in the Pallas
interpreter (repro.kernels.interpret_mode).

Where a patch has fewer pixels than a vector register has lanes (patch
4: 16 pixels), the kernel paints several patches side by side in one
plane row and contracts them against block-diagonal weights
(`pack_factor`); its rows are then the tokens in row-major order.

The compiler cannot partition a Pallas call, so where the program is
partitioned over several devices (the fleet axis on a mesh) each device
runs the kernel on its own cameras under `shard_map`: a camera's crops
depend on nothing of another camera's.
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
                  use_kernel: bool | None = None) -> jnp.ndarray:
    """pos/size [F, M, 2], kind [M], oid [F, M]; windows [F, K, 4] or
    [K, 4] fleet-shared; patch_params {"w": [p, p, 3, D], "b": [D]};
    noise [F, res, res, 3] or None. Returns tokens [F, K, (res/p)^2, D].

    `use_kernel` None (default): the platform chooses — the kernel
    wherever the program is lowered for a TPU, the jnp reference on the
    CPU. True or False forces one path. The kernel has no gradient
    rule, so no caller differentiates through the tokens.

    `block_k` (reference path only; must divide K) slabs the K window
    axis so the transient pixel buffer peaks at [F, block_k, res, res,
    3] instead of all K crops at once — the jnp analogue of the
    kernel's per-block VMEM residency; tokens come out identical
    because each crop's render+embed is independent. The Pallas path
    already blocks per (camera, window) and ignores it. Its patch-embed
    contraction follows the ambient matmul precision as the reference's
    conv does on a TPU: at the default, pixels and weights rounded to
    bfloat16 and summed in float32; at any other setting, float32 at
    HIGHEST.
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
                   block_k: int | None, use_kernel: bool | None,
                   interpret: bool) -> jnp.ndarray:
    args = (pos, size, kind, oid, windows, patch_params, noise)
    ref = partial(_reference, patch=patch, res=res, min_visible=min_visible,
                  dtype=dtype, block_k=block_k)
    ker = partial(_kernel, patch=patch, res=res, min_visible=min_visible,
                  dtype=dtype)
    if use_kernel is None:
        return jax.lax.platform_dependent(
            *args, tpu=partial(_per_device, partial(ker, interpret=False)),
            default=ref)
    return ker(*args, interpret=interpret) if use_kernel else ref(*args)


def _per_device(ker, pos, size, kind, oid, windows, patch_params, noise):
    """`ker` as is on one device; on a program partitioned over several,
    each device runs it on its own cameras (the fleet axis split over
    every axis of the mesh), since the compiler splits no Pallas call."""
    from jax.sharding import PartitionSpec as P

    mesh = getattr(jax.typeof(oid).sharding, "mesh", None)
    args = (pos, size, kind, oid, windows, patch_params, noise)
    if mesh is None or mesh.size <= 1:        # unsharded: an empty mesh
        return ker(*args)
    fleet, whole = P(mesh.axis_names), P()
    specs = (fleet, fleet, whole, fleet,
             fleet if windows.ndim == 3 else whole, whole,
             whole if noise is None else fleet)
    return jax.shard_map(ker, mesh=mesh, in_specs=specs, out_specs=fleet,
                         check_vma=False)(*args)


def _operand_dtype():
    """The contraction's operand dtype under the ambient matmul precision:
    bfloat16 for one bfloat16 pass, the default (what the TPU's
    default-precision conv rounds to); float32 contracted at HIGHEST
    for any other setting."""
    prec = jax.config.jax_default_matmul_precision
    return (jnp.bfloat16 if prec in (None, "default", "bfloat16",
                                     "BF16_BF16_F32")
            else jnp.float32)


def _reference(pos, size, kind, oid, windows, patch_params, noise, *,
               patch: int, res: int, min_visible: float, dtype,
               block_k: int | None) -> jnp.ndarray:
    ref = partial(crop_patchify_ref, pos, size, kind, oid,
                  patch_params=patch_params, patch=patch, res=res,
                  min_visible=min_visible, noise=noise, dtype=dtype)
    k = windows.shape[-2]
    if block_k is None or block_k >= k:
        return ref(windows=windows)
    # slab the window axis: the serial dimension only covers the cheap
    # render+embed; callers still batch the expensive model forward over
    # all K at once
    wblocks = jnp.moveaxis(
        windows.reshape(windows.shape[:-2] + (k // block_k, block_k, 4)),
        -3, 0)
    tok = jax.lax.map(lambda wb: ref(windows=wb), wblocks)
    return jnp.moveaxis(tok, 0, 1).reshape(
        (tok.shape[1], k) + tok.shape[3:])


def _kernel(pos, size, kind, oid, windows, patch_params, noise, *,
            patch: int, res: int, min_visible: float, dtype,
            interpret: bool) -> jnp.ndarray:
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
    w = patch_params["w"].astype(_operand_dtype())              # [p,p,3,D]
    wsplit = jnp.moveaxis(w, 2, 0).reshape(3, patch * patch, -1)
    bias = patch_params.get("b")
    bias = (jnp.zeros((1, w.shape[-1]), jnp.float32) if bias is None
            else bias.astype(jnp.float32)[None])
    r = pack_factor(g * g, patch * patch)
    if r > 1:
        # r patches a plane row: [gg, p*p] -> [gg/r, r*p*p] in row-major
        # order, contracted against r copies of the weights on the
        # diagonal, so that the [gg/r, r*D] rows are the [gg, D] tokens
        d = wsplit.shape[-1]
        rows, cols = (a.reshape(g * g // r, r * patch * patch)
                      for a in (rows, cols))
        bgn = bgn.reshape(f, 3, g * g // r, r * patch * patch)
        eye = jnp.eye(r, dtype=wsplit.dtype)
        wsplit = jnp.einsum("ab,cqd->caqbd", eye, wsplit).reshape(
            3, r * patch * patch, r * d)
        bias = jnp.tile(bias, (1, r))
    tok = crop_patchify_batch(rows, cols, boxes, colors, bgn, wsplit, bias,
                              interpret=interpret)
    if r > 1:
        tok = tok.reshape(tok.shape[:2] + (g * g, -1))
    return tok.astype(dtype)


def pack_factor(n_patches: int, patch_pixels: int, lanes: int = 128) -> int:
    """Patches the kernel paints side by side in one plane row: as many
    as fill the `lanes` of a vector register (1 where one patch does,
    as at patch 16), and a divisor of the patch count. At patch 4 a
    patch is 16 pixels, so 8 share a row and no lane is left empty."""
    r = max(1, lanes // patch_pixels)
    while n_patches % r:
        r -= 1
    return r
