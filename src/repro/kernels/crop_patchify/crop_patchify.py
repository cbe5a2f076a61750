"""Pallas kernel: fused rasterize -> ViT patch-embed for candidate crops.

The detector-in-step fast path scores [F, K] shortlisted candidate
windows per controller timestep. Unfused, every crop is rendered to an
HBM pixel buffer ([F, K, res, res, 3] — at 64 cameras x 18 windows x
224 px that is ~0.7 GB per step) only to be immediately contracted down
to [F, K, gg, D] patch embeddings by the backbone's patch-embed conv.
This kernel fuses the two: each grid step (one (camera, window) pair)
paints the crop in VMEM — same last-painter-wins/visibility/rounding
rules as scene_jax.render.render_crop — and contracts the patch tiles
against the patch-embed weights on the spot, so candidate crops never
round-trip through HBM as pixels; only the (res/p)^2 x D token rows are
written out.

The crop is painted directly in patch-tile layout: one [gg, p*p] plane
per colour channel, row t = patch (gy, gx), column q = pixel (py, px)
inside the patch, so the patch-embed is three [gg, p*p] x [p*p, D]
matmuls and no in-kernel reshape or transpose is needed. Where a patch
has fewer pixels than a row has lanes, ops.py hands in planes of r
patches a row ([gg/r, r*p*p]) and weights with r copies on the
diagonal ([r*p*p, r*D]); the kernel is the same. ops.py hands in
the pixel row/column of every (t, q) slot, the background+noise plane
already in that layout, and the per-(window, object) paint rectangles
and colours as scalars in SMEM. Painting walks the objects in slot
order, overwriting the planes where a rectangle covers them — last
painter wins, the same winner the reference's ownership reduction
picks, with no [M, res, res] intermediate and no vector gather. The
working set is a few [gg, p*p] f32 planes (~200 KB each at res 224),
far inside the scoped VMEM.

The contraction takes its operands in the weights' dtype and
accumulates in float32. ops.py picks that dtype from the ambient matmul
precision, so the kernel does what the convolution it replaces does: at
the default precision bfloat16 operands (one MXU pass), otherwise
float32 operands at HIGHEST.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(n_obj: int):
    def kernel(rr_ref, cc_ref, box_ref, col_ref, bgn_ref, w_ref, b_ref,
               out_ref):
        rr = rr_ref[...]                              # [gg, p*p] pixel row
        cc = cc_ref[...]                              # [gg, p*p] pixel col
        planes = [bgn_ref[0, c] for c in range(3)]    # [gg, p*p] each
        for m in range(n_obj):
            hit = ((rr >= box_ref[0, 0, 2, m]) & (rr < box_ref[0, 0, 3, m])
                   & (cc >= box_ref[0, 0, 0, m])
                   & (cc < box_ref[0, 0, 1, m]))
            planes = [jnp.where(hit, col_ref[0, c, m], planes[c])
                      for c in range(3)]
        exact = w_ref.dtype == jnp.float32
        tok = b_ref[...]                              # [1, D]
        for c in range(3):
            px = jnp.clip(planes[c], 0.0, 1.0).astype(w_ref.dtype)
            tok = tok + jnp.dot(
                px, w_ref[c], preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST if exact else None)
        out_ref[0, 0] = tok

    return kernel


def crop_patchify_batch(rows, cols, boxes, colors, bgn, wsplit, bias, *,
                        interpret: bool) -> jnp.ndarray:
    """rows/cols [gg, p*p] int32 pixel coordinates of each patch-tile
    slot; boxes [F, K, 4, M] int32 paint rectangles (px0, px1, py0, py1
    rows; an invisible object carries an empty one); colors [F, 3, M]
    f32 paint colours; bgn [F, 3, gg, p*p] background + noise in tile
    layout; wsplit [3, p*p, D] per-channel patch-embed weights in the
    contraction's operand dtype (bfloat16, or float32 contracted at
    HIGHEST); bias [1, D] f32. Returns tokens [F, K, gg, D] float32."""
    f, k, _, m = boxes.shape
    gg, pp = rows.shape
    d = wsplit.shape[-1]
    smem = pltpu.SMEM
    return pl.pallas_call(
        _make_kernel(m),
        grid=(f, k),
        in_specs=[
            pl.BlockSpec((gg, pp), lambda i, j: (0, 0)),
            pl.BlockSpec((gg, pp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, 4, m), lambda i, j: (i, j, 0, 0),
                         memory_space=smem),
            pl.BlockSpec((1, 3, m), lambda i, j: (i, 0, 0),
                         memory_space=smem),
            pl.BlockSpec((1, 3, gg, pp), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((3, pp, d), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gg, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, k, gg, d), jnp.float32),
        interpret=interpret,
    )(rows, cols, boxes, colors, bgn, wsplit, bias)
