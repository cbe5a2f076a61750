"""jit'd wrappers: fleet-batched neighbor scoring with padding + dispatch.

`neighbor_scores` accepts the controller-native layout (shape mask, per-cell
centroids, head cell per camera) and returns (scores, candidate mask) over
the un-padded grid. The heavy [B, N, N] reduction dispatches to the Pallas
kernel (padded to 128 lanes) or to the pure-jnp reference — the reference
path is the default inside fused fleet steps (XLA fuses it into the
surrounding program), the kernel path (use_kernel=True) is for TPU
serving where the scoring batch dominates. The kernel runs in the Pallas
interpreter only on the CPU backend (repro.kernels.interpret_mode).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.neighbor_score.neighbor_score import neighbor_score_batch
from repro.kernels.neighbor_score.ref import neighbor_scores_ref

LANES = 128


def geometry_arrays(grid) -> dict:
    """Static per-grid geometry (numpy) consumed by the scorer.

    d_center/overlap are [N, N]; neighbor8 is the 8-connected candidate
    adjacency; cell_x/cell_y are [N] centers. Cached by the caller
    (repro.fleet.state builds it once per FleetStatics).
    """
    centers = np.asarray(grid.centers, np.float32)
    d_center = np.linalg.norm(
        centers[:, None, :] - centers[None, :, :], axis=-1
    ).astype(np.float32)
    return {
        "d_center": d_center,
        "overlap": np.asarray(grid.overlap_matrix, np.float32),
        "neighbor8": np.asarray(grid.neighbor_mask, bool),
        "cell_x": centers[:, 0].copy(),
        "cell_y": centers[:, 1].copy(),
    }


def _pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def neighbor_scores(shape_mask: jnp.ndarray, has_boxes: jnp.ndarray,
                    centroids: jnp.ndarray, head: jnp.ndarray,
                    d_center: jnp.ndarray, overlap: jnp.ndarray,
                    cell_x: jnp.ndarray, cell_y: jnp.ndarray,
                    neighbor8: jnp.ndarray, *, use_kernel: bool = False,
                    block_b: int = 64):
    """shape_mask/has_boxes [B, N] bool; centroids [B, N, 2]; head [B] int;
    geometry [N, N] / [N]. -> (scores [B, N] f32, cand [B, N] bool).

    Scores match core/neighbor.score_candidates on candidate cells;
    non-candidates are scored too (same formula) and masked by `cand`.
    """
    return _neighbor_scores(shape_mask, has_boxes, centroids, head,
                            d_center, overlap, cell_x, cell_y, neighbor8,
                            use_kernel=use_kernel,
                            interpret=interpret_mode(), block_b=block_b)


@partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_b"))
def _neighbor_scores(shape_mask, has_boxes, centroids, head, d_center,
                     overlap, cell_x, cell_y, neighbor8, *,
                     use_kernel: bool, interpret: bool, block_b: int):
    B, N = shape_mask.shape
    member_has = (shape_mask & has_boxes).astype(jnp.float32)
    cent_x = centroids[..., 0]
    cent_y = centroids[..., 1]

    if use_kernel:
        if N > LANES:
            raise ValueError(
                f"neighbor_score kernel supports up to {LANES} grid cells "
                f"(one lane tile), got {N}; use the reference path")
        Bp = -(-B // block_b) * block_b
        mh = jnp.pad(member_has, ((0, Bp - B), (0, LANES - N)))
        cx = jnp.pad(cent_x, ((0, Bp - B), (0, LANES - N)))
        cy = jnp.pad(cent_y, ((0, Bp - B), (0, LANES - N)))
        scores = neighbor_score_batch(
            mh, cx, cy,
            _pad2(d_center, LANES, LANES), _pad2(overlap, LANES, LANES),
            _pad2(jnp.broadcast_to(cell_x[:, None], (N, N)), LANES, LANES),
            _pad2(jnp.broadcast_to(cell_y[:, None], (N, N)), LANES, LANES),
            block_b=block_b, interpret=interpret)[:B, :N]
    else:
        scores = neighbor_scores_ref(member_has, cent_x, cent_y,
                                     d_center, overlap, cell_x, cell_y)
    cand = neighbor8[head] & ~shape_mask
    return scores, cand
