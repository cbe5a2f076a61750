"""Plain reference of MadEye's ranking step (paper sec. 3.3), numpy.

`predicted` scores each explored orientation per query from the
approximation model's counts and box areas: binary (any object), count
(relative to the best explored cell), detect (0.7 count + 0.3 area, both
relative) and agg_count (count weighted up for rarely visited cells,
then relative); the workload score is the mean over queries.

`selection_errors` applies the selection rule to the program's own
scores: the chosen cell is the best score among the explored cells,
ties to the earlier cell on the path, and the `k_send` best are sent.
"""
from __future__ import annotations

import numpy as np


def predicted(wl: dict, counts, areas, visits, explored) -> np.ndarray:
    """counts/areas [..., N, P] at each cell's zoom, visits [..., N]
    earlier visits, explored [..., N] bool -> scores [..., N] float32."""
    f32 = np.float32
    total = np.zeros(explored.shape, f32)
    for q, p in enumerate(wl["pair_idx"]):
        cnt = np.where(explored, counts[..., p], 0).astype(f32)
        area = np.where(explored, areas[..., p], 0).astype(f32)
        task = wl["task"][q]

        def rel(x):
            m = x.max(-1, keepdims=True)
            return np.where(m > 0, x / np.maximum(m, f32(1e-9)), 0)

        if task == 0:
            s = (cnt > 0).astype(f32)
        elif task == 1:
            s = rel(cnt)
        elif task == 2:
            s = f32(0.7) * rel(cnt) + f32(0.3) * rel(area)
        else:
            s = rel(cnt) * (1 + 1 / np.sqrt(1 + visits.astype(f32)))
            sm = np.where(explored, s, 0).max(-1, keepdims=True)
            s = np.where(sm > 0, s / np.maximum(sm, f32(1e-9)), s)
        total = total + np.where(explored, s, 0).astype(f32)
    return (total / f32(len(wl["pair_idx"]))).astype(f32)


def earlier_visits(explored: np.ndarray) -> np.ndarray:
    """[E, F, N] -> visits before each step (exclusive cumulative sum)."""
    c = np.cumsum(explored.astype(np.float32), axis=0)
    return c - explored.astype(np.float32)


def path_position(order: np.ndarray, n_explored: np.ndarray) -> np.ndarray:
    """[..., N] position of each cell on the path, N when not on it (the
    padding of `order` lands in a spare column)."""
    n = order.shape[-1]
    on = np.arange(n) < n_explored[..., None]
    idx = np.where(on, np.clip(order, 0, n - 1), n)
    pos = np.full(order.shape[:-1] + (n + 1,), n, np.int32)
    np.put_along_axis(pos, idx, np.broadcast_to(
        np.arange(n, dtype=np.int32), order.shape), axis=-1)
    return pos[..., :n]


def select(pred, explored, pos, k_send):
    """Chosen cell and sent mask from scores by the selection rule."""
    n = pred.shape[-1]
    best = np.where(explored, pred, -np.inf).max(-1, keepdims=True)
    chosen = np.where(explored & (pred == best), pos, n + 1).argmin(-1)
    better = ((pred[..., None, :] > pred[..., :, None])
              | ((pred[..., None, :] == pred[..., :, None])
                 & (pos[..., None, :] < pos[..., :, None])))
    srank = (better & explored[..., None, :]).sum(-1)
    sent = explored & (srank < k_send[..., None])
    return chosen, sent


def selection_errors(out: dict) -> np.ndarray:
    """[E, F] bool: the step's path does not list exactly its explored
    cells, or its chosen cell or sent set break the selection rule."""
    explored = out["explored"].astype(bool)
    n = explored.shape[-1]
    nexp = out["n_explored"]
    pos = path_position(out["order"], nexp)
    listed = pos < n
    bad = (listed != explored).any(-1) | (explored.sum(-1) != nexp)
    chosen, sent = select(out["pred_acc"], explored, pos, out["k_send"])
    bad |= chosen != out["chosen"]
    bad |= (sent != out["sent"].astype(bool)).any(-1)
    return bad


def _rel_bounds(lo: np.ndarray, hi: np.ndarray, explored: np.ndarray):
    """Bounds of x_c / max over explored cells of x, where each x_c may
    be anything in [lo_c, hi_c]: the cell high and the others low, then
    the cell low and the others high. 0 where the max is 0."""
    f32 = np.float32
    lo = np.where(explored, lo, 0).astype(f32)
    hi = np.where(explored, hi, 0).astype(f32)

    def max_others(x):
        n = x.shape[-1]
        others = np.where(np.eye(n, dtype=bool), f32(0), x[..., None, :])
        return others.max(-1)

    m_hi = np.maximum(hi, max_others(lo))
    m_lo = np.maximum(lo, max_others(hi))
    up = np.where(m_hi > 0, hi / np.maximum(m_hi, f32(1e-9)), 0)
    down = np.where(m_lo > 0, lo / np.maximum(m_lo, f32(1e-9)), 0)
    return down.astype(f32), up.astype(f32)


def predicted_bounds(wl: dict, c_lo, c_hi, a_lo, a_hi, visits, explored):
    """Lowest and highest score [..., N] each explored cell can take when
    its counts and areas per pair [..., N, P] are anywhere in [lo, hi]."""
    f32 = np.float32
    low = np.zeros(explored.shape, f32)
    high = np.zeros(explored.shape, f32)
    for q, p in enumerate(wl["pair_idx"]):
        task = wl["task"][q]
        if task == 0:
            d, u = (c_lo[..., p] > 0).astype(f32), (c_hi[..., p] > 0)
        elif task == 1:
            d, u = _rel_bounds(c_lo[..., p], c_hi[..., p], explored)
        elif task == 2:
            cd, cu = _rel_bounds(c_lo[..., p], c_hi[..., p], explored)
            ad, au = _rel_bounds(a_lo[..., p], a_hi[..., p], explored)
            d, u = f32(0.7) * cd + f32(0.3) * ad, f32(0.7) * cu + f32(0.3) * au
        else:
            w = 1 + 1 / np.sqrt(1 + visits.astype(f32))
            d, u = _rel_bounds(c_lo[..., p] * w, c_hi[..., p] * w, explored)
        low += np.where(explored, d, 0)
        high += np.where(explored, u, 0)
    q = f32(len(wl["pair_idx"]))
    return low / q, high / q
