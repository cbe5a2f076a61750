"""One fleet timestep: budget -> shape -> path -> zoom -> rank (Fig. 8).

Faithful fixed-shape reimplementation of MadEyeController.step over a
[F, n_cells] fleet batch. Each stage mirrors its numpy counterpart:

  _plan            core/tradeoff.plan_timestep   (closed form over the
                   static k in [min_send, max_send] instead of a loop)
  shape evolution  core/search via fleet/shape_ops (masked while-loops)
  _walk_one        core/path.PathPlanner.subtree_walk — induced-MST
                   preorder with deterministic stitch/tie rules, vmapped
  _shrink_to_budget core/path.shrink_to_budget
  _zoom            core/zoom.step on per-cell box summary statistics
  _rank            core/rank.predict_workload_accuracy + stable ranking

Tie-breaking matches the numpy implementation (first extremum / lower
cell id / earlier path position), so an F=1 fleet tracks the reference
controller decision for decision; tests/test_fleet_parity.py asserts it.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ewma
from repro.fleet import shape_ops
from repro.fleet.state import (
    NET_DEFAULT_MBPS,
    NET_WINDOW,
    FleetConfig,
    FleetState,
    FleetStatics,
    WorkloadSpec,
)
from repro.obs.trace import staged

INF = jnp.inf


class FleetObs(NamedTuple):
    """Per-timestep observation substrate.

    Tables are indexed [n_cells, n_zoom, ...] when the whole fleet shares
    one world (the host-precomputed EpisodeTables path) or
    [F, n_cells, n_zoom, ...] when every camera watches its own scene
    (the device-resident repro.scene_jax provider); the step gathers
    rank-aware. mbps/rtt are [] for a shared link or [F] for per-camera
    network traces. counts/areas/geometry may come from the teacher
    tables, the scene-oracle rasterizer, or the distilled detector's
    scored crops (DetectorProvider) — the step is provider-agnostic,
    which is the whole point of the seam: acc_true is always the
    oracle's grade of the chosen orientation."""
    counts: jnp.ndarray     # [(F,) N, Z, P] approx-model count per pair
    areas: jnp.ndarray      # [(F,) N, Z, P] summed box area per pair
    centroid: jnp.ndarray   # [(F,) N, Z, 2] bbox centroid (scene degrees)
    spread: jnp.ndarray     # [(F,) N, Z] box-center spread
    extent: jnp.ndarray     # [(F,) N, Z] max box side
    nbox: jnp.ndarray       # [(F,) N, Z] box count
    acc_true: jnp.ndarray   # [(F,) N, Z] oracle workload accuracy
    mbps: jnp.ndarray       # [] or [F] network sample this step
    rtt: jnp.ndarray        # [] or [F]


class FleetStepOut(NamedTuple):
    explored: jnp.ndarray   # [F, N] bool
    order: jnp.ndarray      # [F, N] int32 path order (-1 padded)
    n_explored: jnp.ndarray  # [F] int32
    zooms: jnp.ndarray      # [F, N] int32 zoom index per cell
    sent: jnp.ndarray       # [F, N] bool — shipped to the backend
    pred_acc: jnp.ndarray   # [F, N] predicted workload accuracy
    path_time: jnp.ndarray  # [F] seconds
    k_send: jnp.ndarray     # [F] int32
    chosen: jnp.ndarray     # [F] int32 — top-ranked explored cell
    acc_chosen: jnp.ndarray  # [F] oracle accuracy of the chosen cell


# ---------------------------------------------------------------------------
# budget (core/tradeoff.plan_timestep, closed form)
# ---------------------------------------------------------------------------

def _plan(cfg: FleetConfig, harmonic, rtt, train_acc, pred_var):
    risk = (1.0 - train_acc) + pred_var
    # same 1e-4 floor guard as core/tradeoff.frames_to_send (f32 and f64
    # must take the same branch on the 0.20-boundary risk values)
    k_risk = jnp.clip(1 + jnp.floor(risk / 0.20 + 1e-4).astype(jnp.int32),
                      cfg.min_send, cfg.max_send)
    hop_time = cfg.hop_degrees / cfg.rotation_speed
    per_extra = max(hop_time, cfg.approx_infer_s)
    ts = cfg.timestep

    karr = jnp.arange(cfg.min_send, cfg.max_send + 1)
    kf = karr.astype(jnp.float32)[None, :]          # [1, K]
    send_time = rtt[:, None] + (cfg.frame_bytes * 8.0 * kf) \
        / (harmonic[:, None] * 1e6)
    backend = cfg.backend_infer_s * kf
    if cfg.pipelined:
        fits = (send_time <= ts) & (backend <= ts)
        t_arr = jnp.where(
            fits, ts,
            ts - jnp.maximum(0.0, send_time - ts)
            - jnp.maximum(0.0, backend - ts))
    else:
        t_arr = ts - send_time - backend
    extra = (t_arr - cfg.approx_infer_s) / per_extra
    mc_arr = jnp.where(
        t_arr > 0,
        1 + jnp.floor(jnp.maximum(0.0, extra) + 1e-4).astype(jnp.int32),
        1)                                          # [F, K]
    feasible = ((mc_arr >= karr[None, :])
                & (karr[None, :] <= k_risk[:, None])
                & (karr[None, :] > cfg.min_send))
    any_f = jnp.any(feasible, axis=-1)
    best = jnp.max(jnp.where(feasible, karr[None, :], -1), axis=-1)
    pos = jnp.where(any_f, best - cfg.min_send, 0)
    k_send = jnp.where(any_f, best, cfg.min_send).astype(jnp.int32)
    t_explore = jnp.take_along_axis(t_arr, pos[:, None], -1)[:, 0]
    mc = jnp.take_along_axis(mc_arr, pos[:, None], -1)[:, 0]
    max_cells = jnp.where(any_f, mc, jnp.maximum(mc, cfg.min_send))
    return k_send, jnp.maximum(t_explore, 0.0), max_cells


# ---------------------------------------------------------------------------
# reachability: induced-MST preorder walk + shrink to the time budget
# ---------------------------------------------------------------------------

def _walk_one(statics: FleetStatics, mask, start):
    """core/path.subtree_walk for one camera. mask [N] bool, start [].

    Returns (order [N] int32 padded with -1, count [], path_time_deg []).
    path_time_deg is in degrees (caller divides by rotation speed).
    """
    n = mask.shape[0]
    dist = statics.dist
    m = jnp.sum(mask)

    masked_d = jnp.where(mask, dist[start], INF)
    start2 = jnp.where(mask[start], start, jnp.argmin(masked_d))
    induced = statics.mst_adj & mask[:, None] & mask[None, :]

    # stitch the components of the induced forest to start2's component
    # by the cheapest (row-major first) cross edge; usually 0 iterations
    seed = jax.nn.one_hot(start2, n, dtype=jnp.bool_)
    done = shape_ops.flood_reach(mask, seed, induced)

    def stitch_cond(carry):
        done, _ = carry
        return jnp.any(mask & ~done)

    def stitch_body(carry):
        done, extra = carry
        rest = mask & ~done
        cross = jnp.where(done[:, None] & rest[None, :], dist, INF)
        idx = jnp.argmin(cross.reshape(-1))
        u, v = idx // n, idx % n
        done = done | shape_ops.flood_reach(
            rest, jax.nn.one_hot(v, n, dtype=jnp.bool_), induced)
        extra = extra.at[u, v].set(True).at[v, u].set(True)
        return done, extra

    done, extra = lax.while_loop(
        stitch_cond, stitch_body, (done, jnp.zeros((n, n), bool)))
    tree = induced | extra

    # preorder DFS, children visited nearest-first (ties: lower cell id).
    # The push ordering (descending distance key) is static per grid
    # (statics.nbr_order), so each sequential loop iteration is gathers +
    # a cumsum — no sort.
    def dfs_cond(carry):
        return carry[1] > 0                         # stack non-empty

    def dfs_body(carry):
        stack, top, seen, order, cnt = carry
        u = stack[top - 1]
        top2 = top - 1
        seen2 = seen.at[u].set(True)
        order2 = order.at[cnt].set(u)
        cnt2 = cnt + 1

        row = statics.nbr_order[u]                  # push order (desc key)
        push = tree[u][row] & ~seen2[row]
        slots = jnp.where(push,
                          top2 + jnp.cumsum(push) - 1, n + 1)
        stack2 = stack.at[slots].set(row, mode="drop")
        k = jnp.sum(push)
        return (stack2, top2 + k.astype(jnp.int32), seen2, order2, cnt2)

    stack0 = jnp.zeros(n, jnp.int32).at[0].set(start2.astype(jnp.int32))
    top0 = (m > 0).astype(jnp.int32)
    order0 = jnp.full(n, -1, jnp.int32)
    _, _, _, order, cnt = lax.while_loop(
        dfs_cond, dfs_body,
        (stack0, top0, jnp.zeros(n, bool), order0,
         jnp.zeros((), jnp.int32)))

    ordc = jnp.maximum(order, 0)
    prev = jnp.concatenate([start[None].astype(jnp.int32), ordc[:-1]])
    hops = dist[prev, ordc]
    t_deg = jnp.sum(jnp.where(jnp.arange(n) < cnt, hops, 0.0))
    return order, cnt, t_deg


_walk = jax.vmap(_walk_one, in_axes=(None, 0, 0))


def _shrink_to_budget(cfg: FleetConfig, statics: FleetStatics, mask, start,
                      labels, budget_s, per_cell):
    """core/path.shrink_to_budget, batched. Returns (mask, order, cnt, t).

    The first walk runs outside the loop: when every camera's shape is
    already coverable (the common case) no removal work is issued at all.
    """
    f, n = mask.shape

    def feasible(mask, cnt, t):
        return (t + per_cell * cnt <= budget_s) | (jnp.sum(mask, -1) <= 1)

    order, cnt, t_deg = _walk(statics, mask, start)
    t = t_deg / cfg.rotation_speed
    done = feasible(mask, cnt, t)

    def cond(c):
        return jnp.any(~c["done"])

    def body(c):
        mask, done = c["mask"], c["done"]
        T = shape_ops.first_removable(mask, labels, statics.neighbor8)
        mask = jnp.where(~done[:, None],
                         mask & ~shape_ops._onehot(T, n), mask)
        order, cnt, t_deg = _walk(statics, mask, start)
        t = t_deg / cfg.rotation_speed
        ok = feasible(mask, cnt, t)
        newly = ~done & ok
        return {"mask": mask, "done": done | ok,
                "order": jnp.where(newly[:, None], order, c["order"]),
                "cnt": jnp.where(newly, cnt, c["cnt"]),
                "t": jnp.where(newly, t, c["t"])}

    out = lax.while_loop(cond, body, {"mask": mask, "done": done,
                                      "order": order, "cnt": cnt, "t": t})
    return out["mask"], out["order"], out["cnt"], out["t"]


# ---------------------------------------------------------------------------
# zoom (core/zoom.step on summary statistics)
# ---------------------------------------------------------------------------

def _zoom(cfg: FleetConfig, statics: FleetStatics, state: FleetState,
          explored):
    """Returns (zoom_idx, zoomed_since) advanced for explored cells."""
    dt = cfg.timestep
    zi, zs = state.zoom_idx, state.zoomed_since
    timer = (zi > 0) & (zs + dt >= cfg.zoom_out_after)

    cluster = state.nb_spread + state.nb_extent
    off = jnp.linalg.norm(state.nb_centroid - statics.centers[None], axis=-1)
    z_geo = jnp.zeros_like(zi)
    for i, z in enumerate(cfg.zoom_levels):
        fw = cfg.fov_scale * cfg.pan_step / z
        fh = cfg.fov_scale * cfg.tilt_step / z
        half = min(fw, fh) / 2.0
        fits = (cluster + off) <= cfg.margin * half
        z_geo = jnp.where(fits, i, z_geo)

    z_new = jnp.where(timer | ~state.nb_has, 0, z_geo).astype(jnp.int32)
    zs_new = jnp.where((z_new > 0) & (zi > 0), zs + dt, 0.0)
    zi_out = jnp.where(explored, z_new, zi)
    zs_out = jnp.where(explored, zs_new, zs)
    return zi_out, zs_out


# ---------------------------------------------------------------------------
# rank (core/rank, relative to the explored set)
# ---------------------------------------------------------------------------

def _rank(wl: WorkloadSpec, counts_g, areas_g, visits, explored):
    """counts_g/areas_g [F, N, P] at the chosen zoom; visits [F, N]
    (pre-update EWMA seen); explored [F, N]. -> pred_acc [F, N]."""
    total = None
    for q in range(len(wl.pair_idx)):
        cnt = jnp.where(explored, counts_g[..., wl.pair_idx[q]], 0.0)
        area = jnp.where(explored, areas_g[..., wl.pair_idx[q]], 0.0)
        task = wl.task_id[q]
        if task == 0:          # binary
            s = (cnt > 0).astype(jnp.float32)
        elif task == 1:        # count
            m = jnp.max(cnt, axis=-1, keepdims=True)
            s = jnp.where(m > 0, cnt / jnp.maximum(m, 1e-9), 0.0)
        elif task == 2:        # detect: count + area proxy
            m = jnp.max(cnt, axis=-1, keepdims=True)
            cs = jnp.where(m > 0, cnt / jnp.maximum(m, 1e-9), 0.0)
            am = jnp.max(area, axis=-1, keepdims=True)
            asc = jnp.where(am > 0, area / jnp.maximum(am, 1e-9), 0.0)
            s = 0.7 * cs + 0.3 * asc
        else:                  # agg_count: novelty-modulated
            m = jnp.max(cnt, axis=-1, keepdims=True)
            base = jnp.where(m > 0, cnt / jnp.maximum(m, 1e-9), 0.0)
            novelty = 1.0 / jnp.sqrt(1.0 + visits)
            s = base * (1.0 + novelty)
            sm = jnp.max(jnp.where(explored, s, 0.0), axis=-1, keepdims=True)
            s = jnp.where(sm > 0, s / jnp.maximum(sm, 1e-9), s)
        s = jnp.where(explored, s, 0.0)
        total = s if total is None else total + s
    return total / len(wl.pair_idx)


def gather_at_zoom(x: jnp.ndarray, zoom_idx: jnp.ndarray,
                   trailing: int = 0) -> jnp.ndarray:
    """Gather an observation table at each cell's chosen zoom.

    x is [N, Z, ...] (fleet-shared tables) or [F, N, Z, ...] (per-camera
    scenes) with `trailing` extra dims; zoom_idx [F, N]. Returns
    [F, N, ...]. The step's observe-at-chosen-zoom gather, shared with
    the in-scan metrics (repro.obs.metrics grades the chosen cell
    against the same oracle row the step saw).
    """
    f, n = zoom_idx.shape
    cell_ax = jnp.arange(n)[None, :]
    if x.ndim == 2 + trailing:                      # shared across fleet
        return x[cell_ax, zoom_idx]
    return x[jnp.arange(f)[:, None], cell_ax, zoom_idx]


# ---------------------------------------------------------------------------
# the timestep
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "wl"))
@staged("controller")
def fleet_step(cfg: FleetConfig, wl: WorkloadSpec, statics: FleetStatics,
               state: FleetState, obs: FleetObs
               ) -> tuple[FleetState, FleetStepOut]:
    f, n = state.shape.shape
    arange_f = jnp.arange(f)

    # 0. network observation (harmonic-mean window, core/tradeoff)
    slot = state.net_count % NET_WINDOW
    samples = state.net_samples.at[arange_f, slot].set(
        jnp.maximum(jnp.broadcast_to(obs.mbps, (f,)), 1e-3))
    net_count = state.net_count + 1
    n_s = jnp.minimum(net_count, NET_WINDOW)
    inv = jnp.where(jnp.arange(NET_WINDOW)[None, :] < n_s[:, None],
                    1.0 / jnp.maximum(samples, 1e-9), 0.0)
    harmonic = jnp.where(n_s > 0, n_s / jnp.maximum(inv.sum(-1), 1e-9),
                         NET_DEFAULT_MBPS)
    rtt = jnp.broadcast_to(obs.rtt, (f,))

    # 1. budget
    k_send, t_explore, max_cells = _plan(cfg, harmonic, rtt,
                                         state.train_acc, state.pred_var)

    # 2. shape: reseed on empty scene, else evolve + resize (+ scout)
    labels = ewma.labels(state.ewma, delta_weight=cfg.delta_weight)
    staleness = (state.step_idx[:, None] - state.last_visit).astype(
        jnp.float32)
    prev = state.shape

    reseed_center = jnp.argmax(labels + 1e-4 * staleness, axis=-1)
    shape_reseed = shape_ops.seed_shape(statics, cfg, max_cells,
                                        reseed_center)

    evolved = shape_ops.evolve_shape(cfg, statics, prev, labels,
                                     state.centroids, state.has_boxes)
    evolved = shape_ops.resize_shape(cfg, statics, evolved, labels,
                                     state.centroids, state.has_boxes,
                                     max_cells)
    if cfg.scout_every:
        scout_now = ((max_cells == 1)
                     & (state.step_idx % cfg.scout_every
                        == cfg.scout_every - 1))
        score = labels + 1e-3 * jnp.sqrt(jnp.maximum(staleness, 0.0))
        score = jnp.where(evolved, -INF, score)
        scout = jnp.argmax(score, axis=-1)
        evolved = jnp.where(scout_now[:, None],
                            shape_ops._onehot(scout, n), evolved)

    reseed = ~state.saw_objects
    shape = jnp.where(reseed[:, None], shape_reseed, evolved)
    newly = jnp.where(reseed[:, None], shape_reseed, shape & ~prev)
    zoom_idx = jnp.where(newly, 0, state.zoom_idx)
    zoomed_since = jnp.where(newly, 0.0, state.zoomed_since)
    state = state._replace(zoom_idx=zoom_idx, zoomed_since=zoomed_since)

    # 3. reachability: shrink until coverable in the exploration budget
    hop_s = cfg.pan_step / cfg.rotation_speed
    per_cell = max(0.0, cfg.approx_infer_s - hop_s)
    budget_s = jnp.maximum(t_explore - cfg.approx_infer_s,
                           cfg.approx_infer_s + hop_s)
    shape, order, cnt, path_time = _shrink_to_budget(
        cfg, statics, shape, state.current_cell, labels, budget_s, per_cell)
    explored = shape

    # path position per cell (for rank tie-breaking + feedback argmaxes)
    ordc = jnp.maximum(order, 0)
    idx = jnp.where(jnp.arange(n)[None, :] < cnt[:, None], ordc, n)
    pos = jnp.full((f, n), n, jnp.int32).at[
        arange_f[:, None], idx].set(
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (f, n)),
        mode="drop")

    # 4. zoom per explored cell (driven by last timestep's boxes)
    zoom_idx, zoomed_since = _zoom(cfg, statics, state, explored)

    # 5. observe at (cell, chosen zoom); tables are either fleet-shared
    # [N, Z, ...] or per-camera [F, N, Z, ...] (the scene-backed provider
    # generates the latter inside the scan) — rank decides the gather
    def at_zoom(x, trailing=0):
        return gather_at_zoom(x, zoom_idx, trailing)

    counts_g = at_zoom(obs.counts, 1)               # [F, N, P]
    areas_g = at_zoom(obs.areas, 1)
    o_centroid = at_zoom(obs.centroid, 1)           # [F, N, 2]
    o_spread = at_zoom(obs.spread)
    o_extent = at_zoom(obs.extent)
    o_has = at_zoom(obs.nbox) > 0
    true_g = at_zoom(obs.acc_true)                  # [F, N]

    # 6. rank explored orientations by predicted workload accuracy
    visits = state.ewma.seen
    pred = _rank(wl, counts_g, areas_g, visits, explored)

    # stable ranking by (-pred, path position) — matches rank_orientations
    # on the path-ordered numpy arrays. srank[c] = number of explored
    # cells strictly ahead of c; pairwise compare beats two sorts here.
    better = ((pred[:, None, :] > pred[:, :, None])
              | ((pred[:, None, :] == pred[:, :, None])
                 & (pos[:, None, :] < pos[:, :, None])))
    srank = jnp.sum(better & explored[:, None, :], axis=-1,
                    dtype=jnp.int32)                # rank of c among explored
    sent = explored & (srank < k_send[:, None])

    # 7. state updates (EWMA labels, stale decay, geometry, feedback)
    step_idx = state.step_idx + 1
    last_visit = jnp.where(explored, step_idx[:, None], state.last_visit)
    ew = ewma.update(state.ewma, explored, pred)
    ew = ewma.decay_unvisited(ew, explored, rate=cfg.stale_decay)

    has_boxes = jnp.where(explored, o_has, state.has_boxes)
    centroids = jnp.where((explored & o_has)[..., None], o_centroid,
                          state.centroids)
    nb_centroid = jnp.where(explored[..., None], o_centroid,
                            state.nb_centroid)
    nb_spread = jnp.where(explored, o_spread, state.nb_spread)
    nb_extent = jnp.where(explored, o_extent, state.nb_extent)
    nb_has = jnp.where(explored, o_has, state.nb_has)
    saw_objects = jnp.any(explored & o_has, axis=-1)

    # backend feedback: rank agreement on the truly-best explored cell
    k_cells = cnt
    mx_pred = jnp.max(jnp.where(explored, pred, -INF), axis=-1,
                      keepdims=True)
    best_pred = jnp.argmin(
        jnp.where(explored & (pred == mx_pred), pos, n + 1), axis=-1)
    mx_true = jnp.max(jnp.where(explored, true_g, -INF), axis=-1,
                      keepdims=True)
    best_true = jnp.argmin(
        jnp.where(explored & (true_g == mx_true), pos, n + 1), axis=-1)
    agree = (best_pred == best_true).astype(jnp.float32)
    train_acc = jnp.where(k_cells > 1,
                          0.9 * state.train_acc + 0.1 * agree,
                          state.train_acc)

    kf = jnp.maximum(k_cells, 1).astype(jnp.float32)
    mean_p = jnp.sum(jnp.where(explored, pred, 0.0), -1) / kf
    var_p = jnp.sum(jnp.where(explored, (pred - mean_p[:, None]) ** 2, 0.0),
                    -1) / kf
    pred_var = jnp.where(k_cells > 1, var_p, 0.0)

    current_cell = jnp.where(
        cnt > 0, ordc[arange_f, jnp.maximum(cnt - 1, 0)],
        state.current_cell).astype(jnp.int32)

    new_state = FleetState(
        ewma=ew, shape=shape, current_cell=current_cell,
        zoom_idx=zoom_idx, zoomed_since=zoomed_since,
        centroids=centroids, has_boxes=has_boxes,
        nb_centroid=nb_centroid, nb_spread=nb_spread,
        nb_extent=nb_extent, nb_has=nb_has,
        train_acc=train_acc, pred_var=pred_var,
        saw_objects=saw_objects, step_idx=step_idx,
        last_visit=last_visit, net_samples=samples,
        net_count=net_count, rtt=rtt, rng=state.rng)
    out = FleetStepOut(explored=explored, order=order, n_explored=cnt,
                       zooms=zoom_idx, sent=sent, pred_acc=pred,
                       path_time=path_time, k_send=k_send,
                       chosen=best_pred.astype(jnp.int32),
                       acc_chosen=true_g[arange_f, best_pred])
    return new_state, out
