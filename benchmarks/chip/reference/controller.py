"""Plain reference of MadEye's controller (paper sec. 3.3), numpy.

Each step a camera plans its budget (frames to send, cells it can
explore), evolves its search shape over the orientation grid (head/tail
swaps toward the cells whose boxes point away from the shape, a drift
or jump for a one-cell shape, grow or shrink to the budget, a scout
every few steps, a reseed when the last step saw nothing), shrinks the
shape until a rotation path through it fits the time budget (preorder
walk of the grid's minimum spanning tree restricted to the shape), and
picks each explored cell's zoom from the boxes it saw there last.

`replay` re-derives every camera-step's decisions teacher-forced: the
state before step e is rebuilt from the program's own outputs of the
earlier steps (explored cells, path, zooms, scores) and from the
reference's observations of the cells it explored (boxes, oracle
grade), so one differing step does not carry into the next. Ties go to
the first extremum and the lower cell id, as the paper's controller.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import world

f32 = np.float32
INF = f32(np.inf)
NET_WINDOW = 5
NEVER = -1000


# ---------------------------------------------------------------------------
# grid geometry
# ---------------------------------------------------------------------------

def best_rect(n_pan: int, n_tilt: int, size: int) -> tuple[int, int]:
    """Most square (w, h) with w * h <= size."""
    size = max(1, min(size, n_pan * n_tilt))
    best = (1, 1)
    for w in range(1, n_pan + 1):
        for h in range(1, n_tilt + 1):
            if w * h <= size and w * h > best[0] * best[1]:
                best = (w, h)
            elif w * h == best[0] * best[1] and abs(w - h) < abs(
                    best[0] - best[1]):
                best = (w, h)
    return best


def mst(dist: np.ndarray) -> np.ndarray:
    """[N, N] bool edges of Prim's minimum spanning tree from cell 0."""
    n = dist.shape[0]
    in_tree = np.zeros(n, bool)
    best = np.full(n, np.inf)
    parent = np.full(n, -1)
    best[0] = 0.0
    adj = np.zeros((n, n), bool)
    for _ in range(n):
        i = int(np.argmin(np.where(in_tree, np.inf, best)))
        in_tree[i] = True
        if parent[i] >= 0:
            adj[parent[i], i] = adj[i, parent[i]] = True
        better = (dist[i] < best) & ~in_tree
        best[better] = dist[i][better]
        parent[better] = i
    return adj


def geometry(config: dict) -> dict:
    g = {**world.GRID, **config["grid"]}
    n_pan, n_tilt = world.grid_shape(config["grid"])
    n = n_pan * n_tilt
    coords = np.stack([np.arange(n) % n_pan, np.arange(n) // n_pan], -1)
    centers = np.stack([(coords[:, 0] + 0.5) * g["pan_step"],
                        (coords[:, 1] + 0.5) * g["tilt_step"]], -1)
    dist = np.abs(centers[:, None] - centers[None]).max(-1)
    hops = np.abs(coords[:, None] - coords[None]).max(-1)
    fw, fh = g["fov_scale"] * g["pan_step"], g["fov_scale"] * g["tilt_step"]
    d = np.abs(centers[:, None] - centers[None])
    overlap = (np.maximum(0.0, fw - d[..., 0])
               * np.maximum(0.0, fh - d[..., 1])) / (fw * fh)
    ids = np.arange(n)
    return {
        "n": n, "n_pan": n_pan, "n_tilt": n_tilt, "grid": g,
        "coords": coords, "centers": centers.astype(f32),
        "dist": dist.astype(f32), "nbr8": hops == 1,
        "overlap": overlap.astype(f32),
        "d_center": np.linalg.norm(centers[:, None] - centers[None],
                                   axis=-1).astype(f32),
        "mst": mst(dist),
        # from u: every cell by descending distance, ties to the higher
        # id, so that popping a stack visits nearest and lower ids first
        "push": np.stack([np.lexsort((-ids, -dist[u])) for u in range(n)]),
        "rect": [best_rect(n_pan, n_tilt, s) for s in range(n + 1)],
    }


def onehot(i: int, n: int) -> np.ndarray:
    m = np.zeros(n, bool)
    m[i] = True
    return m


def reach(mask: np.ndarray, seed: np.ndarray, adj: np.ndarray):
    """Cells of `mask` reachable from `seed` over `adj`."""
    r = seed & mask
    while True:
        grown = mask & (r | adj[r].any(0))
        if (grown == r).all():
            return r
        r = grown


def contiguous(mask: np.ndarray, adj: np.ndarray) -> bool:
    if not mask.any():
        return True
    first = int(np.argmax(mask))
    return bool((~mask | reach(mask, onehot(first, len(mask)), adj)).all())


# ---------------------------------------------------------------------------
# one camera's step
# ---------------------------------------------------------------------------

def plan(c: dict, timestep: float, harmonic, rtt, train_acc, pred_var):
    """(frames to send, exploration seconds, cells to explore)."""
    risk = (f32(1.0) - train_acc) + pred_var
    k_risk = int(np.clip(1 + int(np.floor(risk / f32(0.20) + f32(1e-4))),
                         c["min_send"], c["max_send"]))
    per_extra = max(c["hop_degrees"] / c["rotation_speed"],
                    c["approx_infer_s"])
    ks = np.arange(c["min_send"], c["max_send"] + 1)
    kf = ks.astype(f32)
    send = rtt + (f32(c["frame_bytes"] * 8.0) * kf) / (harmonic * f32(1e6))
    t = f32(timestep) - send - f32(c["backend_infer_s"]) * kf
    extra = (t - f32(c["approx_infer_s"])) / f32(per_extra)
    mc = np.where(t > 0, 1 + np.floor(np.maximum(f32(0), extra)
                                      + f32(1e-4)).astype(np.int32), 1)
    ok = (mc >= ks) & (ks <= k_risk) & (ks > c["min_send"])
    if ok.any():
        i = int(np.flatnonzero(ok)[-1])
        return int(ks[i]), max(t[i], f32(0)), int(mc[i])
    return c["min_send"], max(t[0], f32(0)), max(int(mc[0]), c["min_send"])


def neighbour_scores(geo: dict, mask, has, cent, head):
    """Every cell's score as the overlap-weighted mean, over shape cells
    with boxes, of (distance to that cell's centre) / (distance to its
    boxes' centroid): above 1 where the boxes lean toward the cell; 1
    where no shape cell overlaps with boxes. Candidates: the head's
    lattice neighbours outside the shape."""
    w = geo["overlap"] * (mask & has).astype(f32)[None, :]
    dx = geo["centers"][:, None, 0] - cent[None, :, 0]
    dy = geo["centers"][:, None, 1] - cent[None, :, 1]
    ratio = geo["d_center"] / np.maximum(np.sqrt(dx * dx + dy * dy),
                                         f32(1e-6))
    total, total_w = (w * ratio).sum(-1), w.sum(-1)
    scores = np.where(total_w > 0, total / np.maximum(total_w, f32(1e-9)),
                      f32(1.0))
    return scores, geo["nbr8"][head] & ~mask


def best_candidate(scores, cand) -> tuple[int, float]:
    s = np.where(cand, scores, -INF)
    return int(np.argmax(s)), s.max()


def first_removable(geo: dict, mask, labels) -> int:
    """Lowest-label cell whose removal keeps the shape 8-connected, else
    the lowest-label cell."""
    order = np.argsort(np.where(mask, labels, INF), kind="stable")
    for t in order[:mask.sum()]:
        if contiguous(mask & ~onehot(t, geo["n"]), geo["nbr8"]):
            return int(t)
    return int(order[0])


def seed_rect(geo: dict, size: int, center: int) -> np.ndarray:
    w, h = geo["rect"][int(np.clip(size, 0, geo["n"]))]
    cp, ct = geo["coords"][center]
    p0 = int(np.clip(cp - w // 2, 0, geo["n_pan"] - w))
    t0 = int(np.clip(ct - h // 2, 0, geo["n_tilt"] - h))
    px, tx = geo["coords"][:, 0], geo["coords"][:, 1]
    return (px >= p0) & (px < p0 + w) & (tx >= t0) & (tx < t0 + h)


def evolve(c: dict, geo: dict, mask, labels, cent, has):
    m = int(mask.sum())
    n = geo["n"]
    if m == 0:
        return mask
    base = f32(c["base_threshold"])
    if m == 1:
        h = int(np.argmax(mask))
        lab_h = labels[h]
        best_global = int(np.argmax(labels))
        jump = best_global != h and labels.max() > lab_h * f32(2) * base
        scores, cand = neighbour_scores(geo, mask, has, cent, h)
        best, best_score = best_candidate(scores, cand)
        drift = (not jump and cand.any()
                 and (best_score > f32(1.05) or labels[best] > lab_h * base))
        if jump or drift:
            mask = (mask & ~onehot(h, n)) | onehot(
                best_global if jump else best, n)
        return mask
    # head/tail swaps over the members by descending label
    order = np.argsort(np.where(mask, -labels, INF), kind="stable")
    h_i, t_i, thresh, failed, swaps = 0, m - 1, base, False, 0
    for _ in range(2 * n + 2 * c["max_swaps"]):
        if h_i >= t_i or swaps >= c["max_swaps"]:
            break
        head, tail = int(order[h_i]), int(order[t_i])
        if not labels[head] / max(labels[tail], f32(1e-9)) > thresh:
            break
        scores, cand = neighbour_scores(geo, mask, has, cent, head)
        if not cand.any():
            if failed:
                break
            h_i, thresh, failed = h_i + 1, base, True
            continue
        best, _ = best_candidate(scores, cand)
        trial = (mask | onehot(best, n)) & ~onehot(tail, n)
        t_i -= 1
        if contiguous(trial, geo["nbr8"]):
            mask, failed, swaps = trial, False, swaps + 1
            thresh = thresh * f32(c["threshold_growth"])
    return mask


def resize(geo: dict, mask, labels, cent, has, target: int):
    target = int(np.clip(target, 1, geo["n"]))
    mask = mask.copy()
    while mask.sum() < target:
        free = geo["nbr8"][~mask].any(0)
        eligible = mask & free
        if not eligible.any():
            break
        head = int(np.argmax(np.where(eligible, labels, -INF)))
        scores, cand = neighbour_scores(geo, mask, has, cent, head)
        mask[best_candidate(scores, cand)[0]] = True
    while mask.sum() > target:
        mask[first_removable(geo, mask, labels)] = False
    return mask


def walk(geo: dict, mask, start: int):
    """Preorder walk of the spanning tree restricted to `mask` (its
    pieces joined by their cheapest edges), children nearest first, from
    `start` or the member nearest it. -> (order, seconds-in-degrees)."""
    n, dist = geo["n"], geo["dist"]
    if not mask.any():
        return [], f32(0)
    s = start if mask[start] else int(np.argmin(np.where(mask, dist[start],
                                                         INF)))
    tree = geo["mst"] & mask[:, None] & mask[None, :]
    done = reach(mask, onehot(s, n), tree)
    extra = np.zeros((n, n), bool)
    while (mask & ~done).any():
        rest = mask & ~done
        cross = np.where(done[:, None] & rest[None, :], dist, INF)
        u, v = divmod(int(np.argmin(cross)), n)
        done |= reach(rest, onehot(v, n), tree)
        extra[u, v] = extra[v, u] = True
    tree |= extra
    stack, seen, order = [s], np.zeros(n, bool), []
    while stack:
        u = stack.pop()
        seen[u] = True
        order.append(u)
        stack.extend(int(v) for v in geo["push"][u]
                     if tree[u, v] and not seen[v])
    prev = [start] + order[:-1]
    deg = f32(0)
    for a, b in zip(prev, order):
        deg = deg + dist[a, b]
    return order, deg


def shrink(c: dict, geo: dict, mask, start, labels, budget, per_cell):
    """Drop cells until the walk through the shape fits the budget."""
    mask = mask.copy()
    while True:
        order, deg = walk(geo, mask, start)
        t = deg / f32(c["rotation_speed"])
        if t + f32(per_cell) * f32(len(order)) <= budget or mask.sum() <= 1:
            return mask, order, t
        mask[first_removable(geo, mask, labels)] = False


def zoom(c: dict, geo: dict, dt: float, s: dict, explored):
    """Zoom index per cell from the boxes seen at its last visit: the
    deepest zoom whose view still holds their spread, extent and offset;
    back to the widest after `zoom_out_after` seconds zoomed in."""
    zi, zs = s["zoom"], s["zoomed"]
    timer = (zi > 0) & (zs + f32(dt) >= f32(c["zoom_out_after"]))
    cluster = s["nb_spread"] + s["nb_extent"]
    off = np.linalg.norm(s["nb_centroid"] - geo["centers"], axis=-1)
    g = geo["grid"]
    z_geo = np.zeros_like(zi)
    for i, z in enumerate(c["zoom_levels"]):
        half = min(g["fov_scale"] * g["pan_step"] / z,
                   g["fov_scale"] * g["tilt_step"] / z) / 2.0
        z_geo = np.where(cluster + off <= f32(c["margin"] * half), i, z_geo)
    z_new = np.where(timer | ~s["nb_has"], 0, z_geo)
    zs_new = np.where((z_new > 0) & (zi > 0), zs + f32(dt), f32(0))
    return (np.where(explored, z_new, zi).astype(np.int32),
            np.where(explored, zs_new, zs).astype(f32))


def labels_of(s: dict, c: dict):
    return np.maximum(s["acc"] + f32(c["delta_weight"]) * s["delta"],
                      f32(0)) + f32(1e-3)


def decide(c: dict, geo: dict, dt: float, s: dict, harmonic, rtt) -> dict:
    """One camera's decisions from its state before the step."""
    n = geo["n"]
    k_send, t_explore, max_cells = plan(c, dt, harmonic, rtt,
                                        s["train_acc"], s["pred_var"])
    labels = labels_of(s, c)
    stale = (s["step"] - s["last_visit"]).astype(f32)
    if not s["saw_objects"]:
        center = int(np.argmax(labels + f32(1e-4) * stale))
        shape = seed_rect(geo, max_cells, center)
        newly = shape
    else:
        shape = evolve(c, geo, s["shape"], labels, s["centroids"],
                       s["has_boxes"])
        shape = resize(geo, shape, labels, s["centroids"], s["has_boxes"],
                       max_cells)
        every = c["scout_every"]
        if every and max_cells == 1 and s["step"] % every == every - 1:
            score = labels + f32(1e-3) * np.sqrt(np.maximum(stale, f32(0)))
            shape = onehot(int(np.argmax(np.where(shape, -INF, score))), n)
        newly = shape & ~s["shape"]
    s = dict(s, zoom=np.where(newly, 0, s["zoom"]),
             zoomed=np.where(newly, f32(0), s["zoomed"]))
    hop_s = geo["grid"]["pan_step"] / c["rotation_speed"]
    per_cell = max(0.0, c["approx_infer_s"] - hop_s)
    budget = max(t_explore - f32(c["approx_infer_s"]),
                 f32(c["approx_infer_s"] + hop_s))
    explored, order, t = shrink(c, geo, shape, s["current"], labels,
                                budget, per_cell)
    zooms, _ = zoom(c, geo, dt, s, explored)
    return {"explored": explored, "order": order, "zooms": zooms,
            "newly": newly, "k_send": k_send, "path_time": t}


# ---------------------------------------------------------------------------
# teacher-forced replay over an episode
# ---------------------------------------------------------------------------

def initial_state(c: dict, geo: dict) -> dict:
    n = geo["n"]
    center = (geo["n_tilt"] // 2) * geo["n_pan"] + geo["n_pan"] // 2
    shape = seed_rect(geo, c["seed_size"], center)
    z = np.zeros(n, f32)
    return {"acc": z, "delta": z, "last": z, "seen": z, "shape": shape,
            "current": int(np.argmax(shape)),
            "zoom": np.zeros(n, np.int32), "zoomed": z,
            "centroids": np.zeros((n, 2), f32),
            "has_boxes": np.zeros(n, bool),
            "nb_centroid": np.zeros((n, 2), f32), "nb_spread": z,
            "nb_extent": z, "nb_has": np.zeros(n, bool),
            "train_acc": f32(c["train_acc0"]),
            "pred_var": f32(c["pred_var0"]), "saw_objects": True,
            "step": 0, "last_visit": np.full(n, NEVER, np.int32)}


def advance(c: dict, s: dict, out: dict, obs: dict, zoomed) -> dict:
    """The state after a step whose decisions and scores are the
    program's (`out`, one camera-step) and whose observations of the
    explored cells are the reference's (`obs`)."""
    ex = out["explored"].astype(bool)
    pred = out["pred_acc"].astype(f32)
    n_ex = int(out["n_explored"])
    a, b = f32(2.0 / 11.0), f32(1.0 - 2.0 / 11.0)
    first = (s["seen"] == 0) & ex
    acc = np.where(ex, np.where(first, pred, a * pred + b * s["acc"]),
                   s["acc"])
    delta = np.where(ex, np.where(first, f32(0), a * (pred - s["last"])
                                  + b * s["delta"]), s["delta"])
    acc = np.where(ex, acc, acc * f32(c["stale_decay"]))
    pos = np.full(len(ex), len(ex))
    pos[out["order"][:n_ex]] = np.arange(n_ex)

    def first_best(v):
        m = np.where(ex, v, -INF).max()
        return int(np.argmin(np.where(ex & (v == m), pos, len(ex) + 1)))

    agree = f32(first_best(pred) == first_best(obs["acc"]))
    kf = f32(max(n_ex, 1))
    mean = np.where(ex, pred, f32(0)).sum() / kf
    var = np.where(ex, (pred - mean) ** 2, f32(0)).sum() / kf
    has = obs["has"]
    return dict(
        s, acc=acc, delta=delta, last=np.where(ex, pred, s["last"]),
        seen=s["seen"] + ex.astype(f32), shape=ex,
        current=int(out["order"][n_ex - 1]) if n_ex > 0 else s["current"],
        zoom=out["zooms"].astype(np.int32), zoomed=zoomed,
        has_boxes=np.where(ex, has, s["has_boxes"]),
        centroids=np.where((ex & has)[:, None], obs["centroid"],
                           s["centroids"]),
        nb_centroid=np.where(ex[:, None], obs["centroid"],
                             s["nb_centroid"]),
        nb_spread=np.where(ex, obs["spread"], s["nb_spread"]),
        nb_extent=np.where(ex, obs["extent"], s["nb_extent"]),
        nb_has=np.where(ex, has, s["nb_has"]),
        train_acc=(f32(0.9) * s["train_acc"] + f32(0.1) * agree
                   if n_ex > 1 else s["train_acc"]),
        pred_var=var if n_ex > 1 else f32(0),
        saw_objects=bool((ex & has).any()), step=s["step"] + 1,
        last_visit=np.where(ex, s["step"] + 1, s["last_visit"]))


def harmonic_mean(samples: list) -> np.float32:
    inv = [f32(1) / max(f32(x), f32(1e-9)) for x in samples]
    total = f32(0)
    for v in inv:
        total = total + v
    return f32(len(samples)) / max(total, f32(1e-9))


def same(d: dict, o: dict) -> bool:
    """The reference's decisions `d` are the program's step `o`."""
    n_ex = int(o["n_explored"])
    return bool((d["explored"] == o["explored"].astype(bool)).all()
                and d["order"] == [int(x) for x in o["order"][:n_ex]]
                and (d["zooms"] == o["zooms"]).all()
                and d["k_send"] == int(o["k_send"])
                and abs(d["path_time"] - f32(o["path_time"])) <= 1e-5)


def replay(run, out: dict, obs: list) -> np.ndarray:
    """[E, F] bool: camera-steps whose explored cells, path, path time,
    zooms or send count differ from the reference controller's, each
    re-derived from the state the program's earlier outputs and the
    reference's observations give. `obs` is a list of observation
    sets, each holding [E, F, N, ...] arrays at each cell's zoom (has,
    centroid, spread, extent, acc); each set is replayed on its own and
    a step differs when it differs in every one."""
    c = run.config["controller"]
    geo = geometry(run.config)
    dt = 1.0 / float(run.traffic["fps"])
    mbps = max(f32(run.traffic["mbps"]), f32(1e-3))
    rtt = f32(run.traffic["rtt_ms"] / 1000.0)
    e_n, f_n = out["chosen"].shape
    bad = np.zeros((e_n, f_n), bool)
    for f in range(f_n):
        states = [initial_state(c, geo) for _ in obs]
        for e in range(e_n):
            harmonic = harmonic_mean([mbps] * min(e + 1, NET_WINDOW))
            o = {k: v[e, f] for k, v in out.items()}
            ds = [decide(c, geo, dt, s, harmonic, rtt) for s in states]
            bad[e, f] = not any(same(d, o) for d in ds)
            states = [advance(c, s, o, {k: v[e, f] for k, v in ob.items()},
                              zoomed_after(dt, s, o, d["newly"]))
                      for s, d, ob in zip(states, ds, obs)]
    return bad


def zoomed_after(dt: float, s: dict, o: dict, newly):
    """Seconds each cell has been zoomed in after the program's step:
    reset where the step's shape gained the cell (`newly`, the
    reference's), then advanced at the program's zooms where explored."""
    ex = o["explored"].astype(bool)
    zi = np.where(newly, 0, s["zoom"])
    zs = np.where(newly, f32(0), s["zoomed"])
    z_new = o["zooms"]
    zs_new = np.where((z_new > 0) & (zi > 0), zs + f32(dt), f32(0))
    return np.where(ex, zs_new, zs).astype(f32)
