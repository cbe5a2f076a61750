"""Correctness of a cell whose provider runs the approximation detector.

What the timed path produced, the last window call's per-step outputs,
is compared with the plain reference of the same seeds and the same
harness-made weights:

  oracle_mismatch    share of camera-steps whose oracle accuracy of the
                     chosen orientation differs from the reference
                     grade (world.py) by more than TOL: the scene and
                     the teachers
  score_mismatch     share of camera-steps where the predicted accuracy
                     of an explored cell lies outside the reference's
                     bounds (+- PRED_TOL): the shortlist, the crops and
                     the detector. The reference rebuilds the
                     search-coupled shortlist (the last shape, its
                     8-neighbour ring, then EWMA labels and staleness),
                     renders each explored window that was shortlisted,
                     runs the detector on it at the configuration's
                     precision (vision.py, through the backbone the
                     configuration names), counts boxes per query pair
                     and scores the cells (rank.py); a cell left off
                     the shortlist counts nothing
  decision_mismatch  share of camera-steps whose explored cells, path,
                     path time, zooms or send count differ from the
                     reference controller's (controller.py, teacher-
                     forced on the program's earlier steps and the
                     reference's boxes), or whose chosen cell or sent
                     set break the selection rule on the program's
                     scores (rank.py). The program's detector is known
                     only to within the gap measured between it and the
                     reference on the chip, and a box near the geometry
                     floor may fall either side of it: the controller
                     is replayed on the reference's boxes and on
                     OBS_DRAWS copies with every kept score and box
                     moved by a normal draw of SCORE_SIGMA and
                     BOX_SIGMA (from the seed), and a step differs when
                     it differs in every one

A count is a step function of a box's score, and the random-weight
detector puts boxes near the threshold, so a rounding step can flip a
count. The reference therefore gives each count the range it can take
when every score moves by up to BAND (and a class tie or the edge of
the kept boxes by as much), and scores each cell's lowest and highest
possible accuracy. The mean width of these bounds is printed beside the
numbers.

The control is the reference in bfloat16 put in the program's place:
its scores and oracle grades replace the program's, and the chosen cell
and sent set follow from them. A second reading puts only the detector
in bfloat16, over the float32 scene.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.reference import controller, rank, vision, world

TOL = 1e-5
PRED_TOL = 2e-3
BAND = 8e-3
OBS_DRAWS = 12
SCORE_SIGMA = 3e-3
BOX_SIGMA = 1e-3
N_ZOOM = 3
INFO_BANDS = (0.0, 3e-3, 1e-2)   # score_mismatch at these, printed only
ALPHA = 2.0 / 11.0          # EWMA over ~10 steps
DELTA_WEIGHT = 0.5
STALE_DECAY = 0.995
NEVER = -1000
BLOCK = 256                  # crops per reference call


def seed_shape(n_pan: int, n_tilt: int, size: int = 6) -> np.ndarray:
    """The most square rectangle of at most `size` cells around the grid
    centre (ties to the squarer), the controller's first search shape."""
    best = (1, 1)
    for w in range(1, n_pan + 1):
        for h in range(1, n_tilt + 1):
            if w * h <= size and w * h > best[0] * best[1]:
                best = (w, h)
            elif w * h == best[0] * best[1] and abs(w - h) < abs(
                    best[0] - best[1]):
                best = (w, h)
    w, h = best
    p0 = int(np.clip(n_pan // 2 - w // 2, 0, n_pan - w))
    t0 = int(np.clip(n_tilt // 2 - h // 2, 0, n_tilt - h))
    mask = np.zeros(n_pan * n_tilt, bool)
    for dp in range(w):
        for dt in range(h):
            mask[(t0 + dt) * n_pan + p0 + dp] = True
    return mask


def shortlists(out: dict, k_cells: int, n_pan: int, n_tilt: int):
    """[E, F, N] bool: the cells whose windows each step scores, rebuilt
    from the program's explored cells and scores of the earlier steps."""
    f32 = np.float32
    explored = out["explored"].astype(bool)
    pred = out["pred_acc"].astype(f32)
    e_n, f_n, n = explored.shape
    xy = np.stack(np.divmod(np.arange(n), n_pan), -1)
    nbr = (np.abs(xy[:, None] - xy[None]).max(-1) == 1).astype(f32)
    acc = np.zeros((f_n, n), f32)
    delta, last, seen = acc.copy(), acc.copy(), acc.copy()
    last_visit = np.full((f_n, n), NEVER, np.int32)
    shape = np.broadcast_to(seed_shape(n_pan, n_tilt), (f_n, n))
    a, b = f32(ALPHA), f32(1 - ALPHA)
    lists = np.zeros(explored.shape, bool)
    for e in range(e_n):
        labels = np.maximum(acc + f32(DELTA_WEIGHT) * delta, f32(0)) \
            + f32(1e-3)
        lnorm = labels / np.maximum(labels.max(-1, keepdims=True),
                                    f32(1e-9))
        stale = np.sqrt(np.maximum((e - last_visit).astype(f32), f32(0)))
        ring = (shape.astype(f32) @ nbr) > 0
        score = (f32(4) * shape + f32(2) * (ring & ~shape) + lnorm
                 + f32(1e-3) * stale)
        top = np.argsort(-score, axis=-1, kind="stable")[:, :k_cells]
        np.put_along_axis(lists[e], top, True, axis=-1)
        vis, p = explored[e], pred[e]
        first = (seen == 0) & vis
        acc = np.where(vis, np.where(first, p, a * p + b * acc), acc)
        delta = np.where(vis, np.where(first, f32(0),
                                       a * (p - last) + b * delta), delta)
        last = np.where(vis, p, last)
        seen = seen + vis
        acc = np.where(vis, acc, acc * f32(STALE_DECAY))
        last_visit = np.where(vis, e + 1, last_visit)
        shape = vis
    return lists


@partial(jax.jit, static_argnames=("sizes", "backbone", "scene_json",
                                   "dtype", "precision"))
def _detect_block(params, pos, size, oid, win, cam_key, frame, noise, *,
                  sizes, backbone, scene_json, dtype, precision):
    import json

    d = dict(json.loads(sizes))
    sc = json.loads(scene_json)
    bb = harness.load_backbone(backbone)

    def one(pos, size, oid, win, key, frame):
        nz = vision.noise_image(key, frame, d["img_res"], noise, dtype)
        crop = vision.render(pos.astype(dtype), size.astype(dtype), oid,
                             sc["max_people"], win.astype(dtype), nz,
                             res=d["img_res"],
                             min_visible=sc["min_visible"], dtype=dtype)
        return vision.detect(params, crop, bb, d, precision)

    return jax.vmap(one)(pos, size, oid, win, cam_key, frame)


def detections(run, snap: dict, items: np.ndarray, dtype,
               precision: str | None = None) -> tuple:
    """Reference detections for the windows `items` [(e, f, window)], by
    the backbone the configuration names, at its matmul precision unless
    `precision` is given."""
    import json

    keys = np.asarray(world.camera_keys(run.seeds["spec_seed"],
                                        run.seeds["scene_seeds"]))
    wins = world.windows(run.config["grid"])
    stride = world.stride(run.config, run.traffic)
    sizes = json.dumps(run.config["detector"], sort_keys=True)
    scene_json = json.dumps(run.config["scene"], sort_keys=True)
    noise = run.config["provider_kwargs"]["noise"]
    parts = []
    for s in range(0, len(items), BLOCK):
        blk = items[s:s + BLOCK]
        pad = BLOCK - len(blk)
        blk = np.concatenate([blk, np.repeat(blk[-1:], pad, 0)])
        e, f, w = blk.T
        res = _detect_block(
            run.weights, snap["pos"][e, f], snap["size"][e, f],
            snap["oid"][e, f], wins[w], keys[f],
            (e * stride).astype(np.int32), noise, sizes=sizes,
            backbone=run.config.get("backbone"), scene_json=scene_json,
            dtype=dtype,
            precision=precision or run.config["matmul_precision"])
        parts.append([np.asarray(x, np.float32)[:BLOCK - pad]
                      for x in res])
    return tuple(np.concatenate(p) for p in zip(*parts))


def counts_from(dets, wl: dict, thresh: float, band: float,
                max_boxes: int):
    """Per window and pair [W, P]: the fewest and the most boxes (and
    their summed normalized areas) that can count when every score may
    move by up to `band`. The detector keeps its `max_boxes` best cells;
    a kept box counts for a pair when its score clears the threshold
    and its top class is the pair's object."""
    scores, boxes, probs = dets                      # best first
    w, n = scores.shape
    k = min(max_boxes, n)
    pair_cls = np.asarray(wl["pair_cls"])[None, :, None]
    top2 = np.sort(probs, -1)
    sure_cls = (top2[..., -1] - top2[..., -2] >= band)[:, None, :]
    match = probs.argmax(-1)[:, None, :] == pair_cls
    sure = (scores >= thresh + band)[:, None, :] & match & sure_cls
    maybe = (scores >= thresh - band)[:, None, :] & (match | ~sure_cls)
    # a cell is surely kept when fewer than k others could outscore it,
    # surely dropped when k others surely do; between, `free` of the
    # undecided cells are kept, which ones is open
    srt = -scores                                    # ascending
    could_beat = np.stack([np.searchsorted(r, r + 2 * band, "left")
                           for r in srt]) - 1        # others within 2 band
    sure_beat = np.stack([np.searchsorted(r, r - 2 * band, "left")
                          for r in srt])
    kept = could_beat < k
    dropped = sure_beat >= k
    open_ = ~kept & ~dropped
    free = np.maximum(k - kept.sum(-1), 0)[:, None]  # [W, 1]
    n_open = open_.sum(-1)[:, None]
    area = (boxes[..., 2] * boxes[..., 3])[:, None, :]
    kp, op = kept[:, None, :], open_[:, None, :]
    sure_open = (sure & op).sum(-1)
    maybe_open = (maybe & op).sum(-1)
    c_lo = (sure & kp).sum(-1) + np.maximum(
        free - (n_open - sure_open), 0)
    c_hi = (maybe & kp).sum(-1) + np.minimum(free, maybe_open)
    a_lo = ((sure & kp) * area).sum(-1)
    a_hi = ((maybe & (kp | op)) * area).sum(-1)
    return (c_lo.astype(np.float32), c_hi.astype(np.float32),
            a_lo.astype(np.float32), a_hi.astype(np.float32))


def gap_midpoint(scores: np.ndarray, q: float, span: float
                 ) -> tuple[float, float]:
    """The float32 midpoint of the widest gap between neighbouring
    distinct scores whose quantile lies within q +- span, and that gap.
    Empty windows of one camera-frame are the same crop and score alike,
    so a plain quantile can land on a value that many windows share,
    where rounding alone decides which side a box falls on."""
    lo, hi = np.quantile(scores, [q - span, q + span])
    s = np.unique(scores)
    s = s[(s >= lo) & (s <= hi)]
    gaps = np.diff(s)
    j = int(np.argmax(gaps))
    return float(np.float32((s[j] + s[j + 1]) / 2)), float(gaps[j])


def prepare(run) -> None:
    """Set the detector's operating point from its weights, before the
    program is built: the count threshold and the geometry floor sit in
    the widest gap between the kept box scores near two quantiles, over
    sample windows of the episode (steps, cameras and windows drawn
    from the seed), rounded to float32 so that the program and the
    reference compare with the same number. Random weights score every
    window alike, so a fixed threshold counts every kept box for some
    seeds and none for others."""
    op = run.config.get("operating_point")
    if not op:
        return
    snap = reference_world(run)["snap"]
    rng = np.random.default_rng(run.seeds["weight_seed"])
    n = op["windows"]
    e_n, f_n = snap["oid"].shape[:2]
    items = np.stack([rng.integers(0, e_n, n), rng.integers(0, f_n, n),
                      rng.integers(0, len(world.windows(run.config["grid"])),
                                   n)], -1)
    k = run.config["detector"]["max_boxes"]
    scores = detections(run, snap, items, jnp.float32)[0][:, :k]
    kw = run.config["provider_kwargs"]
    kw["thresh"], run.thresh_gap = gap_midpoint(
        scores, op["thresh_quantile"], op["span"])
    kw["geo_thresh"], run.geo_gap = gap_midpoint(
        scores, op["geo_quantile"], op["span"])


def explored_windows(run, out: dict):
    """The explored cells whose window (at the program's zoom) was
    shortlisted: items [(e, f, window)] and their (e, f, cell) index."""
    n_pan, n_tilt = world.grid_shape(run.config["grid"])
    explored = out["explored"].astype(bool)
    k = run.traffic.get("shortlist_k") or n_pan * n_tilt * N_ZOOM
    lists = shortlists(out, k // N_ZOOM, n_pan, n_tilt)
    e, f, c = np.nonzero(explored & lists)
    w = c * N_ZOOM + out["zooms"][e, f, c]
    return np.stack([e, f, w], -1), (e, f, c)


def pred_bounds(run, dets, idx, out: dict, wl: dict, band: float):
    """Bounds [E, F, N] on the score of every explored cell."""
    explored = out["explored"].astype(bool)
    thresh = float(run.config["provider_kwargs"]["thresh"])
    shape = explored.shape + (len(wl["pairs"]),)
    per_pair = [np.zeros(shape, np.float32) for _ in range(4)]
    bounds = counts_from(dets, wl, thresh, band,
                         run.config["detector"]["max_boxes"])
    for full, part in zip(per_pair, bounds):
        full[idx] = part
    return rank.predicted_bounds(wl, *per_pair,
                                 rank.earlier_visits(explored), explored)


def observations(run, dets, items, idx, out: dict, acc: np.ndarray):
    """What the controller saw at each cell's zoom [E, F, N, ...]: its
    boxes above the geometry floor (any, centroid, spread, extent, in
    scene degrees) and the oracle grade."""
    f32 = np.float32
    k = run.config["detector"]["max_boxes"]
    scores, boxes = dets[0][:, :k], dets[1][:, :k]
    win = world.windows(run.config["grid"])[items[:, 2]][:, None]
    x = win[..., 0] + boxes[..., 0] * win[..., 2]
    y = win[..., 1] + boxes[..., 1] * win[..., 3]
    geo = (scores >= f32(run.config["provider_kwargs"]["geo_thresh"])
           ).astype(f32)
    nbox = geo.sum(-1)
    nb = np.maximum(nbox, f32(1e-9))
    cx, cy = (geo * x).sum(-1) / nb, (geo * y).sum(-1) / nb
    c2 = (geo * (x * x + y * y)).sum(-1) / nb
    has = nbox > 0
    side = np.maximum(boxes[..., 2] * win[..., 2], boxes[..., 3] * win[..., 3])
    shape = out["explored"].shape
    obs = {"has": np.zeros(shape, bool), "spread": np.zeros(shape, f32),
           "extent": np.zeros(shape, f32),
           "centroid": np.zeros(shape + (2,), f32),
           "acc": at_zoom(acc, out["zooms"])}
    obs["has"][idx] = has
    obs["centroid"][idx] = np.where(has[:, None], np.stack([cx, cy], -1), 0)
    obs["spread"][idx] = np.where(has, np.sqrt(np.maximum(
        c2 - cx * cx - cy * cy, 0)), 0)
    obs["extent"][idx] = np.where(geo > 0, side, 0).max(-1)
    return obs


def observation_sets(run, dets, items, idx, out: dict, acc: np.ndarray
                     ) -> list[dict]:
    """The reference's observations, then OBS_DRAWS more with every kept
    score moved by a normal draw of SCORE_SIGMA and every box by one of
    BOX_SIGMA, drawn from the seed."""
    rng = np.random.default_rng(run.seed)
    scores, boxes, probs = dets
    sets = [observations(run, dets, items, idx, out, acc)]
    for _ in range(OBS_DRAWS):
        moved = (scores + rng.normal(0.0, SCORE_SIGMA, scores.shape),
                 boxes + rng.normal(0.0, BOX_SIGMA, boxes.shape), probs)
        sets.append(observations(
            run, tuple(np.asarray(x, np.float32) for x in moved), items,
            idx, out, acc))
    return sets


def reference_world(run, dtype=jnp.float32, n_steps: int | None = None
                    ) -> dict:
    """The reference world's per-step oracle grades and object snapshots,
    as numpy [E, F, ...]."""
    keys = world.camera_keys(run.seeds["spec_seed"],
                             run.seeds["scene_seeds"])
    frozen = world.frozen_inputs(run.config, run.traffic,
                                 world.stride(run.config, run.traffic))
    with jax.default_matmul_precision("highest"):
        acc, snap = world.episode(
            frozen, keys, n_steps or run.traffic["n_steps"], dtype)
    return {"acc": np.asarray(acc, np.float32),
            "snap": jax.tree.map(np.asarray, snap)}


def at_zoom(x: np.ndarray, zooms: np.ndarray) -> np.ndarray:
    """x [E, F, C] over windows -> [E, F, N] at each cell's zoom (window
    = cell * Z + zoom)."""
    idx = np.arange(zooms.shape[-1]) * N_ZOOM + zooms
    return np.take_along_axis(x, idx, axis=-1)


def acc_at_chosen(acc: np.ndarray, out: dict) -> np.ndarray:
    """Oracle accuracy [E, F] of each step's chosen orientation."""
    z = np.take_along_axis(out["zooms"], out["chosen"][..., None], -1)
    w = out["chosen"] * N_ZOOM + z[..., 0]
    return np.take_along_axis(acc, w[..., None], -1)[..., 0]


def with_scores(out: dict, pred: np.ndarray, acc: np.ndarray) -> dict:
    """The program's outputs with its scores replaced by `pred`, and the
    chosen cell, sent set and oracle grade that follow from them."""
    explored = out["explored"].astype(bool)
    pos = rank.path_position(out["order"], out["n_explored"])
    chosen, sent = rank.select(pred, explored, pos, out["k_send"])
    o = dict(out, pred_acc=pred, chosen=chosen, sent=sent)
    o["acc_chosen"] = acc_at_chosen(acc, o)
    return o


def readings(run, control: str | None = None, band: float = BAND) -> dict:
    """The compared shares, then their parts (information only).

    `control` puts the reference in bfloat16 in the program's place:
    "reference" the scene, teachers and detector, "detector" the
    detector alone over the float32 scene."""
    out = run.outputs["out"]
    wl = world.workload_layout(run.config["workload"])
    ref = reference_world(run)
    items, idx = explored_windows(run, out)
    dets = detections(run, ref["snap"], items, jnp.float32)
    if control:
        low_ref = (reference_world(run, jnp.bfloat16)
                   if control == "reference" else ref)
        low = detections(run, low_ref["snap"], items, jnp.bfloat16)
        pred, _ = pred_bounds(run, low, idx, out, wl, band=0.0)
        out = with_scores(out, pred, low_ref["acc"])
    explored = out["explored"].astype(bool)
    pred = out["pred_acc"].astype(np.float32)

    def outside(b):
        lo, hi = pred_bounds(run, dets, idx, out, wl, b)
        bad = ((pred < lo - PRED_TOL) | (pred > hi + PRED_TOL)) & explored
        return bad.any(-1), np.nanmean(np.where(explored, hi - lo, np.nan))

    score_bad, width = outside(band)
    obs = observation_sets(run, dets, items, idx, out, ref["acc"])
    control_bad = controller.replay(run, out, obs)
    select_bad = rank.selection_errors(out)
    acc_gap = np.abs(out["acc_chosen"] - acc_at_chosen(ref["acc"], out))
    return {
        "oracle_mismatch": float(np.mean(~(acc_gap <= TOL))),
        "score_mismatch": float(np.mean(score_bad)),
        "decision_mismatch": float(np.mean(control_bad | select_bad)),
        "controller_mismatch": float(np.mean(control_bad)),
        "select_mismatch": float(np.mean(select_bad)),
        "pred_band_width": float(width),
        "thresh_gap": getattr(run, "thresh_gap", float("nan")),
        "geo_gap": getattr(run, "geo_gap", float("nan")),
        **{f"score_mismatch@{b}": float(np.mean(outside(b)[0]))
           for b in INFO_BANDS},
    }


def check_readings(run, r: dict) -> list[dict]:
    """Numbers with a limit in the configuration are compared; the rest
    go to stderr as information."""
    import sys

    limits = run.config["limits"]
    for k, v in r.items():
        if k not in limits:
            print(f"[check] {k} {v!r} (not compared)", file=sys.stderr,
                  flush=True)
    return [{"name": k, "value": r[k], "limit": v}
            for k, v in limits.items()]


def check(run) -> list[dict]:
    return check_readings(run, readings(run))
