"""Plain reference of the world the cameras watch and of its oracle.

The scene is the benchmark's input: objects that walk and drive through
a 150 x 75 degree panorama, drawn from per-camera `jax.random` keys so
that the program and this reference see the same world from the same
seeds. The dynamics are MadEye's procedural scene (people random-walk
between points of interest, cars cross on tilted lanes, churn
respawns), written out plainly per camera and vmapped over the fleet.

The oracle is the paper's teacher grading: for every orientation window
a teacher detects an object when it is visible (clipped area / object
area >= min_visible) and a hash draw falls under a ramp of its apparent
size. A query's accuracy at a window is its teacher count
relative to the best window of that step (binary queries: any object).

Every float array is kept in `dtype`: float32 for the reference, a
lower precision for the control.
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PERSON, CAR = 0, 1
OBJ_IDS = {"person": PERSON, "car": CAR}
TASKS = ("binary", "count", "detect", "agg_count")
POI_SALT = 0x5CE7E
BASE_SALT = 0xBA5E
RENDER_SALT = 0x9E4DE

TEACHERS = json.loads(
    (Path(__file__).with_name("teachers.json")).read_text())


# ---------------------------------------------------------------------------
# static layout: grid windows, workload pairs, teacher constants
# ---------------------------------------------------------------------------

GRID = {"pan_extent": 150.0, "tilt_extent": 75.0, "pan_step": 30.0,
        "tilt_step": 15.0, "fov_scale": 2.0}


def grid_shape(grid: dict) -> tuple[int, int]:
    """(pan cells, tilt cells); the default grid is 150 x 75 degrees in
    30 x 15 degree steps."""
    g = {**GRID, **grid}
    return (int(round(g["pan_extent"] / g["pan_step"])),
            int(round(g["tilt_extent"] / g["tilt_step"])))


def windows(grid: dict, zoom_levels=(1.0, 2.0, 3.0)) -> np.ndarray:
    """[N * Z, 4] (x0, y0, w, h) in degrees, cell-major: window
    cell * Z + zoom, with a zoom-1 field of view of two steps."""
    g = {**GRID, **grid}
    n_pan, n_tilt = grid_shape(grid)
    rows = []
    for cell in range(n_pan * n_tilt):
        cx = (cell % n_pan + 0.5) * g["pan_step"]
        cy = (cell // n_pan + 0.5) * g["tilt_step"]
        for z in zoom_levels:
            fw = g["fov_scale"] * g["pan_step"] / z
            fh = g["fov_scale"] * g["tilt_step"] / z
            rows.append((cx - fw / 2, cy - fh / 2, fw, fh))
    return np.asarray(rows, np.float32)


def workload_layout(queries) -> dict:
    """Distinct (model, object) pairs in query order, each query's pair
    column and task, each pair's object class."""
    pairs = []
    for model, obj, _ in queries:
        if (model, obj) not in pairs:
            pairs.append((model, obj))
    return {"pairs": pairs,
            "pair_idx": [pairs.index((m, o)) for m, o, _ in queries],
            "task": [TASKS.index(t) for _, _, t in queries],
            "pair_cls": [OBJ_IDS[o] for _, o in pairs]}


def _fnv32(*keys) -> int:
    h = 2166136261
    for b in "|".join(map(str, keys)).encode():
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def _fnv64_01(*keys) -> float:
    h = 1469598103934665603
    for b in "|".join(map(str, keys)).encode():
        h ^= b
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return (h & 0xFFFFFFFF) / 2 ** 32


def teacher_constants(pairs) -> dict:
    """Per pair: quirked apparent-size floor and saturation, plateau,
    flicker weight, class and hash salt (numpy)."""
    out = {k: [] for k in ("a0", "a1", "pmax", "flicker", "cls", "salt")}
    for model, obj in pairs:
        t = TEACHERS[model]
        cls = OBJ_IDS[obj]
        quirk = 0.85 + 0.3 * _fnv64_01(model, "quirk", cls)
        out["a0"].append(t["a_min"] * quirk)
        out["a1"].append(t["a_sat"] * quirk)
        out["pmax"].append(t["p_max"])
        out["flicker"].append(t["flicker"])
        out["cls"].append(cls)
        out["salt"].append(_fnv32(model, obj))
    return {"a0": np.float32(out["a0"]), "a1": np.float32(out["a1"]),
            "pmax": np.float32(out["pmax"]),
            "flicker": np.float32(out["flicker"]),
            "cls": np.int32(out["cls"]), "salt": np.uint32(out["salt"])}


def hash01(*ints):
    """Uniform [0, 1) from integer arrays (FNV seed, xxhash-style mix)."""
    h = jnp.uint32(0x811C9DC5)
    for x in ints:
        h = h ^ jnp.asarray(x).astype(jnp.uint32)
        h = h * jnp.uint32(0x9E3779B1)
        h = h ^ (h >> 15)
        h = h * jnp.uint32(0x85EBCA77)
        h = h ^ (h >> 13)
    return h.astype(jnp.float32) * jnp.float32(2.0 ** -32)


# ---------------------------------------------------------------------------
# the scene, one camera
# ---------------------------------------------------------------------------

def camera_keys(seed: int, scene_seeds) -> jnp.ndarray:
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda s: jax.random.fold_in(base, s))(
        jnp.asarray(scene_seeds, jnp.int32))


def _norm(v):
    return jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))


def _spawn(sc: dict, poi, person_speed, car_speed, key, dtype):
    """Fresh draws for every slot: people near a point of interest
    heading to another, cars entering a lane from either side."""
    m = sc["max_people"] + sc["max_cars"]
    ks = jax.random.split(key, 8)
    extent = jnp.asarray(sc["extent"], dtype)
    pos_p = jnp.clip(
        poi[jax.random.randint(ks[0], (m,), 0, sc["n_poi"])]
        + 8.0 * jax.random.normal(ks[1], (m, 2), dtype),
        jnp.asarray([1.0, 1.0], dtype), extent - 1.0)
    wp_p = poi[jax.random.randint(ks[2], (m,), 0, sc["n_poi"])]
    speed_p = jnp.maximum(
        0.2, person_speed + 0.4 * jax.random.normal(ks[3], (m,), dtype))
    d = wp_p - pos_p
    vel_p = speed_p[:, None] * d / jnp.maximum(_norm(d), 1e-6)
    w_p = jax.random.uniform(ks[4], (m,), dtype, sc["person_size"][0],
                             sc["person_size"][1])
    size_p = jnp.stack([w_p * 0.45, w_p], -1)
    lanes = jnp.asarray(sc["lane_tilts"], dtype)
    lane = lanes[jax.random.randint(ks[5], (m,), 0, len(sc["lane_tilts"]))]
    u = jax.random.uniform(ks[6], (m, 4), dtype)
    direction = jnp.where(u[:, 0] < 0.5, -1.0, 1.0).astype(dtype)
    x0 = jnp.where(direction > 0, 0.0, sc["extent"][0]).astype(dtype)
    x0_init = u[:, 1] * sc["extent"][0]
    tilt = lane + (u[:, 2] - 0.5) * 2.0 * 1.73
    speed_c = jnp.maximum(
        2.0, car_speed + 2.5 * jax.random.normal(ks[7], (m,), dtype))
    vel_c = jnp.stack([direction * speed_c, jnp.zeros_like(speed_c)], -1)
    lo, hi = sc["car_size"]
    w_c = lo + u[:, 3] * (hi - lo)
    size_c = jnp.stack([w_c, w_c * 0.45], -1)
    return {"pos_p": pos_p, "wp_p": wp_p, "vel_p": vel_p, "size_p": size_p,
            "x0": x0, "x0_init": x0_init, "tilt": tilt, "vel_c": vel_c,
            "size_c": size_c}


def _params(sc: dict, traffic: dict, key, dtype):
    m = sc["max_people"] + sc["max_cars"]
    lo = jnp.asarray([15.0, 10.0], dtype)
    hi = jnp.asarray([sc["extent"][0] - 15.0, sc["extent"][1] - 10.0],
                     dtype)
    poi = jax.random.uniform(jax.random.fold_in(key, POI_SALT),
                             (sc["n_poi"], 2), dtype, lo, hi)
    idx = np.arange(m)
    enabled = np.where(idx < sc["max_people"], idx < traffic["n_people"],
                       idx - sc["max_people"] < traffic["n_cars"])
    person = idx < sc["max_people"]
    return poi, jnp.asarray(enabled), jnp.asarray(person)


def init_camera(sc: dict, traffic: dict, key, dtype):
    poi, enabled, person = _params(sc, traffic, key, dtype)
    m = person.shape[0]
    d = _spawn(sc, poi, traffic["person_speed"], traffic["car_speed"], key,
               dtype)
    pos = jnp.where(person[:, None], d["pos_p"],
                    jnp.stack([d["x0_init"], d["tilt"]], -1))
    vel = jnp.where(person[:, None], d["vel_p"], d["vel_c"])
    size = jnp.where(person[:, None], d["size_p"], d["size_c"])
    off = ~enabled
    return {"pos": jnp.where(off[:, None], -1000.0, pos).astype(dtype),
            "vel": jnp.where(off[:, None], 0.0, vel).astype(dtype),
            "size": jnp.where(off[:, None], 0.0, size).astype(dtype),
            "waypoint": d["wp_p"],
            "oid": jnp.arange(m, dtype=jnp.int32),
            "next_id": jnp.asarray(m, jnp.int32)}


def step_camera(sc: dict, traffic: dict, cam_key, frame, s: dict, dtype):
    """One scene frame of one camera, keyed fold_in(camera key, frame)."""
    poi, enabled, person = _params(sc, traffic, cam_key, dtype)
    m = person.shape[0]
    extent = jnp.asarray(sc["extent"], dtype)
    dt = 1.0 / sc["fps"]
    key = jax.random.fold_in(cam_key, frame)
    k_wp, k_jit, k_churn, k_spawn = jax.random.split(key, 4)

    pos = s["pos"] + s["vel"] * dt
    d = s["waypoint"] - pos
    arrived = _norm(d)[:, 0] < 2.0
    kw1, kw2 = jax.random.split(k_wp)
    new_wp = (poi[jax.random.randint(kw1, (m,), 0, sc["n_poi"])]
              + 6.0 * jax.random.normal(kw2, (m, 2), dtype))
    waypoint = jnp.where((person & arrived)[:, None], new_wp, s["waypoint"])
    d = waypoint - pos
    speed = _norm(s["vel"])
    v = (speed * d / jnp.maximum(_norm(d), 1e-6)
         + 0.3 * jax.random.normal(k_jit, (m, 2), dtype))
    vel_p = v / jnp.maximum(_norm(v), 1e-6) * speed
    vel = jnp.where(person[:, None], vel_p, s["vel"])
    pos = jnp.where(person[:, None], jnp.clip(pos, 0.0, extent), pos)

    churn = person & (jax.random.uniform(k_churn, (m,), dtype)
                      < traffic["churn"] * dt * sc["fps"])
    gone = ~person & ((pos[:, 0] < -3.0)
                      | (pos[:, 0] > sc["extent"][0] + 3.0))
    respawn = (churn | gone) & enabled
    sd = _spawn(sc, poi, traffic["person_speed"], traffic["car_speed"],
                k_spawn, dtype)
    sp_pos = jnp.where(person[:, None], sd["pos_p"],
                       jnp.stack([sd["x0"], sd["tilt"]], -1))
    sp_vel = jnp.where(person[:, None], sd["vel_p"], sd["vel_c"])
    sp_size = jnp.where(person[:, None], sd["size_p"], sd["size_c"])
    r = respawn[:, None]
    new_ids = s["next_id"] + jnp.cumsum(respawn.astype(jnp.int32)) - 1
    return {"pos": jnp.where(r, sp_pos, pos).astype(dtype),
            "vel": jnp.where(r, sp_vel, vel).astype(dtype),
            "size": jnp.where(r, sp_size, s["size"]).astype(dtype),
            "waypoint": jnp.where(r, sd["wp_p"], waypoint).astype(dtype),
            "oid": jnp.where(respawn, new_ids, s["oid"]),
            "next_id": s["next_id"] + jnp.sum(respawn, dtype=jnp.int32)}


# ---------------------------------------------------------------------------
# the oracle grade of every window, one camera
# ---------------------------------------------------------------------------

def observe_camera(sc: dict, teach: dict, wl: dict, wins, s: dict,
                   enabled, cam_salt, t, dtype):
    """The oracle workload accuracy [C] of every window at frame t."""
    m = s["oid"].shape[0]
    kind = jnp.asarray(np.arange(m) >= sc["max_people"], jnp.int32)
    flick = jnp.asarray(teach["flicker"], dtype)[:, None]
    salt = jnp.asarray(teach["salt"])[:, None]
    oid = s["oid"][None, :]
    draw = ((1.0 - flick) * hash01(oid, salt, cam_salt, BASE_SALT)
            + flick * hash01(oid, salt, cam_salt,
                             t // sc["flicker_bucket"]))
    draw = draw / jnp.maximum(jnp.asarray(teach["pmax"], dtype)[:, None],
                              1e-6)                         # [P, M]
    live = enabled[None, :] & (jnp.asarray(teach["cls"])[:, None]
                               == kind[None, :])
    teacher = jnp.where(live, draw, 2.0)

    w = jnp.asarray(wins, dtype)
    x0, y0, fw, fh = (w[None, :, i] for i in range(4))     # [1, C]
    ox, oy = s["pos"][:, 0:1], s["pos"][:, 1:2]             # [M, 1]
    ow, oh = s["size"][:, 0:1], s["size"][:, 1:2]
    iw = jnp.maximum(jnp.minimum(ox + ow / 2, x0 + fw)
                     - jnp.maximum(ox - ow / 2, x0), 0.0)   # [M, C]
    ih = jnp.maximum(jnp.minimum(oy + oh / 2, y0 + fh)
                     - jnp.maximum(oy - oh / 2, y0), 0.0)
    visible = iw * ih / jnp.maximum(ow * oh, 1e-9) >= sc["min_visible"]
    apparent = jnp.maximum(iw / fw, ih / fh)
    a0 = jnp.asarray(teach["a0"], dtype)[:, None, None]
    a1 = jnp.asarray(teach["a1"], dtype)[:, None, None]
    ramp = jnp.clip((apparent[None] - a0) / jnp.maximum(a1 - a0, 1e-6),
                    0.0, 1.0)                               # [P, M, C]

    cnt_t = ((teacher[:, :, None] < ramp) & visible[None]).astype(
        dtype).sum(1)                                       # [P, C]
    acc = 0.0
    for q, p in enumerate(wl["pair_idx"]):
        c = cnt_t[p]
        mx = jnp.max(c)
        if wl["task"][q] == 0:
            a = jnp.where(mx > 0, (c > 0).astype(dtype), 1.0)
        else:
            a = jnp.where(mx > 0, c / jnp.maximum(mx, 1e-9), 1.0)
        acc = acc + a
    return acc / len(wl["pair_idx"])


# ---------------------------------------------------------------------------
# the episode, whole fleet
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("frozen", "n_steps", "dtype"))
def episode(frozen, keys, n_steps: int, dtype=jnp.float32):
    """Scene and observations at every controller step of one episode.

    frozen: (scene dict, traffic dict, grid dict, queries, stride) as a
    hashable tuple of JSON strings; keys [F, 2] camera keys. Returns
    per step [E, F, ...]: acc [C] and the object snapshot (pos, size,
    oid) the detector reference renders."""
    sc, traffic, grid, queries, stride = (json.loads(x) for x in frozen)
    wl = workload_layout(queries)
    teach = teacher_constants(wl["pairs"])
    wins = windows(grid)

    def one_camera(key):
        _, enabled, _ = _params(sc, traffic, key, dtype)
        cam_salt = key[0]

        def body(s, e):
            for j in range(stride):
                s = step_camera(sc, traffic, key, e * stride + j, s, dtype)
            t = e * stride
            acc = observe_camera(sc, teach, wl, wins, s, enabled, cam_salt,
                                 t, dtype)
            snap = {"pos": s["pos"], "size": s["size"], "oid": s["oid"]}
            return s, (acc, snap)

        s0 = init_camera(sc, traffic, key, dtype)
        _, ys = jax.lax.scan(body, s0, jnp.arange(n_steps, dtype=jnp.int32))
        return ys

    acc, snap = jax.vmap(one_camera)(keys)
    swap = partial(jnp.swapaxes, axis1=0, axis2=1)          # [F, E] -> [E, F]
    return swap(acc), jax.tree.map(swap, snap)


def frozen_inputs(config: dict, traffic: dict, stride: int) -> tuple:
    """`episode`'s static inputs: the scene, the traffic's density and
    dynamics, the grid, the queries and the stride, as JSON strings."""
    density = {k: traffic[k] for k in ("n_people", "n_cars", "person_speed",
                                       "car_speed", "churn")}
    return tuple(json.dumps(x, sort_keys=True) for x in (
        config["scene"], density, config["grid"], config["workload"],
        stride))


def stride(config: dict, traffic: dict) -> int:
    """Scene frames per controller step."""
    return max(1, int(round(config["scene"]["fps"] / traffic["fps"])))
