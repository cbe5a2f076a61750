"""Device-resident procedural scene: fixed-shape JAX port of data/scene.py.

The numpy `Scene` is a stateful per-object Python loop — fine for building
offline tables, but it pins episode length to host materialization and
forces every camera in a fleet to watch the same world. This module keeps
the same dynamics (POI random-walk people, lane-traffic cars, churn
respawn, stationary density) as pure functions over a `SceneState` pytree
whose leaves lead with a fleet axis [F, max_objects], so a heterogeneous
fleet's scenes advance *inside* the jit'd episode scan:

  * `SceneSpec`        — hashable compile-time constants (extent, slot
                         layout, spawn size ranges, teacher-noise knobs);
  * `SceneFleetParams` — per-camera arrays (speeds, churn, POI layout,
                         density via the `enabled` slot mask) so cameras
                         differ without retracing;
  * `scene_step`       — one frame for the whole fleet, driven by
                         per-camera `jax.random` keys derived as
                         fold_in(camera_key, frame) — reproducible and
                         independent of fleet size or shard layout.

Object identity (`oid`) survives respawns exactly like the numpy scene:
a respawned slot takes the camera's next fresh id, which is what the
aggregate-counting metrics and the flicker-deterministic teachers key on.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.scene import CAR, PERSON, SceneConfig
from repro.obs.trace import staged

_POI_SALT = 0x5CE7E


@dataclass(frozen=True)
class SceneSpec:
    """Static scene layout — everything jit treats as compile-time.

    Slot layout is fixed: slots [0, max_people) are people, the rest cars;
    per-camera density is the `enabled` mask in SceneFleetParams, so a
    sparse camera and a dense camera share one compiled program."""
    extent: tuple = (150.0, 75.0)
    fps: int = 15
    max_people: int = 14
    max_cars: int = 8
    n_poi: int = 3
    person_size: tuple = (2.5, 5.5)
    car_size: tuple = (5.0, 9.0)
    lane_tilts: tuple = (20.0, 32.0, 44.0)
    # observation model (mirrors serving.teachers / pipeline defaults)
    min_visible: float = 0.25
    miss_rate: float = 0.12
    flicker: float = 0.4
    flicker_bucket: int = 3
    # cell_rasterize dispatch (same semantics as FleetConfig.use_kernel)
    use_kernel: bool = False

    @property
    def max_objects(self) -> int:
        return self.max_people + self.max_cars

    @classmethod
    def from_config(cls, cfg: SceneConfig, **overrides) -> "SceneSpec":
        """Geometry/layout of a numpy SceneConfig as a static spec.

        Dynamics (person_speed, car_speed, churn) are per-camera ARRAYS
        in SceneFleetParams, not spec fields — use `fleet_from_config`
        to port a full SceneConfig including its dynamics."""
        kw = dict(extent=tuple(cfg.extent), fps=cfg.fps,
                  max_people=cfg.n_people, max_cars=cfg.n_cars,
                  n_poi=cfg.n_poi, person_size=tuple(cfg.person_size),
                  car_size=tuple(cfg.car_size),
                  lane_tilts=tuple(cfg.lane_tilts))
        kw.update(overrides)
        return cls(**kw)


class SceneFleetParams(NamedTuple):
    """Per-camera scene heterogeneity; every leaf leads with [F]."""
    person_speed: jnp.ndarray   # [F] deg/s mean
    car_speed: jnp.ndarray      # [F] deg/s mean
    churn: jnp.ndarray          # [F] per-step respawn probability
    poi: jnp.ndarray            # [F, n_poi, 2] person points-of-interest
    enabled: jnp.ndarray        # [F, M] bool — density (live slots)


class SceneState(NamedTuple):
    """Struct-of-arrays object state; leaves lead with [F, M]."""
    pos: jnp.ndarray            # [F, M, 2] degrees
    vel: jnp.ndarray            # [F, M, 2] deg/s
    size: jnp.ndarray           # [F, M, 2] degrees (w, h)
    waypoint: jnp.ndarray       # [F, M, 2] person targets
    oid: jnp.ndarray            # [F, M] int32 unique-per-camera ids
    next_id: jnp.ndarray        # [F] int32


def kind_mask(spec: SceneSpec) -> np.ndarray:
    """[M] int — PERSON for the first max_people slots, CAR after."""
    return np.where(np.arange(spec.max_objects) < spec.max_people,
                    PERSON, CAR)


def scene_fleet_params(spec: SceneSpec, n_cameras: int, *, seed: int = 0,
                       scene_seeds=None, person_speed=1.2, car_speed=10.0,
                       churn=0.01, n_people=None, n_cars=None
                       ) -> tuple[SceneFleetParams, jnp.ndarray]:
    """Build per-camera params + camera PRNG keys.

    Every scalar argument broadcasts; pass an [F] array for heterogeneity.
    Camera f's key is fold_in(PRNGKey(seed), scene_seeds[f]) — two fleets
    that share (seed, scene_seeds[f]) produce identical scenes for that
    camera regardless of fleet size or shard layout.
    """
    f, m = n_cameras, spec.max_objects
    if scene_seeds is None:
        scene_seeds = np.arange(f)
    scene_seeds = jnp.asarray(np.broadcast_to(scene_seeds, (f,)), jnp.int32)
    rng = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), scene_seeds)

    def bc(x):
        return jnp.asarray(np.broadcast_to(np.asarray(x, np.float32), (f,)))

    n_people = spec.max_people if n_people is None else n_people
    n_cars = spec.max_cars if n_cars is None else n_cars
    n_people = np.broadcast_to(np.asarray(n_people, np.int32), (f,))
    n_cars = np.broadcast_to(np.asarray(n_cars, np.int32), (f,))
    if (n_people > spec.max_people).any() or (n_cars > spec.max_cars).any():
        raise ValueError("per-camera n_people/n_cars exceed SceneSpec slots")
    idx = np.arange(m)
    enabled = np.where(idx[None, :] < spec.max_people,
                       idx[None, :] < n_people[:, None],
                       (idx[None, :] - spec.max_people) < n_cars[:, None])

    poi_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
        rng, _POI_SALT)
    lo = jnp.array([15.0, 10.0])
    hi = jnp.array([spec.extent[0] - 15.0, spec.extent[1] - 10.0])
    poi = jax.vmap(lambda k: jax.random.uniform(
        k, (spec.n_poi, 2), minval=lo, maxval=hi))(poi_keys)

    params = SceneFleetParams(
        person_speed=bc(person_speed), car_speed=bc(car_speed),
        churn=bc(churn), poi=poi, enabled=jnp.asarray(enabled))
    return params, rng


def fleet_from_config(cfg: SceneConfig, n_cameras: int, *, seed: int = 0,
                      scene_seeds=None, **spec_overrides
                      ) -> tuple[SceneSpec, SceneFleetParams, jnp.ndarray]:
    """Port one numpy SceneConfig — geometry AND dynamics — to the fleet
    substrate: (SceneSpec, homogeneous SceneFleetParams, camera keys)."""
    spec = SceneSpec.from_config(cfg, **spec_overrides)
    params, rng = scene_fleet_params(
        spec, n_cameras, seed=seed, scene_seeds=scene_seeds,
        person_speed=cfg.person_speed, car_speed=cfg.car_speed,
        churn=cfg.churn)
    return spec, params, rng


# ---------------------------------------------------------------------------
# spawn / step (single camera; vmapped over the fleet axis)
# ---------------------------------------------------------------------------

def _norm(v, axis=-1, keepdims=True):
    return jnp.sqrt(jnp.sum(v * v, axis=axis, keepdims=keepdims))


def _spawn_draws(spec: SceneSpec, p, key):
    """All per-slot respawn draws for one camera -> dict of [M, ...]."""
    m = spec.max_objects
    ks = jax.random.split(key, 8)
    extent = jnp.asarray(spec.extent)
    # person draws
    poi_a = p.poi[jax.random.randint(ks[0], (m,), 0, spec.n_poi)]
    pos_p = jnp.clip(poi_a + 8.0 * jax.random.normal(ks[1], (m, 2)),
                     jnp.array([1.0, 1.0]), extent - 1.0)
    wp_p = p.poi[jax.random.randint(ks[2], (m,), 0, spec.n_poi)]
    speed_p = jnp.maximum(
        0.2, p.person_speed + 0.4 * jax.random.normal(ks[3], (m,)))
    d = wp_p - pos_p
    vel_p = speed_p[:, None] * d / jnp.maximum(_norm(d), 1e-6)
    w_p = jax.random.uniform(ks[4], (m,), minval=spec.person_size[0],
                             maxval=spec.person_size[1])
    size_p = jnp.stack([w_p * 0.45, w_p], -1)
    # car draws
    lanes = jnp.asarray(spec.lane_tilts)
    lane = lanes[jax.random.randint(ks[5], (m,), 0, len(spec.lane_tilts))]
    u = jax.random.uniform(ks[6], (m, 4))
    direction = jnp.where(u[:, 0] < 0.5, -1.0, 1.0)
    x0 = jnp.where(direction > 0, 0.0, spec.extent[0])
    x0_init = u[:, 1] * spec.extent[0]          # initial=True placement
    tilt = lane + (u[:, 2] - 0.5) * 2.0 * 1.73  # ~N(0,1) spread, uniform
    speed_c = jnp.maximum(
        2.0, p.car_speed + 2.5 * jax.random.normal(ks[7], (m,)))
    vel_c = jnp.stack([direction * speed_c, jnp.zeros_like(speed_c)], -1)
    w_c = spec.car_size[0] + u[:, 3] * (spec.car_size[1] - spec.car_size[0])
    size_c = jnp.stack([w_c, w_c * 0.45], -1)
    return dict(pos_p=pos_p, wp_p=wp_p, vel_p=vel_p, size_p=size_p,
                x0=x0, x0_init=x0_init, tilt=tilt, vel_c=vel_c,
                size_c=size_c)


def _init_one(spec: SceneSpec, p: SceneFleetParams, key) -> SceneState:
    m = spec.max_objects
    person = jnp.asarray(kind_mask(spec) == PERSON)
    d = _spawn_draws(spec, p, key)
    pos = jnp.where(person[:, None], d["pos_p"],
                    jnp.stack([d["x0_init"], d["tilt"]], -1))
    vel = jnp.where(person[:, None], d["vel_p"], d["vel_c"])
    size = jnp.where(person[:, None], d["size_p"], d["size_c"])
    # disabled slots park far outside with zero size: never visible
    off = ~p.enabled
    pos = jnp.where(off[:, None], -1000.0, pos)
    vel = jnp.where(off[:, None], 0.0, vel)
    size = jnp.where(off[:, None], 0.0, size)
    return SceneState(pos=pos, vel=vel, size=size, waypoint=d["wp_p"],
                      oid=jnp.arange(m, dtype=jnp.int32),
                      next_id=jnp.asarray(m, jnp.int32))


def _step_one(spec: SceneSpec, p: SceneFleetParams, key,
              s: SceneState) -> SceneState:
    m = spec.max_objects
    person = jnp.asarray(kind_mask(spec) == PERSON)
    extent = jnp.asarray(spec.extent)
    dt = 1.0 / spec.fps
    k_wp, k_jit, k_churn, k_spawn = jax.random.split(key, 4)

    pos = s.pos + s.vel * dt

    # people: retarget near waypoints, jitter heading, stay in bounds
    d = s.waypoint - pos
    arrived = _norm(d, keepdims=False) < 2.0
    kw1, kw2 = jax.random.split(k_wp)
    new_wp = p.poi[jax.random.randint(kw1, (m,), 0, spec.n_poi)] \
        + 6.0 * jax.random.normal(kw2, (m, 2))
    waypoint = jnp.where((person & arrived)[:, None], new_wp, s.waypoint)
    d = waypoint - pos
    speed = _norm(s.vel)
    v = speed * d / jnp.maximum(_norm(d), 1e-6) \
        + 0.3 * jax.random.normal(k_jit, (m, 2))
    vel_pn = v / jnp.maximum(_norm(v), 1e-6) * speed
    pos_pn = jnp.clip(pos, 0.0, extent)
    vel = jnp.where(person[:, None], vel_pn, s.vel)
    pos = jnp.where(person[:, None], pos_pn, pos)

    # respawn: person churn + cars leaving the panorama
    churn = person & (jax.random.uniform(k_churn, (m,))
                      < p.churn * dt * spec.fps)
    out = ~person & ((pos[:, 0] < -3.0) | (pos[:, 0] > spec.extent[0] + 3.0))
    respawn = (churn | out) & p.enabled

    sd = _spawn_draws(spec, p, k_spawn)
    sp_pos = jnp.where(person[:, None], sd["pos_p"],
                       jnp.stack([sd["x0"], sd["tilt"]], -1))
    sp_vel = jnp.where(person[:, None], sd["vel_p"], sd["vel_c"])
    sp_size = jnp.where(person[:, None], sd["size_p"], sd["size_c"])

    pos = jnp.where(respawn[:, None], sp_pos, pos)
    vel = jnp.where(respawn[:, None], sp_vel, vel)
    size = jnp.where(respawn[:, None], sp_size, s.size)
    waypoint = jnp.where(respawn[:, None], sd["wp_p"], waypoint)
    new_ids = s.next_id + jnp.cumsum(respawn.astype(jnp.int32)) - 1
    oid = jnp.where(respawn, new_ids, s.oid)
    next_id = s.next_id + jnp.sum(respawn, dtype=jnp.int32)
    return SceneState(pos=pos, vel=vel, size=size, waypoint=waypoint,
                      oid=oid, next_id=next_id)


@partial(jax.jit, static_argnames=("spec",))
def init_scene(spec: SceneSpec, params: SceneFleetParams,
               rng: jnp.ndarray) -> SceneState:
    """Initial spawn for the whole fleet. rng [F, 2] camera keys."""
    return jax.vmap(partial(_init_one, spec))(params, rng)


@partial(jax.jit, static_argnames=("spec",))
def scene_step(spec: SceneSpec, params: SceneFleetParams, keys: jnp.ndarray,
               state: SceneState) -> SceneState:
    """Advance every camera's scene one frame. keys [F, 2] per-step keys
    (derive as vmap(fold_in)(camera_rng, frame_index) so replays and
    host-materialized tables see the identical stream)."""
    return jax.vmap(partial(_step_one, spec))(params, keys, state)


@staged("scene_advance")
def advance_scene(spec: SceneSpec, params: SceneFleetParams,
                  rng: jnp.ndarray, state: SceneState, step_idx,
                  stride: int) -> SceneState:
    """Advance `stride` scene frames for controller step `step_idx` —
    the scene runs at spec.fps while the controller runs at the response
    rate, exactly like run_madeye's frame stride. step_idx may be [F]."""
    step_idx = jnp.broadcast_to(step_idx, rng.shape[:1])
    for j in range(stride):
        frame = step_idx * stride + j
        keys = jax.vmap(jax.random.fold_in)(rng, frame)
        state = scene_step(spec, params, keys, state)
    return state
