"""Batched inference engine — the TPU-native serving core.

The paper runs approximation models round-robin on a Jetson (Nexus-style
scheduler). The TPU adaptation batches instead: every explored orientation
of every camera in a fleet becomes one row of a single [B, H, W, 3] batch
— the MXU wants one big matmul, not 75 small ones. The fleet dimension is
the leading batch axis and shards over the mesh's `data` axis via pjit
(launch/serve.py wires the mesh); controller state (EWMA labels) is a
pytree with the same leading axis, updated with vmapped pure functions
from core/ewma.py.

`run_fleet_controller` drives the FULL per-timestep controller (shape
search + path + zoom + rank, repro/fleet) for a whole fleet in one jit'd
scan; the EWMA-only helpers below remain for pipelines that rank on the
server side without camera-side shape search.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import DetectorConfig
from repro.core import ewma
from repro.models import detector as det


# Module-level jits, NOT per-engine lambdas: a fresh `jax.jit(lambda ...)`
# per InferenceEngine (the old __post_init__) meant every engine instance
# — and every vmap call site with its own static threshold — carried its
# own compilation cache, so the in-step detector path re-traced per site.
# Hoisted here the cache keys on (cfg, shapes) alone; score_thresh is a
# *traced* scalar, so sweeping thresholds never recompiles
# (tests/test_render_jax.py asserts the cache stays at one entry).

@partial(jax.jit, static_argnames=("cfg",))
def detector_scores(params, cfg: DetectorConfig,
                    images: jnp.ndarray) -> det.Detections:
    """images [B, H, W, 3] -> Detections (static [B, max_boxes, ...])."""
    return det.detector_forward(params, cfg, images)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def detector_scores_tokens(params, cfg: DetectorConfig,
                           tokens: jnp.ndarray) -> det.Detections:
    """Patch-embedding tokens [B, P, D] -> Detections.

    The candidate-sparse fast path's ONE batched forward: the fleet
    provider flattens its [F, K] shortlisted crops to B = F*K token rows
    (emitted by kernels/crop_patchify without materializing pixels) and
    scores them in a single program instead of a serial per-chunk
    lax.map. The token buffer is donated — at the top level XLA reuses
    it for activations, so peak memory stays at the activation slab
    rather than tokens + activations (inside an enclosing jit, e.g. the
    episode scan, donation is a no-op and XLA schedules as usual).
    """
    return det.detector_forward_tokens(params, cfg, tokens)


@partial(jax.jit, static_argnames=("cfg",))
def detector_counts_and_areas(params, cfg: DetectorConfig,
                              images: jnp.ndarray,
                              score_thresh: jnp.ndarray):
    """-> (counts [B], areas [B]) for rank.py consumption."""
    d = det.detector_forward(params, cfg, images)
    keep = d.scores >= score_thresh
    counts = jnp.sum(keep, axis=-1)
    areas = jnp.sum(d.boxes[..., 2] * d.boxes[..., 3] * keep, axis=-1)
    return counts, areas


@dataclass
class InferenceEngine:
    """jit'd detector inference over orientation batches."""
    cfg: DetectorConfig
    params: dict

    def score_batch(self, images: jnp.ndarray) -> det.Detections:
        """images [B, H, W, 3] -> Detections (static [B, max_boxes, ...])."""
        return detector_scores(self.params, self.cfg, images)

    def counts_and_areas(self, images: jnp.ndarray, *,
                         score_thresh: float = 0.5):
        """-> (counts [B], areas [B]) for rank.py consumption."""
        return detector_counts_and_areas(
            self.params, self.cfg, images,
            jnp.asarray(score_thresh, jnp.float32))


# ---------------------------------------------------------------------------
# Fleet-scale EWMA ranking state (vmapped over cameras)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=())
def fleet_update_labels(state: ewma.EWMAState, visited: jnp.ndarray,
                        acc_values: jnp.ndarray) -> ewma.EWMAState:
    """state leaves [C, N]; visited/acc_values [C, N] — C cameras."""
    return jax.vmap(ewma.update)(state, visited, acc_values)


@jax.jit
def fleet_labels(state: ewma.EWMAState) -> jnp.ndarray:
    return jax.vmap(ewma.labels)(state)


def init_fleet_state(n_cameras: int, n_cells: int) -> ewma.EWMAState:
    z = jnp.zeros((n_cameras, n_cells), jnp.float32)
    return ewma.EWMAState(z, z, z, z)


@partial(jax.jit, static_argnames=("k",))
def fleet_topk_cells(labels: jnp.ndarray, k: int = 4):
    """labels [C, N] -> (values [C, k], cells [C, k]) — per-camera ranking."""
    return jax.lax.top_k(labels, k)


# The three run_fleet_*_controller functions are thin shims over the
# unified experiment API (repro.fleet.api): each builds a declarative
# FleetRunSpec for its provider and returns the raw (final FleetState,
# FleetStepOut) pair it always returned — via prepare_fleet_run +
# .episode(), so the outputs stay on device with none of run_fleet's
# host-side summarization. New code should construct FleetRunSpec
# directly and keep the typed FleetResult.

def run_fleet_controller(video, workload, tables, budget, trace, *,
                         n_cameras: int, mesh=None,
                         approx_miss: float = 0.12,
                         acc_table=None, max_steps: int | None = None):
    """Fleet controller on a prebuilt host serving substrate — the
    many-camera analogue of pipeline.run_madeye, now a shim over
    `run_fleet` with the `tables` provider (the prebuilt video/tables/
    trace objects ride through provider_kwargs). Returns (final
    FleetState, FleetStepOut stacked over steps)."""
    from repro.fleet import FleetRunSpec, prepare_fleet_run

    spec = FleetRunSpec.from_objects(
        "tables", n_cameras=n_cameras, n_steps=max_steps,
        grid=video.grid, workload=workload, budget=budget,
        video=video, tables=tables, trace=trace, acc_table=acc_table,
        approx_miss=approx_miss)
    return prepare_fleet_run(spec, mesh=mesh).episode()


def run_fleet_scene_controller(grid, workload, budget, *, n_cameras: int,
                               n_steps: int, mesh=None, seed: int = 0,
                               **scene_kwargs):
    """Fleet controller on the device-resident scene substrate — a shim
    over `run_fleet` with the `scene` provider: per-camera scenes
    (repro.scene_jax) advance and are observed inside the jit'd episode
    scan, so episode length and fleet heterogeneity cost no host work.

    `scene_kwargs` go to fleet.make_scene_provider (scene_seeds,
    person_speed, n_people, mbps, net_seed, ... — scalars broadcast, [F]
    arrays give per-camera heterogeneity). Returns (final FleetState,
    FleetStepOut stacked over steps).
    """
    from repro.fleet import FleetRunSpec, prepare_fleet_run

    spec = FleetRunSpec.from_objects(
        "scene", n_cameras=n_cameras, n_steps=n_steps, seed=seed,
        grid=grid, workload=workload, budget=budget, **scene_kwargs)
    return prepare_fleet_run(spec, mesh=mesh).episode()


def run_fleet_detector_controller(grid, workload, budget, *,
                                  n_cameras: int, n_steps: int, mesh=None,
                                  seed: int = 0, det_cfg=None,
                                  det_params=None, distill=None,
                                  **scene_kwargs):
    """Fleet controller with the approximation model in the loop — a
    shim over `run_fleet` with the `detector` provider, the paper's full
    camera-side pipeline (§3.4): every candidate orientation is
    *rendered* from the device-resident scene and *scored* by the
    detector network (models/detector) inside the jit'd episode scan;
    the controller ranks on those detections instead of precomputed
    teacher tables. Oracle accuracy still comes from the scene teachers,
    as backend feedback.

    det_cfg defaults to the madeye-approx smoke config (64 px crops);
    det_params are initialized from `seed` when not given — pass a
    distilled checkpoint (pytree or .npz path) for a trained camera.
    `distill` (True / DistillSpec / dict, see repro.learn) turns on
    in-scan continual distillation: per-camera detector heads train
    against the scene teachers inside the scan, and the episode return
    grows the (extras, final carry) tail documented on
    fleet.run_fleet_episode. `scene_kwargs` go to
    fleet.make_detector_provider (same scene/network heterogeneity
    knobs as the scene controller). Returns (final FleetState,
    FleetStepOut stacked over steps) on frozen runs.
    """
    from repro.fleet import FleetRunSpec, prepare_fleet_run

    scene_kwargs.setdefault("det_seed", seed)
    spec = FleetRunSpec.from_objects(
        "detector", n_cameras=n_cameras, n_steps=n_steps, seed=seed,
        grid=grid, workload=workload, budget=budget,
        det_cfg=det_cfg, det_params=det_params, distill=distill,
        **scene_kwargs)
    return prepare_fleet_run(spec, mesh=mesh).episode()


@partial(jax.jit, static_argnames=("k_send",))
def fleet_step(state: ewma.EWMAState, counts: jnp.ndarray,
               areas: jnp.ndarray, visited: jnp.ndarray, *,
               k_send: int = 2):
    """One fleet-wide ranking timestep, fully on-device (pjit-able: shard
    the camera axis over `data`).

    counts/areas [C, N] — approximation-model outputs for the explored
    cells of every camera (zeros elsewhere); visited [C, N] bool.
    Returns (new_state, send_cells [C, k], pred_acc [C, N]).

    This is the TPU-native heart of the controller: the per-task relative
    scoring of core/rank.py for the counting abstraction, the EWMA label
    update, and the top-k selection — one fused program for 10k cameras
    instead of 10k Python loops.
    """
    # relative predicted accuracy per camera (count task, §3.1)
    cmax = jnp.max(jnp.where(visited, counts, 0.0), axis=1, keepdims=True)
    cscore = jnp.where(cmax > 0, counts / jnp.maximum(cmax, 1e-9), 0.0)
    amax = jnp.max(jnp.where(visited, areas, 0.0), axis=1, keepdims=True)
    ascore = jnp.where(amax > 0, areas / jnp.maximum(amax, 1e-9), 0.0)
    pred = 0.7 * cscore + 0.3 * ascore
    pred = jnp.where(visited, pred, 0.0)

    new_state = jax.vmap(ewma.update)(state, visited, pred)
    # rank only explored cells (unexplored get -inf)
    masked = jnp.where(visited, pred, -jnp.inf)
    _, cells = jax.lax.top_k(masked, k_send)
    return new_state, cells, pred
