"""Episode runner: ONE jit'd lax.scan behind the observation-provider seam.

The fleet episode is a single scan body (`_episode`) parameterized by an
`ObservationProvider` (repro.fleet.api): the provider owns a scan-carry
(`init_carry`), per-step scanned inputs (`scan_xs`), and an `observe`
hook that turns (carry, state, xs) into the `FleetObs` the controller
step consumes. Three providers ship in the registry:

  * `EpisodeTables` (`tables`) — the host-materialized path
    (`build_episode_tables`: O(E*N*Z*P) numpy loops over the procedural
    scene + teacher models, identical inputs to what run_madeye feeds
    MadEyeController). Kept for decision-parity tests against the numpy
    controller and for replaying recorded substrates; every camera
    shares one world and episode length is bounded by host
    materialization.

  * `SceneProvider` (`scene`) — the device-resident path: per-camera
    scenes (repro.scene_jax) advance and are observed *inside* the
    scanned step, so a 512-camera episode with per-camera scene configs
    and per-camera network traces runs with no per-step host transfers,
    and episode length / fleet heterogeneity are free of host work.
    Scene randomness is driven by the per-camera keys threaded through
    `FleetState.rng` (fold_in(camera_key, frame)), so streams are
    reproducible and independent of fleet size or shard layout.

  * `DetectorProvider` (`detector`) — the scene path with the
    approximation model in the loop (paper §3.4): candidate (cell,
    zoom) crops are *rendered* from the scene and *scored* by the
    detector network (models/detector via serving.engine) inside the
    scanned step; the controller ranks on those detections, the oracle
    teachers only grade what it chose (acc_true). The default pipeline
    is candidate-sparse and fused: a search-coupled shortlist keeps the
    `shortlist_k` windows reachable by the shape search / top-EWMA
    cells, kernels/crop_patchify rasterizes the survivors directly into
    the backbone's patch embeddings, and one batched forward over the
    flattened [F*K] axis scores them (shortlist_k = N*Z is exhaustive and
    bit-identical to the retained fused=False chunked reference).
    Detector params ride in the scan carry so a future in-scan
    distillation step can update them; render noise keys fold from the
    same per-camera keys as the scene, so decisions stay
    fleet-size/shard independent.

Each provider registers as a jax pytree whose static configuration
(SceneSpec, stride, DetectorConfig, chunk) is aux_data — so the one
jitted `_episode` keys its compilation cache on provider statics
automatically, and provider arrays trace like any other argument.

The fleet axis shards over a mesh `data` axis (launch/mesh.py) via each
provider's `shard` hook: shared EpisodeTables are replicated (a few
hundred KB), scene state/params shard with the fleet, detector params
are fleet-shared and replicate.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ewma
from repro.core.rank import Workload
from repro.core.tradeoff import BudgetConfig
from repro.fleet.state import (
    FleetConfig,
    FleetState,
    FleetStatics,
    WorkloadSpec,
    fleet_statics,
    workload_spec,
)
from repro.fleet.step import FleetObs, FleetStepOut, fleet_step
from repro.obs.trace import stage, staged
from repro.scene_jax.observe import (
    TeacherArrays,
    detections_obs,
    grid_windows,
    observe_all_cells,
    teacher_arrays,
)
from repro.scene_jax.render import render_fleet_crops, render_noise
from repro.scene_jax.scene import (
    SceneFleetParams,
    SceneSpec,
    SceneState,
    advance_scene,
    init_scene,
    kind_mask,
    scene_fleet_params,
)

# FleetObs fields recorded by collect_obs (everything but the network
# leaves, which the provider carries separately as [E]/[E, F] traces)
_TABLE_FIELDS = ("counts", "areas", "centroid", "spread", "extent",
                 "nbox", "acc_true")


def shard_fleet(state, mesh, axis: int = 0):
    """Place the fleet axis (`axis`) of every pytree leaf on the mesh
    `data` axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sh(x):
        spec = [None] * x.ndim
        spec[axis] = "data"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree.map(sh, state)


class EpisodeTables(NamedTuple):
    """Scanned observation substrate; every leaf leads with [E] steps.
    mbps/rtt are [E] for a fleet-shared link or [E, F] per camera."""
    counts: jnp.ndarray     # [E, N, Z, P]
    areas: jnp.ndarray      # [E, N, Z, P]
    centroid: jnp.ndarray   # [E, N, Z, 2]
    spread: jnp.ndarray     # [E, N, Z]
    extent: jnp.ndarray     # [E, N, Z]
    nbox: jnp.ndarray       # [E, N, Z]
    acc_true: jnp.ndarray   # [E, N, Z]
    mbps: jnp.ndarray       # [E] or [E, F]
    rtt: jnp.ndarray        # [E] or [E, F]

    @property
    def n_steps(self) -> int:
        return self.counts.shape[0]

    # -- ObservationProvider hooks (repro.fleet.api) --------------------
    def init_carry(self, state: FleetState):
        return ()

    def scan_xs(self):
        return self

    def observe(self, cfg: FleetConfig, wl: WorkloadSpec, carry,
                state: FleetState, xs):
        return carry, FleetObs(**xs._asdict())

    def shard(self, mesh):
        # fleet-shared tables replicate (a few hundred KB)
        return self


@dataclass(frozen=True)
class SceneProvider:
    """Scene-backed observation provider: everything the scanned step
    needs to generate FleetObs on device. Build with `make_scene_provider`
    (which also returns the matching FleetState so the scene keys in
    `FleetState.rng` line up with the per-camera scene seeds)."""
    spec: SceneSpec             # static scene layout (jit constant)
    params: SceneFleetParams    # per-camera arrays [F, ...]
    teach: TeacherArrays        # per-pair teacher constants
    state0: SceneState          # initial object state [F, M, ...]
    windows: jnp.ndarray        # [N * Z, 4] flattened FOV windows
    mbps: jnp.ndarray           # [E] or [E, F] network trace
    rtt: jnp.ndarray            # [E] or [E, F]
    stride: int                 # scene frames per controller step

    @property
    def n_steps(self) -> int:
        return self.mbps.shape[0]

    # -- ObservationProvider hooks --------------------------------------
    def init_carry(self, state: FleetState):
        return self.state0

    def scan_xs(self):
        return (self.mbps, self.rtt)

    def observe(self, cfg: FleetConfig, wl: WorkloadSpec, carry,
                state: FleetState, xs):
        mbps_t, rtt_t = xs
        sc = advance_scene(self.spec, self.params, state.rng, carry,
                           state.step_idx, self.stride)
        o = observe_all_cells(self.spec, self.teach, self.params, sc,
                              state.step_idx * self.stride, self.windows,
                              task_id=wl.task_id, pair_idx=wl.pair_idx,
                              n_zoom=len(cfg.zoom_levels),
                              cam_salt=state.rng[:, 0])
        obs = FleetObs(counts=o.counts, areas=o.areas, centroid=o.centroid,
                       spread=o.spread, extent=o.extent, nbox=o.nbox,
                       acc_true=o.acc_true, mbps=mbps_t, rtt=rtt_t)
        return sc, obs

    def shard(self, mesh):
        # [E, F] per-camera network traces shard their fleet axis too;
        # fleet-shared [E] traces replicate
        mbps, rtt = (x if x.ndim == 1 else shard_fleet(x, mesh, axis=1)
                     for x in (self.mbps, self.rtt))
        return dataclasses.replace(
            self, state0=shard_fleet(self.state0, mesh),
            params=shard_fleet(self.params, mesh), mbps=mbps, rtt=rtt)


@staged("shortlist")
def shortlist_windows(cfg: FleetConfig, state: FleetState,
                      neighbor8: jnp.ndarray, k: int) -> jnp.ndarray:
    """Search-coupled candidate shortlist: the [F, K] flattened window
    ids (cell * Z + zoom) worth rendering + scoring this step.

    The shape search only ever explores cells reachable from the
    camera's current state (paper §3.3): the carried shape itself, its
    8-neighbor ring (evolve/resize grow into it), and the top-EWMA cells
    (reseed and scout jump there). Cells are ranked by exactly that
    reachability — shape > ring > normalized EWMA label, with the scout
    rule's sqrt-staleness bonus as the tiebreak — and the top K/Z cells
    contribute all Z zoom windows each. Pure per-camera function of
    (state, grid statics): deterministic, fleet-size/shard independent
    (the same key discipline as the scene streams). lax.top_k breaks
    ties toward the lower cell id, so the selection is stable.
    """
    z = len(cfg.zoom_levels)
    if k <= 0 or k % z != 0:
        raise ValueError(f"shortlist k={k} must be a positive multiple "
                         f"of the {z} zoom levels (whole cells)")
    kc = k // z
    labels = ewma.labels(state.ewma, delta_weight=cfg.delta_weight)
    lnorm = labels / jnp.maximum(
        jnp.max(labels, axis=-1, keepdims=True), 1e-9)
    stale = jnp.sqrt(jnp.maximum(
        (state.step_idx[:, None] - state.last_visit).astype(jnp.float32),
        0.0))
    shape = state.shape
    ring = (shape.astype(jnp.float32) @ neighbor8.astype(jnp.float32)) > 0
    score = (4.0 * shape + 2.0 * (ring & ~shape)
             + lnorm + 1e-3 * stale)
    _, cells = jax.lax.top_k(score, kc)                     # [F, Kc]
    return (cells[:, :, None] * z
            + jnp.arange(z, dtype=cells.dtype)[None, None, :]
            ).reshape(cells.shape[0], kc * z)


@dataclass(frozen=True)
class DetectorProvider:
    """Scene-backed provider with the approximation model in the loop:
    candidate-orientation crops are rendered and scored by the detector
    network inside the scanned step. Build with `make_detector_provider`
    (pass a distilled checkpoint — pytree or .npz path — for a trained
    camera).

    Two pipelines share the observation contract:

      * fused=True (default) — the candidate-sparse fast path: a
        search-coupled shortlist keeps the top `shortlist_k` of the N*Z
        windows per camera (shortlist_k = N*Z reproduces exhaustive
        scoring bit-for-bit), kernels/crop_patchify turns the survivors
        straight into patch-embedding tokens (the platform chooses: the
        Pallas kernel on a TPU, where crops never hit HBM as pixels;
        the jnp reference on the CPU), and ONE batched forward over the
        flattened [F*K] axis scores them
        (engine.detector_scores_tokens).
      * fused=False — the pre-shortlist reference: every window rendered
        to pixels and scored through a serial per-chunk lax.map. Kept
        exhaustive-only, as the bit-exact anchor the fast path's parity
        tests pin against.

    With `distill` set (a repro.learn.DistillSpec — static, so it keys
    the jit cache like every other config), the provider LEARNS in-scan:
    a LearnState (per-camera trainable params + optimizer state + pair
    ring) joins the carry, the fused forward routes through per-camera
    heads, and after each fleet_step the `learn` hook harvests teacher
    pairs from the SENT crops and takes a cadence-gated optimizer step —
    entirely inside the episode scan. distill=None compiles the exact
    frozen-params program (decisions bit-identical, pinned by
    tests/test_learn.py).
    """
    scene: SceneProvider        # world + teachers (oracle feedback)
    det_cfg: object             # DetectorConfig (hashable, jit-static)
    det_params: object          # detector pytree (scan carry)
    thresh: jnp.ndarray         # [P] per-pair score threshold
    geo_thresh: jnp.ndarray     # [] score floor for zoom geometry
    noise: jnp.ndarray          # [] render noise scale
    nbr8: jnp.ndarray           # [N, N] 8-neighbor mask (shortlist ring)
    chunk: int                  # windows per slab (static; fused=False)
    shortlist_k: int = 0        # windows scored per camera (0 = all)
    fused: bool = True          # fast path vs reference chunk loop
    distill: object = None      # repro.learn.DistillSpec | None (static)

    @property
    def n_steps(self) -> int:
        return self.scene.n_steps

    @property
    def learns(self) -> bool:
        """True when the episode should call the `learn` hook — kept off
        the ObservationProvider protocol (runtime_checkable would demand
        it of every provider); the episode probes via getattr."""
        return self.distill is not None

    def _effective_k(self) -> int:
        c = self.scene.windows.shape[0]
        k = self.shortlist_k
        return k if 0 < k < c else c

    # -- ObservationProvider hooks --------------------------------------
    def init_carry(self, state: FleetState):
        # detector params ride in the carry; with distill on, the
        # LearnState (per-camera trainable heads/params + opt + ring)
        # rides alongside and is what the optimizer step rewrites
        if self.distill is None:
            return (self.scene.state0, self.det_params)
        from repro.learn.loop import init_learn

        lc = init_learn(self.distill, self.det_cfg, self.det_params,
                        state.step_idx.shape[0], self._effective_k())
        return (self.scene.state0, self.det_params, lc)

    def scan_xs(self):
        return (self.scene.mbps, self.scene.rtt)

    def observe(self, cfg: FleetConfig, wl: WorkloadSpec, carry,
                state: FleetState, xs):
        learn_on = self.distill is not None
        if learn_on:
            sc, dp, lc = carry
        else:
            sc, dp = carry
        mbps_t, rtt_t = xs
        p = self.scene
        kinds = jnp.asarray(kind_mask(p.spec))
        pair_cls = jnp.asarray(wl.pair_cls, jnp.int32)
        res = self.det_cfg.img_res

        sc = advance_scene(p.spec, p.params, state.rng, sc,
                           state.step_idx, p.stride)
        frame = state.step_idx * p.stride
        # oracle pass: only acc_true survives DCE — the teachers grade
        # the camera's choices, they no longer feed its ranking
        o = observe_all_cells(p.spec, p.teach, p.params, sc, frame,
                              p.windows, task_id=wl.task_id,
                              pair_idx=wl.pair_idx,
                              n_zoom=len(cfg.zoom_levels),
                              cam_salt=state.rng[:, 0])
        with stage("crop_to_tokens"):
            noise_img = render_noise(state.rng, frame, res) * self.noise

        if learn_on:
            dets, lc = self._score_learn(cfg, state, sc, dp, lc, kinds,
                                         noise_img)
        elif self.fused:
            dets = self._score_fused(cfg, state, sc, dp, kinds, noise_img)
        else:
            dets = self._score_chunked(sc, dp, kinds, noise_img, p, res)
        do = detections_obs(dets, p.windows, pair_cls, self.thresh,
                            self.geo_thresh, o.acc_true,
                            n_zoom=len(cfg.zoom_levels))
        obs = FleetObs(counts=do.counts, areas=do.areas,
                       centroid=do.centroid, spread=do.spread,
                       extent=do.extent, nbox=do.nbox,
                       acc_true=do.acc_true, mbps=mbps_t, rtt=rtt_t)
        return ((sc, dp, lc) if learn_on else (sc, dp)), obs

    def _score_fused(self, cfg, state, sc, dp, kinds, noise_img):
        """Shortlist -> fused crop->token kernel -> one [F*K] forward,
        detections scattered back to the full window axis."""
        from repro.kernels.crop_patchify.ops import crop_patchify
        from repro.models.detector import patch_embed
        from repro.serving.engine import detector_scores_tokens

        p = self.scene
        c = p.windows.shape[0]
        k = self.shortlist_k if 0 < self.shortlist_k < c else c
        if k < c:
            widx = shortlist_windows(cfg, state, self.nbr8, k)
            wins = p.windows[widx]                          # [F, K, 4]
        else:
            wins = p.windows                                # shared [C, 4]
        tokens = crop_patchify(
            sc.pos, sc.size, kinds, sc.oid, wins,
            patch_embed(dp),
            patch=self.det_cfg.patch, res=self.det_cfg.img_res,
            min_visible=p.spec.min_visible, noise=noise_img,
            dtype=self.det_cfg.dtype,
            block_k=_auto_chunk(k, self.chunk))             # [F, K, gg, D]
        f = tokens.shape[0]
        dets = detector_scores_tokens(
            dp, self.det_cfg,
            tokens.reshape((f * k,) + tokens.shape[2:]))
        dets = jax.tree.map(
            lambda x: x.reshape((f, k) + x.shape[1:]), dets)
        if k < c:
            # un-shortlisted windows read as score-0 detections (empty
            # under any positive threshold), so detections_obs and the
            # step consume the same full [F, C] axis either way
            with stage("heads"):
                arange_f = jnp.arange(f)[:, None]
                dets = jax.tree.map(
                    lambda x: jnp.zeros((f, c) + x.shape[2:], x.dtype)
                    .at[arange_f, widx].set(x), dets)
        return dets

    def _score_chunked(self, sc, dp, kinds, noise_img, p, res):
        """Pre-shortlist reference: serial lax.map over window chunks,
        peak memory [F, chunk, res, res, 3] — the bit-exact anchor."""
        from repro.serving.engine import detector_scores

        c = p.windows.shape[0]
        wchunks = p.windows.reshape(c // self.chunk, self.chunk, 4)

        def score_chunk(wc):
            crops = render_fleet_crops(sc.pos, sc.size, kinds, sc.oid, wc,
                                       res=res,
                                       min_visible=p.spec.min_visible,
                                       noise=noise_img)
            return jax.vmap(
                lambda im: detector_scores(dp, self.det_cfg, im))(crops)

        dets = jax.lax.map(score_chunk, wchunks)
        return jax.tree.map(
            lambda x: jnp.moveaxis(x, 0, 1).reshape(
                (x.shape[1], c) + x.shape[3:]), dets)

    def _score_learn(self, cfg, state, sc, dp, lc, kinds, noise_img):
        """The fused fast path routed through the LEARNED per-camera
        params, staging the student payload for the pair harvest.

        Head-only mode: the shared frozen backbone+neck runs once over
        the flattened [F*K] shortlist (identical compute to the frozen
        path), per-camera head convs finish the forward, and the
        post-neck features are staged — so distillation training re-runs
        ZERO backbone compute. Full-param mode: the whole per-camera
        network scores its own camera's crops (vmap over the fleet) and
        the patch tokens are staged instead."""
        from repro.kernels.crop_patchify.ops import crop_patchify
        from repro.models.detector import (
            detections_from_feats,
            detector_forward_tokens,
            detector_neck_feats_tokens,
            patch_embed,
        )

        p = self.scene
        c = p.windows.shape[0]
        k = self._effective_k()
        if k < c:
            widx = shortlist_windows(cfg, state, self.nbr8, k)
            wins = p.windows[widx]                          # [F, K, 4]
        else:
            wins = p.windows                                # shared [C, 4]
        tokens = crop_patchify(
            sc.pos, sc.size, kinds, sc.oid, wins,
            patch_embed(dp),
            patch=self.det_cfg.patch, res=self.det_cfg.img_res,
            min_visible=p.spec.min_visible, noise=noise_img,
            dtype=self.det_cfg.dtype,
            block_k=_auto_chunk(k, self.chunk))             # [F, K, gg, D]
        f = tokens.shape[0]
        if k == c:
            widx = jnp.broadcast_to(
                jnp.arange(c, dtype=jnp.int32)[None], (f, c))

        if self.distill.head_only:
            feats = detector_neck_feats_tokens(
                dp, self.det_cfg,
                tokens.reshape((f * k,) + tokens.shape[2:]))
            fe = feats.reshape((f, k) + feats.shape[1:])    # [F,K,g,g,Fd]
            dets = jax.vmap(
                lambda heads, x: detections_from_feats(
                    self.det_cfg, heads, x))(lc.params, fe)
            payload = fe
        else:
            dets = jax.vmap(
                lambda par, x: detector_forward_tokens(
                    par, self.det_cfg, x))(lc.params, tokens)
            payload = tokens
        if k < c:
            with stage("heads"):
                arange_f = jnp.arange(f)[:, None]
                dets = jax.tree.map(
                    lambda x: jnp.zeros((f, c) + x.shape[2:], x.dtype)
                    .at[arange_f, widx].set(x), dets)
        lc = lc._replace(staged=payload.astype(lc.staged.dtype),
                         staged_widx=widx.astype(jnp.int32))
        return dets, lc

    @staged("distill")
    def learn(self, cfg: FleetConfig, wl: WorkloadSpec, carry,
              state: FleetState, out):
        """Post-step learning hook (called by _episode when `learns`):
        harvest teacher pairs from the crops the budget SENT, then take
        the cadence-gated optimizer step. `state` is the post-step
        controller state (step_idx already incremented — the observation
        frame is recovered as (step_idx - 1) * stride); `out` this
        step's FleetStepOut. Returns (carry', aux) with aux {"loss": [F]
        (-1.0 for skipped/idle cameras), "lr": [F]} — emitted into the
        scan outputs. Every stage is row-wise per camera, preserving
        fleet-size/shard independence."""
        from repro.learn.loop import distill_step
        from repro.learn.pairs import (
            harvest_into_buffer,
            select_sent_windows,
            teacher_window_targets,
        )

        sc, dp, lc = carry
        p = self.scene
        sel_widx, sel_ok = select_sent_windows(
            out, len(cfg.zoom_levels), self.distill.harvest)
        boxes, classes, bvalid = teacher_window_targets(
            p.spec, p.teach, p.params, sc,
            (state.step_idx - 1) * p.stride, p.windows[sel_widx],
            self.det_cfg.max_boxes, state.rng[:, 0])
        lc = lc._replace(buf=harvest_into_buffer(
            lc.buf, lc.staged, lc.staged_widx, sel_widx, sel_ok,
            boxes, classes, bvalid))
        lc, aux = distill_step(self.distill, self.det_cfg, lc,
                               state.step_idx)
        return (sc, dp, lc), aux

    def learned_params(self, carry, camera=None):
        """Full detector params from a learning episode's final carry —
        per-camera trained subtree merged with the shared frozen rest.
        camera=None keeps the fleet axis on trained leaves; an int
        selects one camera's checkpoint (ready for
        `save_detector_params`)."""
        from repro.learn.loop import merged_params

        if self.distill is None:
            raise ValueError("learned_params needs a distill-enabled "
                             "provider (distill=None runs frozen)")
        sc, dp, lc = carry
        return merged_params(self.distill, dp, lc.params, camera)

    def shard(self, mesh):
        # scene state/params shard with the fleet; detector params are
        # fleet-shared and replicate (as is the nbr8 grid geometry)
        return dataclasses.replace(self, scene=self.scene.shard(mesh))


# static configuration is aux_data: the one jitted episode keys its
# compilation cache on (SceneSpec, stride, DetectorConfig, chunk) through
# the treedef, arrays trace as children
jax.tree_util.register_dataclass(
    SceneProvider,
    data_fields=["params", "teach", "state0", "windows", "mbps", "rtt"],
    meta_fields=["spec", "stride"])
jax.tree_util.register_dataclass(
    DetectorProvider,
    data_fields=["scene", "det_params", "thresh", "geo_thresh", "noise",
                 "nbr8"],
    meta_fields=["det_cfg", "chunk", "shortlist_k", "fused", "distill"])


def build_episode_tables(video, workload: Workload, tables: dict,
                         budget: BudgetConfig, trace, *,
                         approx_miss: float = 0.12,
                         acc_table: np.ndarray | None = None,
                         max_steps: int | None = None) -> EpisodeTables:
    """Materialize what `observe` + the backend would return at every
    (controller timestep, cell, zoom) — the exact observations
    serving/pipeline.run_madeye feeds the numpy controller."""
    from repro.serving import accuracy as acc_mod
    from repro.serving.pipeline import ZOOM_LEVELS, _observation_from_tables

    grid = video.grid
    spec = workload_spec(workload)
    n, z_n, p_n = grid.n_cells, len(ZOOM_LEVELS), len(spec.pairs)
    if acc_table is None:
        acc_table = acc_mod.workload_acc_table(video, workload, tables,
                                               ZOOM_LEVELS)
    stride = max(1, int(round(video.fps / budget.fps)))
    frames = list(range(0, video.n_frames, stride))
    if max_steps is not None:
        frames = frames[:max_steps]
    e = len(frames)

    counts = np.zeros((e, n, z_n, p_n), np.float32)
    areas = np.zeros((e, n, z_n, p_n), np.float32)
    centroid = np.zeros((e, n, z_n, 2), np.float32)
    spread = np.zeros((e, n, z_n), np.float32)
    extent = np.zeros((e, n, z_n), np.float32)
    nbox = np.zeros((e, n, z_n), np.int32)
    acc_true = np.zeros((e, n, z_n), np.float32)
    mbps = np.zeros(e, np.float32)

    for ei, t in enumerate(frames):
        acc_true[ei] = acc_table[t]
        mbps[ei] = trace.observed_mbps(t)
        for c in range(n):
            for zi in range(z_n):
                o = _observation_from_tables(tables, workload, grid, t, c,
                                             zi, approx_miss)
                for pi, pair in enumerate(spec.pairs):
                    counts[ei, c, zi, pi] = o.counts.get(pair, 0)
                    areas[ei, c, zi, pi] = o.areas.get(pair, 0.0)
                k = o.box_centers.shape[0]
                nbox[ei, c, zi] = k
                if k:
                    centroid[ei, c, zi] = o.centroid
                    spread[ei, c, zi] = float(np.linalg.norm(
                        o.box_centers - o.centroid, axis=1).mean())
                    extent[ei, c, zi] = float(o.box_sizes.max())

    return EpisodeTables(
        counts=jnp.asarray(counts), areas=jnp.asarray(areas),
        centroid=jnp.asarray(centroid), spread=jnp.asarray(spread),
        extent=jnp.asarray(extent), nbox=jnp.asarray(nbox),
        acc_true=jnp.asarray(acc_true), mbps=jnp.asarray(mbps),
        rtt=jnp.full(e, float(trace.rtt_s), np.float32))


# ---------------------------------------------------------------------------
# provider construction (the registry factories — repro.fleet.api)
# ---------------------------------------------------------------------------

def budget_from_config(cfg: FleetConfig) -> BudgetConfig:
    """Recover the numpy-side BudgetConfig a FleetConfig mirrors, so
    host-materialization helpers and the jitted step consume identical
    constants (the inverse of `fleet_config` for the budget fields)."""
    return BudgetConfig(
        fps=cfg.fps, rotation_speed=cfg.rotation_speed,
        hop_degrees=cfg.hop_degrees, approx_infer_s=cfg.approx_infer_s,
        backend_infer_s=cfg.backend_infer_s, frame_bytes=cfg.frame_bytes,
        min_send=cfg.min_send, max_send=cfg.max_send,
        pipelined=cfg.pipelined)


def make_tables_provider(grid, workload: Workload, cfg: FleetConfig, *,
                         n_cameras: int, n_steps: int | None = None,
                         seed: int = 3, mbps: float = 24.0,
                         rtt_ms: float = 20.0, approx_miss: float = 0.12,
                         scene_fps: float = 15.0, video=None, tables=None,
                         trace=None, acc_table=None
                         ) -> tuple[EpisodeTables, FleetState]:
    """Host-materialized provider: numpy scene + teacher oracles recorded
    into EpisodeTables (every camera shares one world).

    Builds the substrate from `seed` (procedural scene at `scene_fps`,
    long enough for `n_steps` controller steps at cfg.fps, fixed
    mbps/rtt link) — or reuses prebuilt `video`/`tables`/`trace`/
    `acc_table` objects when the caller already has them (the serving
    launcher and benchmarks do; those kwargs are in-memory-only, not
    JSON-serializable)."""
    from repro.data import SceneConfig, build_video
    from repro.fleet.state import init_fleet
    from repro.serving import NetworkTrace, detection_tables

    budget = budget_from_config(cfg)
    if video is None:
        if n_steps is None:
            raise ValueError("tables provider needs n_steps (or a "
                             "prebuilt video=) to size the substrate")
        stride = max(1, int(round(scene_fps / cfg.fps)))
        video = build_video(grid, SceneConfig(fps=scene_fps, seed=seed),
                            (n_steps * stride + 2) / scene_fps)
    if tables is None:
        tables = detection_tables(video, workload)
    if trace is None:
        trace = NetworkTrace.fixed(mbps, rtt_ms, video.n_frames)
    ep = build_episode_tables(video, workload, tables, budget, trace,
                              approx_miss=approx_miss, acc_table=acc_table,
                              max_steps=n_steps)
    return ep, init_fleet(grid, n_cameras)


def fleet_network_traces(n_steps: int, n_cameras: int | None = None, *,
                         mbps=24.0, rtt_ms=20.0, seed: int | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-episode network arrays for the scanned step.

    With n_cameras=None returns fleet-shared [E] traces; otherwise
    [E, F] with `mbps`/`rtt_ms` broadcast per camera. seed=None gives
    fixed links; an int seed gives every camera its own LTE-ish AR(1)
    trace with deep fades (transport.ar1_mobile_trace — the same model
    NetworkTrace.mobile draws from).
    """
    from repro.serving.transport import ar1_mobile_trace

    shape = (n_steps,) if n_cameras is None else (n_steps, n_cameras)
    base = np.broadcast_to(np.asarray(mbps, np.float32), shape[1:])
    rtt = np.broadcast_to(np.asarray(rtt_ms, np.float32), shape[1:]) / 1e3
    if seed is None:
        x = np.broadcast_to(base, shape).astype(np.float32)
    else:
        x = ar1_mobile_trace(n_steps, base,
                             np.random.default_rng(seed)).astype(np.float32)
    return (jnp.asarray(x),
            jnp.asarray(np.broadcast_to(rtt, shape).astype(np.float32)))


def make_scene_provider(grid, workload: Workload, cfg: FleetConfig, *,
                        n_cameras: int, n_steps: int,
                        spec: SceneSpec | None = None, seed: int = 0,
                        scene_seeds=None, person_speed=1.2, car_speed=10.0,
                        churn=0.01, n_people=None, n_cars=None,
                        mbps=24.0, rtt_ms=20.0, net_seed: int | None = None,
                        seed_size: int = 6
                        ) -> tuple[SceneProvider, FleetState]:
    """Build a heterogeneous scene-backed provider + the matching fleet
    state. Scalar scene arguments broadcast; pass [F] arrays for
    per-camera heterogeneity (density via n_people/n_cars, dynamics via
    speeds/churn, world layout via scene_seeds). The returned FleetState
    carries fold_in(PRNGKey(seed), scene_seeds[f]) in `rng` — the same
    keys the provider's initial scene state was drawn from."""
    from repro.fleet.state import init_fleet

    spec = spec or SceneSpec()
    params, rng = scene_fleet_params(
        spec, n_cameras, seed=seed, scene_seeds=scene_seeds,
        person_speed=person_speed, car_speed=car_speed, churn=churn,
        n_people=n_people, n_cars=n_cars)
    state0 = init_scene(spec, params, rng)
    sw = workload_spec(workload)
    net_mbps, net_rtt = fleet_network_traces(
        n_steps, None if np.isscalar(mbps) and np.isscalar(rtt_ms)
        and net_seed is None else n_cameras,
        mbps=mbps, rtt_ms=rtt_ms, seed=net_seed)
    provider = SceneProvider(
        spec=spec, params=params, teach=teacher_arrays(sw.pairs),
        state0=state0, windows=grid_windows(grid, cfg.zoom_levels),
        mbps=net_mbps, rtt=net_rtt,
        stride=max(1, int(round(spec.fps / cfg.fps))))
    # install the SAME key array the initial scene state was drawn from —
    # one derivation, so init stream and step stream can't drift apart
    state = init_fleet(grid, n_cameras, seed_size, rng=rng)
    return provider, state


def save_detector_params(path: str, params) -> str:
    """Write a detector params pytree (nested dicts of arrays) to .npz,
    keys '/'-joined — the checkpoint format `make_detector_provider`
    loads. Anything outside that contract (non-dict interior nodes,
    '/'-bearing or empty keys, non-array leaves) fails loudly here
    rather than producing an .npz that loads into a different treedef.
    Returns the path written."""
    flat = {}

    def walk(tree, prefix):
        if isinstance(tree, dict):
            for k in sorted(tree):
                k = str(k)
                if "/" in k or not k:
                    raise ValueError(
                        f"key {k!r} under {prefix or '<root>'!r} would "
                        f"not round-trip through '/'-joined npz names")
                walk(tree[k], f"{prefix}/{k}" if prefix else k)
        elif not prefix:
            raise TypeError("detector params must be a dict pytree, got "
                            f"{type(tree).__name__}")
        elif not hasattr(tree, "shape"):
            raise TypeError(f"leaf {prefix!r} is {type(tree).__name__}, "
                            f"not an array")
        else:
            flat[prefix] = np.asarray(tree)

    walk(params, "")
    np.savez(path, **flat)
    return path


def load_detector_params(path: str) -> dict:
    """Load a `save_detector_params` .npz back into the nested pytree."""
    out: dict = {}
    with np.load(path) as z:
        for key in z.files:
            node = out
            parts = key.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(z[key])
    return out


def _auto_chunk(n_windows: int, default: int) -> int:
    """Largest divisor of n_windows that is <= default (>= 1). The
    auto-selected render+infer slab for the chunked reference path: on
    grids where the one-cell-row default does not divide N*Z, walk down
    to the nearest divisor instead of silently slabbing unevenly."""
    chunk = max(1, min(default, n_windows))
    while n_windows % chunk != 0:
        chunk -= 1
    return chunk


def make_detector_provider(grid, workload: Workload, cfg: FleetConfig, *,
                           n_cameras: int, n_steps: int,
                           det_cfg=None, det_params=None,
                           det_seed: int = 0, thresh=None,
                           geo_thresh: float | None = None,
                           noise: float = 0.05,
                           chunk: int | None = None,
                           shortlist_k: int | None = None,
                           fused: bool = True,
                           distill=None, **scene_kwargs
                           ) -> tuple[DetectorProvider, FleetState]:
    """Scene provider + the approximation detector scored in-step.

    det_cfg is a DetectorConfig or a registered config name
    (`"madeye-approx"` is the published 224 px width, resolved through
    repro.configs.get_config, so a full-width FleetRunSpec stays
    JSON-round-trippable); it defaults to the madeye-approx smoke config
    (64 px crops — the crop resolution IS det_cfg.img_res). det_params
    select the camera's approximation model: a trained pytree, a `.npz`
    checkpoint path (written by `save_detector_params`, e.g. a distilled
    snapshot), or None for a fresh undistilled `detector_init(PRNGKey(det_seed))` demo
    net. `thresh` broadcasts to a per-pair [P] score threshold; left
    None it adapts to the params source — 0.3 for the undistilled demo
    (inside a fresh net's score range, so counts stay scene-dependent),
    0.5 for a trained checkpoint — and `geo_thresh` (zoom-geometry score
    floor) follows the same rule at +0.05.

    Fast-path knobs: `shortlist_k` caps how many of the N*Z candidate
    windows are rendered + scored per camera per step (the
    search-coupled shortlist — must be a multiple of the zoom count;
    None/N*Z scores everything, reproducing exhaustive behavior
    bit-for-bit); `fused` picks the candidate-sparse fused pipeline
    (default) vs the pre-shortlist chunked reference. The fused
    crop->token stage runs the Pallas crop_patchify kernel wherever the
    episode is lowered for a TPU and the jnp reference on the CPU; the
    platform chooses, not a knob.
    `chunk` bounds the reference path's render+infer slab (must divide
    N*Z, default one cell-row of zooms at a time — `_auto_chunk`).
    `scene_kwargs` are make_scene_provider's heterogeneity knobs.

    `distill` turns on in-scan continual distillation (paper §3.4): a
    repro.learn.DistillSpec, a dict of its fields, or True for the
    default spec — the camera's per-query heads then train inside the
    episode scan on teacher grades of the crops the budget sent. Fused
    pipeline only (the chunked reference stays the frozen bit-exact
    anchor); None keeps today's frozen-params program exactly.
    """
    from repro.configs import get_config, get_smoke_config
    from repro.models.detector import detector_init

    if det_cfg is None:
        det_cfg = get_smoke_config("madeye-approx")
    elif isinstance(det_cfg, str):
        det_cfg = get_config(det_cfg)
    trained = det_params is not None
    if isinstance(det_params, (str, bytes)):
        det_params = load_detector_params(det_params)
    elif det_params is None:
        det_params = detector_init(jax.random.PRNGKey(det_seed), det_cfg)
    if thresh is None:
        thresh = 0.5 if trained else 0.3
    if geo_thresh is None:
        geo_thresh = float(np.asarray(thresh).max()) + 0.05
    scene, state = make_scene_provider(
        grid, workload, cfg, n_cameras=n_cameras, n_steps=n_steps,
        **scene_kwargs)
    n_pairs = len(workload_spec(workload).pairs)
    c = scene.windows.shape[0]
    z = len(cfg.zoom_levels)
    if chunk is None:
        chunk = _auto_chunk(c, z * max(1, cfg.n_pan))
    elif c % chunk != 0:
        raise ValueError(
            f"chunk={chunk} must divide the {c} candidate windows "
            f"(n_cells * n_zoom) — a non-dividing slab would silently "
            f"fall back to rendering all windows at once")
    if shortlist_k is None:
        shortlist_k = c
    elif not (0 < shortlist_k <= c) or shortlist_k % z != 0:
        raise ValueError(
            f"shortlist_k={shortlist_k} must be a multiple of the "
            f"{z} zoom levels in [{z}, {c}] — the shortlist keeps whole "
            f"cells (all zooms of a kept cell are scored)")
    if not fused and shortlist_k < c:
        raise ValueError(
            "the chunked reference path (fused=False) is exhaustive-"
            f"only; drop shortlist_k={shortlist_k} or use the fused "
            "fast path")
    if shortlist_k < c and (float(np.min(np.asarray(thresh))) <= 0.0
                            or float(geo_thresh) <= 0.0):
        raise ValueError(
            "shortlisting needs strictly positive thresh/geo_thresh: "
            "un-shortlisted windows are scattered as score-0 "
            "detections, which only read as empty under a positive "
            f"threshold (got thresh={thresh!r}, "
            f"geo_thresh={geo_thresh!r})")
    from repro.learn.spec import normalize_distill

    distill = normalize_distill(distill)
    if distill is not None:
        if not fused:
            raise ValueError(
                "in-scan distillation rides the fused fast path (the "
                "student payload is staged from the fused forward); the "
                "chunked reference (fused=False) stays the frozen "
                "bit-exact anchor — drop distill or fused=False")
        if distill.harvest > grid.n_cells:
            raise ValueError(
                f"distill.harvest={distill.harvest} exceeds the "
                f"{grid.n_cells} grid cells — no step can send that "
                f"many distinct orientations")
    provider = DetectorProvider(
        scene=scene, det_cfg=det_cfg, det_params=det_params,
        thresh=jnp.broadcast_to(
            jnp.asarray(thresh, jnp.float32), (n_pairs,)),
        geo_thresh=jnp.asarray(geo_thresh, jnp.float32),
        noise=jnp.asarray(noise, jnp.float32),
        nbr8=fleet_statics(grid).neighbor8,
        chunk=chunk, shortlist_k=shortlist_k, fused=fused,
        distill=distill)
    return provider, state


# ---------------------------------------------------------------------------
# THE episode: one scan body for every provider
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "wl", "collect_obs", "metrics"))
def _episode(cfg: FleetConfig, wl: WorkloadSpec, statics: FleetStatics,
             state: FleetState, provider, *, collect_obs: bool = False,
             metrics=None):
    """The unified scan body: provider.observe generates this step's
    FleetObs from (provider carry, controller state, scanned xs), then
    fleet_step consumes it. Every provider — host tables, device scenes,
    detector-in-the-loop — runs through this one program; adding a
    scenario means adding a provider, not a fourth scan body.

    collect_obs additionally records camera 0's observation tables
    (per-camera [F, ...] leaves sliced to [0]) so a scene episode can be
    re-materialized as EpisodeTables — see materialize_scene_tables.

    metrics (a static repro.obs.MetricsSpec, part of the jit cache key)
    additionally emits a per-step FleetMetrics dict from *inside* the
    scan (shortlist hit-rate, chosen-vs-oracle rank, EWMA labels, budget
    counters — repro.obs.metrics.step_metrics); when None/disabled this
    function compiles to the exact metrics-free program, so decisions
    are bit-identical either way (pinned by tests/test_obs.py).

    Learning providers (getattr(provider, "learns", False) — the
    DetectorProvider with a DistillSpec) additionally get their `learn`
    hook called after every fleet_step, the per-step learn aux joins the
    extras dict under "learn" (and, with metrics on, as
    distill_loss/distill_lr in the FleetMetrics), and the FINAL provider
    carry is returned as a third element — the learned params live
    there. distill off compiles the exact pre-learning program.

    With any extra enabled, ys becomes (FleetStepOut, extras dict keyed
    "obs"/"metrics"/"learn"); bare FleetStepOut otherwise.
    """
    if metrics is not None and not metrics.enabled:
        metrics = None
    learns = getattr(provider, "learns", False)

    def body(carry, xs):
        st, pc = carry
        pc, obs = provider.observe(cfg, wl, pc, st, xs)
        st2, out = fleet_step(cfg, wl, statics, st, obs)
        if learns:
            pc, laux = provider.learn(cfg, wl, pc, st2, out)
        if collect_obs or metrics is not None or learns:
            ex = {}
            if collect_obs:
                ex["obs"] = {f: getattr(obs, f)[0] for f in _TABLE_FIELDS}
            if metrics is not None:
                from repro.obs.metrics import step_metrics

                ex["metrics"] = step_metrics(metrics, cfg, provider,
                                             st, st2, obs, out)
            if learns:
                ex["learn"] = laux
                if metrics is not None:
                    # distill keys join the emitted FleetMetrics only on
                    # learning runs — MetricsSpec.keys() (and the
                    # metrics-off parity pin) stay distill-agnostic
                    ex["metrics"]["distill_loss"] = laux["loss"]
                    ex["metrics"]["distill_lr"] = laux["lr"]
            return (st2, pc), (out, ex)
        return (st2, pc), out

    (state, pc_final), ys = jax.lax.scan(
        body, (state, provider.init_carry(state)), provider.scan_xs())
    if learns:
        return state, ys, pc_final
    return state, ys


def materialize_scene_tables(cfg: FleetConfig, wl: WorkloadSpec,
                             statics: FleetStatics, state: FleetState,
                             provider: SceneProvider) -> EpisodeTables:
    """Host-materialize the observation stream camera 0 of `provider`
    would see — an EpisodeTables the tables-backed path can scan.

    Deliberately runs the identical full-fleet scene episode program
    (not an F=1 slice): the recorded floats are then bit-identical to
    what the in-scan provider feeds fleet_step, which is what the
    decision-parity tests pin — a differently-shaped program could
    legally round reductions differently. That costs one episode at full
    F; for cheap replay tables where bit-exactness doesn't matter, build
    the provider/state at n_cameras=1 and materialize that instead."""
    _, (out, ex) = _episode(cfg, wl, statics, state, provider,
                            collect_obs=True)
    rec = ex["obs"]
    mbps, rtt = provider.mbps, provider.rtt
    if mbps.ndim == 2:
        mbps = mbps[:, 0]
    if rtt.ndim == 2:
        rtt = rtt[:, 0]
    return EpisodeTables(mbps=mbps, rtt=rtt,
                         **{f: rec[f] for f in _TABLE_FIELDS})


def run_fleet_episode(cfg: FleetConfig, wl: WorkloadSpec,
                      statics: FleetStatics, state: FleetState,
                      provider, *, mesh=None, metrics=None):
    """Run the whole episode in one jit'd scan.

    `provider` is any ObservationProvider — the shipped EpisodeTables /
    SceneProvider / DetectorProvider, or anything registered through
    repro.fleet.api. Returns (final state, FleetStepOut with leaves
    stacked to [E, F, ...]). With `mesh`, the fleet axis (controller
    state plus whatever the provider's `shard` hook places — scene
    state/params on the scene paths) is sharded over the mesh `data`
    axis first, and the scan runs SPMD across devices, like
    launch/serve.py's batched inference path.

    `metrics` (a repro.obs.MetricsSpec) turns on in-scan telemetry; the
    return becomes (final state, FleetStepOut, FleetMetrics dict with
    leaves [E, ...]). With it None/disabled the compiled program is the
    exact metrics-free one and the return stays a 2-tuple.

    A LEARNING provider (DetectorProvider with distill set) appends two
    more elements: (..., extras, final_carry) where extras is the
    per-step dict {"learn": {...}} (+ "metrics" when enabled — also
    reachable positionally as the 3-tuple's metrics element) and
    final_carry holds the learned params
    (provider.learned_params(final_carry)).

    Prefer `repro.fleet.api.run_fleet(spec)` unless you are composing
    providers/state yourself (parity tests and benchmarks do).
    """
    if mesh is not None:
        state = shard_fleet(state, mesh)
        provider = provider.shard(mesh)
    if metrics is not None and not metrics.enabled:
        metrics = None
    learns = getattr(provider, "learns", False)
    if learns:
        state, (out, ex), fc = _episode(cfg, wl, statics, state, provider,
                                        metrics=metrics)
        if metrics is None:
            return state, out, ex, fc
        return state, out, ex["metrics"], ex, fc
    if metrics is None:
        return _episode(cfg, wl, statics, state, provider)
    state, (out, ex) = _episode(cfg, wl, statics, state, provider,
                                metrics=metrics)
    return state, out, ex["metrics"]
