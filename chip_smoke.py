"""Bring-up smoke run of the MadEye fleet episode on a TPU.

Drives the system's main path once through its public entry point,
`repro.fleet.run_fleet(FleetRunSpec)`, at the published width of the
approximation detector (`madeye-approx` FULL: 224 px crops, 6 layers,
d_model 192) and checks what comes out. Phases, in order:

  device      the default device is a TPU; no CPU fallback
  kernels     each Pallas kernel of the main path, compiled, against its
              jnp reference at real width
  controller  a homogeneous 64-camera scene fleet; decisions against the
              numpy reference controller (core/madeye.py)
  detector    the 64-camera detector episode at FULL width, K = 18
  distill     the same episode with head-only continual distillation

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the detector fleet sharded over
                                      # four chips vs one of them

Any failed check exits non-zero. The last line of stdout is one JSON
object naming the device, printed only when every phase passed. Times
and memory printed here come from one smoke run: they are not
measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

FULL = "madeye-approx"          # registered name of the published width
N_CAMERAS = 64
N_STEPS = 16
SHORTLIST_K = 18
FPS = 15.0
MBPS, RTT_MS = 24.0, 20.0
DECISIONS = ("explored", "order", "n_explored", "zooms", "sent", "k_send")

PRECISION_CHUNK = 3         # crop slab of the precision comparison
REF_SLAB = 4                # cameras per crop_patchify reference call

# tests/test_kernels.py's (atol, rtol), kernel vs reference
TOL = {"neighbor_score": (1e-5, 1e-5), "cell_rasterize": (1e-4, 1e-4),
       "crop_patchify": (2e-5, 2e-5)}


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(chips: int = 1) -> dict:
    """Refuse anything but a TPU, then turn on the compile cache."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: the default JAX platform is "
                         f"{d.platform!r}, not 'tpu'; this script runs on "
                         f"a TPU only")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX reports {len(devs)}")
    cache = use_compile_cache()
    say("device", f"platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} compile_cache={cache}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _compare(name: str, kernel, reference) -> float:
    """assert_allclose's rule under TOL[name], over a tuple of outputs;
    returns the largest absolute difference."""
    atol, rtol = TOL[name]
    worst, ok = 0.0, True
    for k, r in zip(kernel, reference):
        k, r = np.asarray(k, np.float64), np.asarray(r, np.float64)
        diff = np.abs(k - r)
        worst = max(worst, float(diff.max()))
        ok &= bool((diff <= atol + rtol * np.abs(r)).all())
    say("kernels", f"{name}: max |kernel - reference| = {worst!r} "
        f"(atol {atol}, rtol {rtol})")
    check(ok, f"{name} differs from its reference beyond tolerance")
    return worst


def kernel_phase(det_cfg, *, n_cameras: int = N_CAMERAS,
                 shortlist_k: int = SHORTLIST_K, seed: int = 0) -> dict:
    """Each main-path kernel once at real width against its reference:
    neighbor_score over the fleet batch, cell_rasterize over the fleet's
    student + teacher channels, crop_patchify at the detector's width.
    Both crop_patchify paths contract at full f32 precision."""
    import jax

    from repro.core import DEFAULT_GRID
    from repro.fleet.api import DEFAULT_QUERIES
    from repro.kernels.cell_rasterize.ops import cell_rasterize, window_arrays
    from repro.kernels.crop_patchify.ops import crop_patchify
    from repro.kernels.neighbor_score.ops import (
        geometry_arrays,
        neighbor_scores,
    )
    from repro.models.layers import conv_init
    from repro.scene_jax.scene import SceneSpec

    rng = np.random.default_rng(seed)
    grid = DEFAULT_GRID
    n = grid.n_cells
    f = n_cameras
    m = SceneSpec().max_objects
    diffs = {}

    # neighbor_score: [F, N] shape state of the whole fleet
    geo = geometry_arrays(grid)
    mask = rng.random((f, n)) < 0.3
    mask[:, 0] |= ~mask.any(1)
    heads = np.array([rng.choice(np.flatnonzero(r)) for r in mask],
                     np.int32)
    args = (mask, rng.random((f, n)) < 0.7,
            rng.uniform(0.0, [150.0, 75.0], (f, n, 2)).astype(np.float32),
            heads, geo["d_center"], geo["overlap"], geo["cell_x"],
            geo["cell_y"], geo["neighbor8"])
    ref, cand_r = neighbor_scores(*args, use_kernel=False)
    ker, cand_k = neighbor_scores(*args, use_kernel=True)
    check(np.array_equal(cand_r, cand_k), "neighbor_score candidates")
    diffs["neighbor_score"] = _compare("neighbor_score", [ker], [ref])

    # cell_rasterize: the scene observation's 2P student+teacher channels
    p = 2 * len({(q[0], q[1]) for q in DEFAULT_QUERIES})
    boxes = [rng.uniform(lo, hi, (f, m)).astype(np.float32)
             for lo, hi in ((-10, 160), (-10, 85), (0, 9), (0, 9))]
    draw = rng.uniform(0, 1.2, (f, p, m)).astype(np.float32)
    a0 = rng.uniform(0.02, 0.1, p).astype(np.float32)
    a1 = (a0 + rng.uniform(0.05, 0.2, p)).astype(np.float32)
    wins = window_arrays(grid)
    ref = cell_rasterize(*boxes, draw, a0, a1, wins, n_moment=p // 2)
    ker = cell_rasterize(*boxes, draw, a0, a1, wins, n_moment=p // 2,
                         use_kernel=True)
    diffs["cell_rasterize"] = _compare("cell_rasterize", ker, ref)

    # crop_patchify: K shortlisted windows per camera at the model width
    res, patch = det_cfg.img_res, det_cfg.patch
    pos = rng.uniform([0, 0], [150, 75], (f, m, 2)).astype(np.float32)
    size = rng.uniform(1.5, 9.0, (f, m, 2)).astype(np.float32)
    kind = rng.integers(0, 2, m).astype(np.int32)
    oid = rng.integers(0, 4000, (f, m)).astype(np.int32)
    widx = np.stack([rng.choice(len(wins), shortlist_k, replace=False)
                     for _ in range(f)])
    pe = conv_init(jax.random.PRNGKey(seed), patch, patch, 3,
                   det_cfg.d_model)
    noise = 0.05 * rng.standard_normal((f, res, res, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        ker = crop_patchify(pos, size, kind, oid, wins[widx], pe,
                            patch=patch, res=res, noise=noise,
                            use_kernel=True)
    check(ker.shape == (f, shortlist_k, (res // patch) ** 2,
                        det_cfg.d_model), f"crop_patchify shape {ker.shape}")
    # the reference in camera slabs: its [..., 3] pixel planes pad to 128
    # lanes in HBM (~30 GB for the whole fleet at res 224)
    refs = []
    for s in range(0, f, REF_SLAB):
        sl = slice(s, s + REF_SLAB)
        with jax.default_matmul_precision("highest"):
            refs.append(np.asarray(crop_patchify(
                pos[sl], size[sl], kind, oid[sl], wins[widx[sl]], pe,
                patch=patch, res=res, noise=noise[sl], use_kernel=False)))
    diffs["crop_patchify"] = _compare("crop_patchify", [ker],
                                      [np.concatenate(refs)])
    return diffs


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------

def _numpy_lockstep(spec, tab, out) -> list:
    """Drive core/madeye.MadEyeController over camera 0's recorded
    observation stream and compare its decisions with the fleet's lane 0,
    step by step. Box geometry is rebuilt from the recorded centroid,
    spread and extent (two centres at centroid +- spread, one box of side
    extent), which reproduces exactly the statistics the numpy zoom rule
    derives from boxes. Returns one bool per step."""
    from repro.core.madeye import MadEyeController, Observation
    from repro.fleet.state import workload_spec

    wl = spec.workload_obj()
    pairs = workload_spec(wl).pairs
    ctrl = MadEyeController(spec.grid_obj(), wl, budget=spec.budget_obj())
    t = {k: np.asarray(v) for k, v in tab._asdict().items()}
    order = np.asarray(out.order)[:, 0]
    n_exp = np.asarray(out.n_explored)[:, 0]
    zooms = np.asarray(out.zooms)[:, 0]
    sent = np.asarray(out.sent)[:, 0]

    def observe_one(e, c, z):
        cen = t["centroid"][e, c, z].astype(np.float64)
        s = float(t["spread"][e, c, z])
        has = int(t["nbox"][e, c, z]) > 0
        centers = (np.array([[cen[0] + s, cen[1]], [cen[0] - s, cen[1]]])
                   if has else np.zeros((0, 2)))
        sizes = (np.full((1, 2), float(t["extent"][e, c, z])) if has
                 else np.zeros((0, 2)))
        return Observation(
            counts={pr: float(t["counts"][e, c, z, i])
                    for i, pr in enumerate(pairs)},
            areas={pr: float(t["areas"][e, c, z, i])
                   for i, pr in enumerate(pairs)},
            centroid=cen, has_boxes=has, box_centers=centers,
            box_sizes=sizes)

    agree = []
    for e in range(order.shape[0]):
        ctrl.report_network(float(t["mbps"][e]), float(t["rtt"][e]))
        res = ctrl.step(lambda cells, zs, _e=e: [
            observe_one(_e, c, int(z)) for c, z in zip(cells, zs)])
        zoom_of = {c: int(z) for c, z in zip(res.explored, res.zooms)}
        if len(res.explored) > 1:
            # run_madeye's rank-agreement feedback, at the f32 ranking
            # precision both controllers share
            true = np.asarray([t["acc_true"][e, c, zoom_of[c]]
                               for c in res.explored], np.float32)
            pred = np.asarray(res.pred_acc, np.float32)
            hit = float(res.explored[int(np.argmax(pred))]
                        == res.explored[int(np.argmax(true))])
            ctrl.report_train_acc(0.9 * ctrl.train_acc + 0.1 * hit)
        j_order = [int(c) for c in order[e][: n_exp[e]]]
        agree.append(
            j_order == list(res.explored)
            and set(np.flatnonzero(sent[e]).tolist()) == set(res.sent)
            and {c: int(zooms[e][c]) for c in j_order} == zoom_of)
    return agree


def controller_phase(*, n_cameras: int = N_CAMERAS,
                     n_steps: int = N_STEPS, scene_seed: int = 7) -> dict:
    """The `scene` provider on a homogeneous fleet: every camera watches
    the same world, so every lane must decide like lane 0, and lane 0
    like the numpy reference controller fed the same observations."""
    from repro.fleet import (
        FleetRunSpec,
        materialize_scene_tables,
        prepare_fleet_run,
        run_fleet,
    )

    spec = FleetRunSpec(
        provider="scene", n_cameras=n_cameras, n_steps=n_steps,
        budget={"fps": FPS},
        provider_kwargs={"scene_seeds": [scene_seed] * n_cameras,
                         "mbps": MBPS, "rtt_ms": RTT_MS})
    r = run_fleet(spec)
    for name in DECISIONS:
        x = np.asarray(getattr(r.out, name))
        check((x == x[:, :1]).all(), f"homogeneous fleet: {name} differs "
              f"between cameras")
    prep = prepare_fleet_run(spec)
    tab = materialize_scene_tables(prep.cfg, prep.wl, prep.statics,
                                   prep.state, prep.provider)
    agree = _numpy_lockstep(spec, tab, r.out)
    share = float(np.mean(agree))
    first = next((e for e, ok in enumerate(agree) if not ok), None)
    say("controller", f"{n_cameras} cameras x {n_steps} steps: lanes "
        f"identical; numpy-controller agreement {share!r} of steps "
        f"(first divergence: {first}); compile_s="
        f"{r.timings['compile_s']!r} steady_s={r.timings['steady_s']!r}")
    check(share == 1.0, f"fleet decisions diverge from the numpy "
          f"controller at step {first}")
    return {"agreement": share, "timings": r.timings}


# ---------------------------------------------------------------------------
# detector (+ distill)
# ---------------------------------------------------------------------------

def detector_spec(det_cfg: str | None = FULL, *, n_cameras: int = N_CAMERAS,
                  n_steps: int = N_STEPS, shortlist_k: int = SHORTLIST_K,
                  **kw):
    """The smoke run's detector fleet; det_cfg None is the smoke width."""
    from repro.fleet import FleetRunSpec

    return FleetRunSpec(
        provider="detector", n_cameras=n_cameras, n_steps=n_steps,
        shortlist_k=shortlist_k, budget={"fps": FPS}, metrics=True,
        provider_kwargs={} if det_cfg is None else {"det_cfg": det_cfg},
        **kw)


def _check_episode(r, n_cells: int) -> None:
    out = r.out
    for name in ("pred_acc", "acc_chosen", "path_time"):
        check(np.isfinite(np.asarray(getattr(out, name))).all(),
              f"non-finite {name}")
    for name, v in r.metrics.items():
        check(np.isfinite(np.asarray(v, np.float64)).all(),
              f"non-finite metric {name}")
    check(0.0 <= r.accuracy <= 1.0, f"accuracy {r.accuracy} not in [0, 1]")
    chosen = np.asarray(out.chosen)
    check(((chosen >= 0) & (chosen < n_cells)).all(), "chosen cell id "
          "out of range")
    explored = np.asarray(out.explored)
    e, f = chosen.shape
    check(explored[np.arange(e)[:, None], np.arange(f)[None], chosen].all(),
          "a chosen cell was not explored")
    check(sum(r.frames_sent) > 0, "no frame was sent")


def _orientations(r) -> np.ndarray:
    """[E, F, 2] chosen (cell, zoom index) per camera-step."""
    chosen = np.asarray(r.out.chosen)
    zooms = np.asarray(r.out.zooms)
    e, f = chosen.shape
    z = zooms[np.arange(e)[:, None], np.arange(f)[None], chosen]
    return np.stack([chosen, z], -1)


def orientation_agreement(a, b) -> float:
    """Share of camera-steps whose chosen orientation is the same."""
    return float((_orientations(a) == _orientations(b)).all(-1).mean())


def detector_phase(det_cfg: str | None = FULL, **kw) -> dict:
    import jax

    from repro.fleet import run_fleet

    spec = detector_spec(det_cfg, **kw)
    r = run_fleet(spec)
    _check_episode(r, spec.grid_obj().n_cells)
    peak = peak_bytes(jax.devices()[0])
    say("detector", f"{spec.n_cameras} cameras x {r.n_steps} steps, "
        f"K={spec.shortlist_k}: accuracy={r.accuracy!r} frames_sent="
        f"{sum(r.frames_sent)}")
    say("detector", f"one smoke run, not a measurement: compile_s="
        f"{r.timings['compile_s']!r} steady_s={r.timings['steady_s']!r} "
        f"peak_bytes_in_use={peak}")
    # the precision pair renders crops PRECISION_CHUNK windows at a time:
    # at the default slab the "highest" program needs 14.6 GiB of the
    # chip's 16 GiB (compiled for a described v5e)
    pspec = dataclasses.replace(spec, provider_kwargs=dict(
        spec.provider_kwargs, chunk=PRECISION_CHUNK))
    r_lo = run_fleet(pspec)
    with jax.default_matmul_precision("highest"):
        r_hi = run_fleet(pspec)
    share = orientation_agreement(r_lo, r_hi)
    say("detector", f"chosen orientations agreeing, default vs highest "
        f"matmul precision: {share!r}; default slab vs "
        f"{PRECISION_CHUNK}-window slab: {orientation_agreement(r, r_lo)!r} "
        f"(information, not a gate)")
    return {"accuracy": r.accuracy, "timings": r.timings, "peak": peak,
            "precision_agreement": share}


def distill_phase(det_cfg: str | None = FULL, **kw) -> dict:
    from repro.fleet import run_fleet

    spec = detector_spec(det_cfg, distill=True, **kw)
    check(spec.distill.head_only, "expected the head-only DistillSpec")
    r = run_fleet(spec)
    _check_episode(r, spec.grid_obj().n_cells)
    loss = np.asarray(r.metrics["distill_loss"], np.float64)   # [E, F]
    check(np.isfinite(loss).all(), "non-finite distill loss")
    upd = loss >= 0.0
    check(upd.any(), "no distillation update happened")
    steps = [v for v in r.distill_loss if v >= 0.0]
    say("distill", f"{int(upd.sum())} camera updates over {len(steps)} "
        f"steps; fleet-mean loss {steps[0]!r} -> {steps[-1]!r}; "
        f"compile_s={r.timings['compile_s']!r} "
        f"steady_s={r.timings['steady_s']!r}")
    return {"updates": int(upd.sum()), "loss": steps}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def shard_phase(det_cfg: str | None = FULL, *, n_chips: int = 4,
                **kw) -> dict:
    """The detector fleet sharded over `n_chips` devices on the mesh
    `data` axis, against the same spec unsharded on device 0."""
    import jax

    from repro.fleet import ShardSpec, run_fleet

    spec = detector_spec(det_cfg, shard=ShardSpec(kind="debug",
                                                  n_data=n_chips), **kw)
    devs = jax.devices()[:n_chips]
    r_sh = run_fleet(spec)
    _check_episode(r_sh, spec.grid_obj().n_cells)
    peaks = [peak_bytes(d) for d in devs]
    per = spec.n_cameras // n_chips
    # every leaf of the fleet state leads with the fleet axis; the [E, F]
    # outputs carry it second
    placed = [(f"state.{k}", x, 0) for k, x in zip(
        r_sh.state._fields, r_sh.state)]
    placed.append(("out.chosen", r_sh.out.chosen, 1))
    for name, x, axis in placed:
        for leaf in jax.tree.leaves(x):
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == n_chips,
                  f"{name} is not spread over {n_chips} devices")
            check(all(s.data.shape[axis] == per for s in shards),
                  f"{name} shards are not {per} cameras each")
    r_one = run_fleet(dataclasses.replace(spec, shard=None))
    share = orientation_agreement(r_sh, r_one)
    say("shard", f"{spec.n_cameras} cameras over {n_chips} chips "
        f"({per} each): chosen orientations agreeing with the unsharded "
        f"run {share!r}; sharded steady_s={r_sh.timings['steady_s']!r}, "
        f"unsharded steady_s={r_one.timings['steady_s']!r} (one smoke "
        f"run, not a measurement)")
    say("shard", f"peak_bytes_in_use per device after the sharded run: "
        f"{peaks}")
    return {"agreement": share, "peaks": peaks}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the detector fleet sharded over four "
                         "chips and its unsharded comparison")
    args = ap.parse_args(argv)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    t0 = time.perf_counter()
    dev = device_phase(args.chips)
    if args.chips == 4:
        shard_phase(n_chips=4)
    else:
        from repro.configs import get_config

        kernel_phase(get_config(FULL))
        controller_phase()
        detector_phase()
        distill_phase()
    say("done", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
