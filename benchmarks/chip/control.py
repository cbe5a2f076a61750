"""Readings that set the limits of `correct`, for one cell on the chip.

    python3 benchmarks/chip/control.py --workload approx-k18-f15 \
        --seeds 101 102 103

For each seed, in one process: set-up and one call of the timed path as
run.py makes them, then the numbers the cell compares, read three ways:
the program as it is, the control (the reference in bfloat16 put in the
program's place; a second reading with the detector alone in bfloat16)
and each planted fault (reference/faults.py).
Prints one JSON line per seed and reading. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def score_gaps(run, n: int) -> dict:
    """Gaps between the program's kept scores (its crop-to-tokens and
    detector forward at the configured precision) and the reference's,
    sorted, over n windows drawn from the seed: widest, 99th percentile,
    median, and the share of windows whose count above the threshold
    differs. The reference is read at the default and the highest matmul
    precision, and in bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip import harness
    from benchmarks.chip.reference import detector, vision, world
    from repro.kernels.crop_patchify.ops import crop_patchify
    from repro.serving.engine import detector_scores_tokens

    ref = detector.reference_world(run)
    snap = ref["snap"]
    e_n, f_n = snap["oid"].shape[:2]
    rng = np.random.default_rng(run.seed)
    wins = world.windows(run.config["grid"])
    items = np.stack([rng.integers(0, e_n, n), rng.integers(0, f_n, n),
                      rng.integers(0, len(wins), n)], -1)
    e, f, w = items.T
    d = run.config["detector"]
    sc = run.config["scene"]
    keys = world.camera_keys(run.seeds["spec_seed"],
                             run.seeds["scene_seeds"])
    stride = world.stride(run.config, run.traffic)
    noise = jax.vmap(lambda k, fr: vision.noise_image(
        k, fr, d["img_res"], run.config["provider_kwargs"]["noise"],
        jnp.float32).transpose(1, 2, 0))(keys[f],
                                         jnp.asarray(e * stride, jnp.int32))
    m = sc["max_people"] + sc["max_cars"]
    kind = jnp.asarray(np.arange(m) >= sc["max_people"], jnp.int32)
    tokens = crop_patchify(
        jnp.asarray(snap["pos"][e, f]), jnp.asarray(snap["size"][e, f]),
        kind, jnp.asarray(snap["oid"][e, f]), jnp.asarray(wins[w][:, None]),
        run.weights["backbone"]["vit"]["patch_embed"], patch=d["patch"],
        res=d["img_res"], min_visible=sc["min_visible"], noise=noise)
    prog = detector_scores_tokens(run.weights,
                                  harness.detector_config(run.config),
                                  tokens[:, 0])
    prog = np.sort(np.asarray(prog.scores, np.float32), -1)
    thresh = run.config["provider_kwargs"]["thresh"]
    n_prog = (prog >= thresh).sum(-1)
    out = {}
    for name, dtype, prec in (("default", jnp.float32, None),
                              ("highest", jnp.float32, "highest"),
                              ("bf16", jnp.bfloat16, None)):
        kept = detector.detections(run, snap, items, dtype, prec)[0][
            :, :prog.shape[-1]]
        r = np.sort(kept, -1)
        gap = np.abs(prog - r)
        out[f"{name}_max"] = float(gap.max())
        out[f"{name}_p99"] = float(np.quantile(gap, 0.99))
        out[f"{name}_median"] = float(np.median(gap))
        out[f"{name}_count_flips"] = float(np.mean(
            (r >= thresh).sum(-1) != n_prog))
    out["near_thresh_share"] = float(
        np.mean(np.abs(prog - thresh) < detector.BAND))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program alone")
    ap.add_argument("--calibrate", type=int, default=0,
                    help="also compare this many detector windows: the "
                         "program's scores against the reference's")
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    from benchmarks.chip.reference import faults

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: not a TPU", file=sys.stderr)
        return 3

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        run, res = harness.run_cell(bench, cell, seed=seed, seconds=0.0,
                                    trace=False, t_start=t0, log=log)
        ref = harness.load_module("reference", run.config["reference"])
        rows = {"program": ref.readings(run)}
        if not args.no_control:
            rows["control"] = ref.readings(run, control="reference")
            rows["control_detector"] = ref.readings(run, control="detector")
            clean = run.outputs
            for name, fault in faults.FAULTS.items():
                run.outputs = dict(clean, out=fault(clean["out"]))
                rows[name] = ref.readings(run)
            run.outputs = clean
        if args.calibrate and "detector" in run.config:
            rows["score_gap"] = score_gaps(run, args.calibrate)
        for kind, r in rows.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": kind, **r}), flush=True)
        log(f"[control] seed {seed} done in "
            f"{time.perf_counter() - t0:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
