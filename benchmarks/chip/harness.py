"""The chip benchmark's harness: one cell of BENCHMARK.json, end to end.

A cell names a configuration (`configs/<name>.json`), a traffic mix
(`traffic/<name>.json`) and a chip count. Metrics are readers
(`metrics/<name>.py`, one `read(ctx)` each) and the correctness check
is a reference module (`reference/<config["reference"]>.py`, one
`check(run)` each). A configuration with a `detector` names its
backbone (`backbones/<config["backbone"]>.py`, `vit` where it names
none): the weights (`reference/weights.py`), the reference forward
(`reference/vision.py`) and the FLOP count (`flops.py`) take from it
everything up to the neck's input, and share the neck, heads and
decode. All are found by the names in BENCHMARK.json and the
configuration, so a new cell, metric, configuration or backbone is new
files, never an edit.

One run:

  set-up   make the detector weights on the device from the seed
           (through the configuration's backbone), let
           the reference set what it derives from them (`prepare`: the
           detector's operating point), build the FleetRunSpec, AOT
           lower + compile the episode scan
           (persistent cache inside the checkout), one warm-up call
  window   whole calls of the compiled episode, one dispatched ahead
           of the one waited for, until `seconds` have passed; then
           the calls in flight finish inside the window
  trace    (trace runs only) a second, profiled window of whole calls
  check    the last window call's outputs against the reference

The platform gate lives in `run.py`, which refuses anything but a TPU;
tests drive `run_cell` on the CPU past it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

# profiled window: whole calls, at least this many and at least this long
TRACE_MIN_CALLS = 2
TRACE_MIN_S = 2.0


class BenchError(RuntimeError):
    """The cell cannot be run as BENCHMARK.json describes it."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json; known: "
                     f"{', '.join(c['name'] for c in bench['workloads'])}")


def load_json(kind: str, name: str, base: Path = HERE) -> dict:
    """`configs/<name>.json` or `traffic/<name>.json`."""
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(base)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, base: Path | None = None):
    """`metrics/<name>.py`, `reference/<name>.py` or `backbones/<name>.py`
    as a fresh module, under `base` (default: this directory, as
    `HERE` says when called)."""
    base = base or HERE
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path.relative_to(base)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_backbone(name: str | None):
    """The detector backbone a configuration's `backbone` key names
    (`backbones/<name>.py`); `vit` where it names none."""
    return load_module("backbones", name or "vit")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The metric entries this cell reports: its end-to-end metrics, or
    with `trace` its per-layer ones (a `workloads` list narrows either)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


# ---------------------------------------------------------------------------
# the run's inputs, from the seed
# ---------------------------------------------------------------------------

def draw_seeds(seed: int, n_cameras: int) -> dict:
    """Everything the program receives that varies with --seed: the
    FleetRunSpec seed, one scene seed per camera, the weights' seed."""
    rng = np.random.default_rng(int(seed))
    top = 2 ** 31 - 1
    return {"spec_seed": int(rng.integers(0, top)),
            "scene_seeds": [int(s) for s in rng.integers(0, top, n_cameras)],
            "weight_seed": int(rng.integers(0, top))}


def build_spec(config: dict, traffic: dict, chips: int, seeds: dict,
               det_params=None):
    """The FleetRunSpec this cell runs (the program's public entry)."""
    from repro.fleet import FleetRunSpec, ShardSpec
    from repro.scene_jax.scene import SceneSpec

    n_cameras = config["cameras_per_chip"] * chips
    kwargs = dict(config.get("provider_kwargs", {}))
    kwargs.update(
        spec=SceneSpec(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in config["scene"].items()}),
        scene_seeds=seeds["scene_seeds"], mbps=traffic["mbps"],
        rtt_ms=traffic["rtt_ms"],
        **{k: traffic[k] for k in ("n_people", "n_cars", "person_speed",
                                   "car_speed", "churn")})
    if "detector" in config:
        kwargs.update(det_cfg=detector_config(config),
                      det_params=det_params)
    return FleetRunSpec(
        provider=config["provider"], n_cameras=n_cameras,
        n_steps=traffic["n_steps"], seed=seeds["spec_seed"],
        workload=tuple(tuple(q) for q in config["workload"]),
        budget={"fps": float(traffic["fps"])}, grid=dict(config["grid"]),
        provider_kwargs=kwargs, shortlist_k=traffic.get("shortlist_k"),
        metrics=traffic.get("metrics"), distill=traffic.get("distill"),
        shard=(ShardSpec(kind="debug", n_data=chips) if chips > 1
               else None))


def detector_config(config: dict):
    from repro.configs.base import DetectorConfig

    return DetectorConfig(name=config["name"], **config["detector"])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a run hands to the metric readers and the reference."""
    cell: dict
    config: dict
    traffic: dict
    chips: int
    seed: int
    seeds: dict
    spec: object = None
    weights: object = None            # harness-made detector params
    setup_s: float = 0.0
    compile_s: float = 0.0
    warmup_s: float = 0.0
    window_calls: int = 0
    window_s: float = 0.0
    call_s: list = dataclasses.field(default_factory=list)
    hbm_bytes: int = 0                # memory_analysis, fullest device
    peak_bytes: int = 0               # peak_bytes_in_use, fullest device
    trace: dict | None = None         # devtrace.reduce() of the window
    outputs: dict | None = None       # host copy of the last window call

    @property
    def camera_steps_per_call(self) -> int:
        return self.spec.n_cameras * self.traffic["n_steps"]


def enable_compile_cache() -> str:
    """The persistent cache at a fixed path inside the checkout; every
    program, however fast it compiles, is kept."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def memory_bytes(compiled) -> int:
    """Device bytes the compiled program needs, per device: arguments,
    outputs and temporaries, less what outputs alias."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def peak_in_use(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, log=print,
             config: dict | None = None, traffic: dict | None = None
             ) -> tuple[Run, dict]:
    """Set-up, window, optional trace and check for one cell.

    `config`/`traffic` override the files the cell names (tests pass
    small ones). Returns the Run and the result dict for the last line
    of stdout. `t_start` is when the process started: set-up counts
    from there."""
    import jax

    from repro.fleet import prepare_fleet_run
    from repro.fleet.runner import _episode, shard_fleet

    config = config or load_json("configs", cell["config"])
    traffic = traffic or load_json("traffic", cell["traffic"])
    chips = int(cell["chips"])
    n_cameras = config["cameras_per_chip"] * chips
    seeds = draw_seeds(seed, n_cameras)
    run = Run(cell=cell, config=config, traffic=traffic, chips=chips,
              seed=seed, seeds=seeds)
    devices = jax.devices()[:chips]

    # -- set-up --------------------------------------------------------
    stages = {"start_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    ref = load_module("reference", config["reference"])
    if "detector" in config:
        from benchmarks.chip.reference import weights

        run.weights = jax.block_until_ready(
            weights.init(config, seeds["weight_seed"]))
    stages["weights_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if hasattr(ref, "prepare"):
        ref.prepare(run)
    stages["prepare_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    run.spec = build_spec(config, traffic, chips, seeds, run.weights)
    prep = prepare_fleet_run(run.spec)
    state, provider = prep.state, prep.provider
    if prep.mesh is not None:
        state = shard_fleet(state, prep.mesh)
        provider = provider.shard(prep.mesh)
    stages["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered = _episode.lower(prep.cfg, prep.wl, prep.statics, state,
                             provider, metrics=run.spec.metrics)
    stages["lower_s"] = time.perf_counter() - t0
    compiled = lowered.compile()
    run.compile_s = time.perf_counter() - t0
    run.hbm_bytes = memory_bytes(compiled)
    args = (prep.statics, state, provider)
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    run.warmup_s = time.perf_counter() - t0
    run.setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s={run.setup_s!r} compile_s={run.compile_s!r} "
        f"warmup_s={run.warmup_s!r} "
        + " ".join(f"{k}={v!r}" for k, v in stages.items())
        + f" cameras={run.spec.n_cameras} steps/call={traffic['n_steps']} "
        f"chips={chips} provider_kwargs={config.get('provider_kwargs')}")

    # -- window --------------------------------------------------------
    # one call is dispatched ahead of the one waited for, so that the
    # chip stays fed while the host stands still; once `seconds` have
    # passed nothing more is sent, and the window closes when all that
    # was sent has finished
    t0 = t1 = time.perf_counter()
    pending = compiled(*args)
    run.window_calls = 1
    while True:
        ahead = None
        if time.perf_counter() - t0 < seconds:
            ahead = compiled(*args)
            run.window_calls += 1
        res = jax.block_until_ready(pending)
        t2 = time.perf_counter()
        run.call_s.append(t2 - t1)
        t1, pending = t2, ahead
        if pending is None:
            break
    run.window_s = time.perf_counter() - t0
    run.peak_bytes = peak_in_use(devices)
    log(f"[window] calls={run.window_calls} window_s={run.window_s!r} "
        f"call_s min/max={min(run.call_s)!r}/{max(run.call_s)!r}")
    log(f"[memory] memory_analysis total={run.hbm_bytes} bytes "
        f"({run.hbm_bytes / 2**30!r} GiB) peak_bytes_in_use="
        f"{run.peak_bytes} bytes ({run.peak_bytes / 2**30!r} GiB)")

    if trace:
        run.trace = traced_window(compiled, args, devices, log)

    # -- check ---------------------------------------------------------
    run.outputs = host_outputs(res)
    del res, compiled, args, state, provider, prep
    checks = ref.check(run)
    return run, result(bench, run, checks, trace)


def traced_window(compiled, args, devices, log) -> dict:
    """Whole calls under the profiler, reduced to per-device busy time,
    op counts and the longest ops and gaps (devtrace)."""
    import jax

    from benchmarks.chip import devtrace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    calls, t0 = 0, time.perf_counter()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
        while True:
            with jax.profiler.TraceAnnotation("bench/call"):
                jax.block_until_ready(compiled(*args))
            calls += 1
            if (calls >= TRACE_MIN_CALLS
                    and time.perf_counter() - t0 >= TRACE_MIN_S):
                break
    wall_s = time.perf_counter() - t0
    red = devtrace.reduce_dir(TRACE_DIR, n_devices=len(devices))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    red["calls"] = calls
    red["wall_s"] = wall_s
    for name, lines in red.pop("planes")[:12]:
        log(f"[trace] plane {name!r}: lines {lines[:8]}")
    log(f"[trace] calls={calls} wall_s={wall_s!r} window_s="
        f"{red['window_s']!r} busy_s={red['busy_s']!r} "
        f"devices={len(red['devices'])}")
    return red


def host_outputs(res) -> dict:
    """The episode's (final state, per-step outputs) as numpy arrays."""
    import jax

    state, out = res[0], res[1]
    if not hasattr(out, "_asdict"):     # (FleetStepOut, extras)
        out = out[0]
    return {"out": {k: np.asarray(jax.device_get(v))
                    for k, v in out._asdict().items()},
            "state": {k: jax.device_get(v)
                      for k, v in state._asdict().items()}}


def result(bench: dict, run: Run, checks: list, trace: bool) -> dict:
    """The last line of stdout."""
    import jax

    metrics = {}
    for m in cell_metrics(bench, run.cell, trace):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": run.peak_bytes}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks),
           "attempted": run.window_calls * run.camera_steps_per_call,
           "failed": nonfinite_camera_steps(run) * run.window_calls,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["top_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def nonfinite_camera_steps(run: Run) -> int:
    """Camera-steps of one call whose predicted or oracle accuracy is not
    a finite number."""
    o = run.outputs["out"]
    bad = ~np.isfinite(o["acc_chosen"])                 # [E, F]
    bad |= ~np.isfinite(o["pred_acc"]).all(-1)
    return int(bad.sum())
