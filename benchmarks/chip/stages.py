"""Device time of each stage of the fleet step, from a profiled window.

The program names its stages with `jax.named_scope` (the names of
`repro.obs.trace.STAGES`, copied into `STAGES` here so that this file
reads a program that has none). A scope lands in the `op_name`
metadata of every op it traces, and the compiled program's HLO text
carries it per instruction:

  %fusion.12 = ... fusion(...), ..., metadata={op_name="jit(_episode)/
      while/body/crop_to_tokens/jit(_crop_patchify)/..."}

An op's stage is the first component of its `op_name` path that is a
stage; ops with none go to `unstaged`. `reduce_stages` groups the
window's per-op self times (`devtrace.self_times`, so nested ops count
once) by stage: the stages plus `unstaged` sum to the window's op self
time, mean over chips.

`stage_times(run)` measures it for a traced run of the harness: it
compiles the run's episode again (a hit in the persistent cache),
profiles a window of whole calls as the harness's traced window does,
and reduces it. Where the compiled program carries no stage it returns
None before profiling anything. The result is kept on the run, so the
stage readers share one window.
"""
from __future__ import annotations

import re
import shutil
import sys
import time

from benchmarks.chip import devtrace

STAGES = ("scene_advance", "oracle_grade", "shortlist", "crop_to_tokens",
          "backbone", "heads", "controller", "distill", "telemetry")
UNSTAGED = "unstaged"
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*$", re.M)
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
TOP = 5


def stage_of(op_name: str) -> str:
    """The first component of an `op_name` path that is a stage."""
    for part in op_name.split("/"):
        if part in STAGES:
            return part
    return UNSTAGED


def op_stages(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> stage, for every instruction of a
    compiled module's text (instruction names are unique in a module)."""
    out = {}
    for m in INSTRUCTION.finditer(hlo_text):
        name = OP_NAME.search(m.group(0))
        out[m.group(1)] = stage_of(name.group(1)) if name else UNSTAGED
    return out


def instruction(event_name: str) -> str:
    """A device op event's HLO instruction name: `%fusion.12 = bf16[..]
    fusion(...)` and `fusion.12` both give `fusion.12`."""
    return event_name.split("=", 1)[0].strip().lstrip("%").split(" ")[0]


def reduce_stages(devices: list[list[tuple[str, int, int]]],
                  host_events: list[tuple[str, int, int]],
                  stages: dict[str, str]) -> dict:
    """devices/host_events as `devtrace.reduce_events` takes them;
    stages: instruction name -> stage (`op_stages`). Returns the window,
    the op self time and each stage's self time in seconds, means over
    the devices, and the longest ops of each stage on the fullest one."""
    spans = [(s, s + d) for n, s, d in host_events
             if n == devtrace.WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        ends = [(s, s + d) for ops in devices for _, s, d in ops]
        lo = min((s for s, _ in ends), default=0)
        hi = max((e for _, e in ends), default=0)
    per_dev = []
    for ops in devices:
        inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
        totals = devtrace.self_times(inside)
        by_stage = dict.fromkeys(STAGES + (UNSTAGED,), 0)
        for name, ns in totals.items():
            by_stage[stages.get(instruction(name), UNSTAGED)] += ns
        per_dev.append((sum(totals.values()), by_stage, totals))
    n = max(len(per_dev), 1)
    top: dict[str, list] = {}
    if per_dev:
        fullest = max(per_dev, key=lambda d: d[0])[2]
        for name, ns in sorted(fullest.items(), key=lambda kv: -kv[1]):
            st = stages.get(instruction(name), UNSTAGED)
            if len(top.setdefault(st, [])) < TOP:
                top[st].append([devtrace.short_name(name), ns * 1e-9])
    return {
        "window_s": max(hi - lo, 0) * 1e-9,
        "op_s": sum(d[0] for d in per_dev) * 1e-9 / n,
        "stages": {k: sum(d[1][k] for d in per_dev) * 1e-9 / n
                   for k in STAGES + (UNSTAGED,)},
        "top": top,
    }


def stage_times(run) -> dict | None:
    """Per-stage device time of a profiled window of the run's episode
    (see the module docstring), with `calls` and `steps` of the window;
    None for an untraced run or a program without stage scopes."""
    if run.trace is None:
        return None
    if "_stage_times" not in run.__dict__:
        run.__dict__["_stage_times"] = _measure(run)
    return run.__dict__["_stage_times"]


def _measure(run) -> dict | None:
    import jax

    from benchmarks.chip import harness
    from repro.fleet import prepare_fleet_run
    from repro.fleet.runner import _episode, shard_fleet

    prep = prepare_fleet_run(run.spec)
    state, provider = prep.state, prep.provider
    if prep.mesh is not None:
        state = shard_fleet(state, prep.mesh)
        provider = provider.shard(prep.mesh)
    compiled = _episode.lower(prep.cfg, prep.wl, prep.statics, state,
                              provider, metrics=run.spec.metrics).compile()
    stages = op_stages(compiled.as_text())
    if not any(s != UNSTAGED for s in stages.values()):
        log("[stages] the compiled episode names no stage; nothing read")
        return None
    args = (prep.statics, state, provider)
    trace_dir = harness.TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    calls, t0 = 0, time.perf_counter()
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        while True:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                jax.block_until_ready(compiled(*args))
            calls += 1
            if (calls >= harness.TRACE_MIN_CALLS
                    and time.perf_counter() - t0 >= harness.TRACE_MIN_S):
                break
    del args, state, provider, prep, compiled
    try:
        path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
        devices, host, _ = devtrace.read_xplane(path, n_devices=run.chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    red = reduce_stages(devices, host, stages)
    red["calls"] = calls
    red["steps"] = calls * run.traffic["n_steps"]
    log(f"[stages] calls={calls} window_s={red['window_s']!r} "
        f"op_s={red['op_s']!r} "
        + " ".join(f"{k}={v!r}" for k, v in red["stages"].items()))
    for st, ops in red["top"].items():
        log(f"[stages] top {st}: {ops}")
    return red


def ms_per_step(run, name: str) -> float | None:
    """Stage `name`'s device self time per fleet step, in ms; None where
    nothing was read or the stage ran no op."""
    t = stage_times(run)
    if not t or t["stages"][name] <= 0:
        return None
    return 1e3 * t["stages"][name] / t["steps"]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
