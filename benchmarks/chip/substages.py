"""Device time of the parts of the `backbone` stage, from a profiled window.

Inside its `backbone` stage the program names parts of a backbone with
`jax.named_scope` (`repro.obs.trace.SUBSTAGES`, copied into `SUBSTAGES`
here so that this file reads a program that has none): Swin's window
attention (each block's roll, window partition, biased and masked
attention and its projections, un-partition, roll back) and its patch
merges. An op's substage is the first component of its `op_name` path
that is a substage and comes after a `backbone` component:

  %fusion.7 = ... metadata={op_name="jit(_episode)/while/body/backbone/
      while/body/window_attention/dot_general"}

Ops outside the backbone, or in it under no substage, count in none.
`substage_times(run)` measures it for a traced run of the harness: it
compiles the run's episode again (a hit in the persistent cache),
profiles its own window of whole calls as `stages.py` does, and groups
the ops' self times (`devtrace.self_times`, so nested ops count once)
by substage, mean over chips. Where the compiled program names no
substage it returns None before profiling anything. The result is kept
on the run, so the readers share one window.
"""
from __future__ import annotations

import shutil
import time

from benchmarks.chip import devtrace, stages

SUBSTAGES = ("window_attention", "patch_merge")
STAGE = "backbone"


def substage_of(op_name: str) -> str | None:
    """The first substage on an `op_name` path after its `backbone`."""
    parts = op_name.split("/")
    if STAGE not in parts:
        return None
    return next((p for p in parts[parts.index(STAGE) + 1:]
                 if p in SUBSTAGES), None)


def op_substages(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> substage, for the instructions of a
    compiled module's text that have one."""
    out = {}
    for m in stages.INSTRUCTION.finditer(hlo_text):
        name = stages.OP_NAME.search(m.group(0))
        sub = substage_of(name.group(1)) if name else None
        if sub:
            out[m.group(1)] = sub
    return out


def reduce_substages(devices: list[list[tuple[str, int, int]]],
                     host_events: list[tuple[str, int, int]],
                     subs: dict[str, str]) -> dict:
    """devices/host_events as `devtrace.reduce_events` takes them; subs:
    instruction name -> substage (`op_substages`). Returns the window
    and each substage's op self time in seconds, means over devices."""
    spans = [(s, s + d) for n, s, d in host_events
             if n == devtrace.WINDOW_SPAN]
    ends = spans or [(s, s + d) for ops in devices for _, s, d in ops]
    lo = min((s for s, _ in ends), default=0)
    hi = max((e for _, e in ends), default=0)
    totals = dict.fromkeys(SUBSTAGES, 0)
    for ops in devices:
        inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
        for name, ns in devtrace.self_times(inside).items():
            sub = subs.get(stages.instruction(name))
            if sub:
                totals[sub] += ns
    n = max(len(devices), 1)
    return {"window_s": max(hi - lo, 0) * 1e-9,
            "substages": {k: v * 1e-9 / n for k, v in totals.items()}}


def substage_times(run) -> dict | None:
    """Per-substage device time of a profiled window of the run's
    episode, with `calls` and `steps` of the window; None for an
    untraced run or a program without substage scopes."""
    if run.trace is None:
        return None
    if "_substage_times" not in run.__dict__:
        run.__dict__["_substage_times"] = _measure(run)
    return run.__dict__["_substage_times"]


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
    subs = op_substages(compiled.as_text())
    if not subs:
        stages.log("[substages] the compiled episode names no substage; "
                   "nothing read")
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
    red = reduce_substages(devices, host, subs)
    red["calls"] = calls
    red["steps"] = calls * run.traffic["n_steps"]
    stages.log(f"[substages] calls={calls} window_s={red['window_s']!r} "
               + " ".join(f"{k}={v!r}"
                          for k, v in red["substages"].items()))
    return red


def ms_per_step(run, name: str) -> float | None:
    """Substage `name`'s device self time per fleet step, in ms; None
    where nothing was read or the substage ran no op."""
    t = substage_times(run)
    if not t or t["substages"][name] <= 0:
        return None
    return 1e3 * t["substages"][name] / t["steps"]
