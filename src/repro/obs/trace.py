"""Host spans and device stage names, both readable from one profile.

Two kinds of name, one per side of the dispatch:

  * stages — `STAGES` names the stages of the fleet step, and
    `stage(name)` (or the `staged(name)` decorator) opens a
    `jax.named_scope` for one. A scope changes only the `op_name`
    metadata of the ops traced inside it, never the compiled ops, so
    it costs nothing at run time; a profiler trace of the chip, read
    with that metadata, gives each stage's device time. Inside the
    `backbone` stage, `substage(name)` names one of `SUBSTAGES` (Swin's
    window attention and patch merging); a reader by stage still sees
    `backbone`, the first stage on the op's path.
  * host spans — `span(name)` always opens a
    `jax.profiler.TraceAnnotation`, so any `jax.profiler.trace` of the
    program shows its host phases beside the device ops. With a
    `Tracer` active it also records the span as Chrome trace JSON
    (`chrome://tracing`, Perfetto). A span times host work: call it
    outside jitted code, around work that ends in `block_until_ready`.

The Chrome JSON is stamped on the wall clock in microseconds
(`time.time_ns()`), the clock the profiler's host events carry:
`jax.profiler.ProfileData` gives them relative to the
`profile_start_time` stat of the trace's "Task Environment" plane,
itself wall-clock nanoseconds. So `ts * 1e3 - profile_start_time` puts
a Chrome span on the xplane's timeline.

Usage:

    from repro.obs.trace import span, stage, tracing

    with tracing("run_trace.json"):          # activate + save on exit
        with span("build", provider="scene"):
            ...

    with stage("controller"):                # inside traced code
        ...
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from contextlib import contextmanager

import jax

# The stages of one fleet step, in step order. An op's stage is the
# first component of its `op_name` path that is listed here.
STAGES = ("scene_advance", "oracle_grade", "shortlist", "crop_to_tokens",
          "backbone", "heads", "controller", "distill", "telemetry")


def stage(name: str):
    """`jax.named_scope(name)` for one of `STAGES`; any other name is
    refused, so every scope is one the trace reduction can read."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; STAGES: "
                         f"{', '.join(STAGES)}")
    return jax.named_scope(name)


# Parts of the `backbone` stage, read on their own. Never stages: an
# op's stage stays the first component of its path that is in STAGES.
SUBSTAGES = ("window_attention", "patch_merge")


def substage(name: str):
    """`jax.named_scope(name)` for one of `SUBSTAGES`, opened inside
    `stage("backbone")`; any other name is refused."""
    if name not in SUBSTAGES:
        raise ValueError(f"unknown substage {name!r}; SUBSTAGES: "
                         f"{', '.join(SUBSTAGES)}")
    return jax.named_scope(name)


def staged(name: str):
    """Decorator form of `stage`: the function's ops carry stage `name`.
    A fresh scope is opened per call, so concurrent or nested traces do
    not share one. Place it under `jax.jit` so the scope is inside the
    traced body."""
    stage(name)

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class Tracer:
    """Span recorder exporting the Chrome trace event format."""

    def __init__(self):
        self.events: list[dict] = []
        self._lock = threading.Lock()

    @contextmanager
    def span(self, name: str, **args):
        """Record one complete ("ph": "X") span around the with-body,
        inside a profiler annotation of the same name. Extra kwargs land
        in the event's `args` (must be JSON-native)."""
        with jax.profiler.TraceAnnotation(name):
            start = time.time_ns()
            try:
                yield self
            finally:
                dur = time.time_ns() - start
                ev = {"name": name, "ph": "X", "pid": os.getpid(),
                      "tid": threading.get_ident(),
                      "ts": start * 1e-3, "dur": dur * 1e-3}
                if args:
                    ev["args"] = {k: v if isinstance(
                        v, (int, float, str, bool, type(None))) else str(v)
                        for k, v in args.items()}
                with self._lock:
                    self.events.append(ev)

    def to_chrome(self) -> dict:
        """The chrome://tracing / Perfetto JSON object."""
        with self._lock:
            return {"traceEvents": list(self.events),
                    "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


# ---------------------------------------------------------------------------
# module-level activation (what library code talks to)
# ---------------------------------------------------------------------------

_active: Tracer | None = None


def activate(tracer: Tracer | None = None) -> Tracer:
    """Install `tracer` (or a fresh Tracer) as the active one."""
    global _active
    _active = tracer if tracer is not None else Tracer()
    return _active


def deactivate() -> Tracer | None:
    """Remove and return the active tracer."""
    global _active
    t, _active = _active, None
    return t


def active_tracer() -> Tracer | None:
    return _active


def span(name: str, **args):
    """Span on the active tracer; with none active, the profiler
    annotation alone (a no-op unless a profiler trace is running)."""
    t = _active
    if t is None:
        return jax.profiler.TraceAnnotation(name)
    return t.span(name, **args)


@contextmanager
def tracing(path: str | None = None):
    """Activate a fresh tracer for the with-body; save Chrome trace JSON
    to `path` on exit (when given) and restore the previous tracer."""
    prev = _active
    t = activate(Tracer())
    try:
        yield t
    finally:
        globals()["_active"] = prev
        if path is not None:
            t.save(path)
