"""Profiler trace -> per-device busy time, op counts, collectives, gaps.

`reduce_dir` reads the `.xplane.pb` that `jax.profiler.trace` writes
(through `jax.profiler.ProfileData`, nothing else) and hands plain
event lists to `reduce_events`, which is what the tests check on a
small recorded trace:

  device ops   the events of each device plane's "XLA Ops" line (or
               its first line whose name holds "Ops")
  window       first to last host span named `bench/call` (the calls
               the harness traced); without such spans, the ops' extent
  busy         the union of a device's op intervals inside the window
  collectives  ops whose name holds one of COLLECTIVES (async -start and
               -done halves included)
  idle gaps    holes in the union, each named by the innermost host
               event that covers its middle
  top ops      the ops of the fullest device by self time (less the ops
               nested inside, as a while loop holds its body), each
               named by its HLO name and result type
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench/call"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP = 10


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def union(intervals) -> list[tuple[int, int]]:
    """Merge [start, end) intervals (ns) into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def short_name(name: str) -> str:
    """`%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(...)` ->
    `%fusion.12 bf16[8,128]`, a tuple result as `(...)`; other names as
    they are."""
    m = re.match(r"(%[\w.\-]+) = (\S+)", name)
    if not m:
        return name[:80]
    typ = "(...)" if m.group(2).startswith("(") else m.group(2).split("{")[0]
    return f"{m.group(1)} {typ}"


def self_times(ops) -> dict[str, int]:
    """Total self time (ns) per op name: each op's duration less that of
    the ops nested inside it on the same line."""
    totals: dict[str, int] = {}
    stack: list[list] = []          # [end, name, self_ns]
    for n, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            end, pn, own = stack.pop()
            totals[pn] = totals.get(pn, 0) + own
        if stack and s + d <= stack[-1][0]:
            stack[-1][2] -= d
        stack.append([s + d, n, d])
    for end, pn, own in stack:
        totals[pn] = totals.get(pn, 0) + own
    return totals


def host_name_at(t: int, host_events) -> str:
    """The innermost (shortest) host event that covers instant t."""
    best = None
    for name, s, d in host_events:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host:none"


def reduce_events(devices: list[list[tuple[str, int, int]]],
                  host_events: list[tuple[str, int, int]]) -> dict:
    """devices: per device, its ops as (name, start_ns, duration_ns);
    host_events: (name, start_ns, duration_ns) of the host threads.
    Returns seconds and counts; see the module docstring."""
    spans = [(s, s + d) for n, s, d in host_events if n == WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        ends = [(s, s + d) for ops in devices for _, s, d in ops]
        lo = min((s for s, _ in ends), default=0)
        hi = max((e for _, e in ends), default=0)
    window_ns = max(hi - lo, 0)

    per_dev = []
    for ops in devices:
        inside = [(n, s, d) for n, s, d in ops
                  if s + d > lo and s < hi]
        busy = union(clip([(s, s + d) for _, s, d in inside], lo, hi))
        totals = self_times(inside)
        op_ns = sum(totals.values())
        coll_ns = sum(v for n, v in totals.items() if is_collective(n))
        gaps = []
        edge = lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        per_dev.append({
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "n_ops": len(inside),
            "op_s": op_ns * 1e-9,
            "collective_s": coll_ns * 1e-9,
            "totals": totals,
            "gaps": gaps,
        })

    n = max(len(per_dev), 1)
    fullest = max(per_dev, key=lambda d: d["op_s"], default=None)
    top_ops, idle = [], []
    if fullest is not None:
        top_ops = [[short_name(k), v * 1e-9] for k, v in sorted(
            fullest["totals"].items(), key=lambda kv: -kv[1])[:TOP]]
        longest = sorted(fullest["gaps"], key=lambda g: g[0] - g[1])[:TOP]
        idle = [[host_name_at((s + e) // 2, host_events), (e - s) * 1e-9]
                for s, e in longest]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "devices": [{k: d[k] for k in ("busy_s", "n_ops", "op_s",
                                       "collective_s")} for d in per_dev],
        "top_ops": top_ops,
        "idle_gaps": idle,
    }


def read_xplane(path: Path, n_devices: int):
    """(device op lists, host events, plane summary) from one xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[int, list] = {}
    host: list = []
    summary = []
    for plane in pd.planes:
        lines = list(plane.lines)
        summary.append((plane.name, [ln.name for ln in lines]))
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(2))
            if idx >= n_devices:
                continue
            names = [ln.name for ln in lines]
            want = OPS_LINE if OPS_LINE in names else next(
                (n for n in names if "Ops" in n), None)
            for ln in lines:
                if ln.name == want:
                    devices.setdefault(idx, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in ln.events)
    return [devices[k] for k in sorted(devices)], host, summary


def reduce_dir(trace_dir: Path, n_devices: int) -> dict:
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host, summary = read_xplane(paths[-1], n_devices)
    red = reduce_events(devices, host)
    red["planes"] = summary
    return red
