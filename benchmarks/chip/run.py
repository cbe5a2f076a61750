"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 benchmarks/chip/run.py --workload approx-k18-f15 --seed 7 \
        --seconds 30 --trace 0

Prints progress and the compared numbers on stderr and, as the last line
of stdout, one JSON object: correct, attempted, failed, metrics (the
cell's end-to-end metrics, or with --trace 1 its per-layer ones),
device, breakdown (--trace 1) and checks. Exits non-zero, printing no
result, where the default JAX device is not a TPU or there are fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    harness.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"run.py: the default JAX device is {devices[0].platform!r}, "
            f"not a TPU; this benchmark measures the chip only")
        return 3
    if len(devices) < int(cell["chips"]):
        log(f"run.py: {args.workload} needs {cell['chips']} chips, JAX "
            f"reports {len(devices)}")
        return 3
    log(f"[device] {devices[0].device_kind} x{len(devices)}; workload "
        f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}")
    _, res = harness.run_cell(bench, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START, log=log)
    for name, c in res["checks"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
