"""The whole step's share of the chips' bf16 peak in a Swin cell, read
as `detector_mfu_pct` reads it: the detector FLOPs of one fleet step,
counted by `flops.py` through `backbones/swin.py`'s `window_flops` and
the shared neck and heads, times fleet steps per second of the traced
window, over chips x the peak of the device kind."""
from benchmarks.chip import harness


def read(run):
    return harness.load_module("metrics", "detector_mfu_pct").read(run)
