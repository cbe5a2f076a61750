"""The whole step's share of the chips' bf16 peak: detector FLOPs of one
fleet step (every camera's scored windows, counted from shapes by
flops.py through the backbone the configuration names) times fleet
steps per second of the traced window, over chips x the peak of the
device kind (peaks.py)."""
from benchmarks.chip import flops, peaks


def read(run):
    t = run.trace
    if "detector" not in run.config or not t or t["busy_s"] <= 0:
        return None
    import jax

    kind = jax.devices()[0].device_kind
    windows = (run.traffic.get("shortlist_k")
               or run.spec.grid_obj().n_orientations)
    step = flops.detector_step_flops(run.config, run.spec.n_cameras,
                                     windows)
    steps_per_s = t["calls"] * run.traffic["n_steps"] / t["window_s"]
    return 100.0 * step * steps_per_s / (
        run.chips * peaks.peak(kind, "bf16_flops"))
