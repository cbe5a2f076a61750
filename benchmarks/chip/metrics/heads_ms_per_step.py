"""Device self time of the `heads` stage (neck, heads, box decoding,
the scatter back to all windows and `detections_obs`) per fleet step,
from the profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "heads")
