"""Device self time of the `controller` stage (`fleet_step`: budget,
shape search, path, zoom, ranking and send) per fleet step, from the
profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "controller")
