"""Device self time of the `scene_advance` stage (the scene simulator
moving every camera's objects one controller step) per fleet step, from
the profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "scene_advance")
