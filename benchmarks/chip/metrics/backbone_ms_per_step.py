"""Device self time of the `backbone` stage (the detector's ViT over
the [F*K] scored windows) per fleet step, from the profiled window of
`stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "backbone")
