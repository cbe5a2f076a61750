"""Device self time of the `shortlist` stage (`shortlist_windows`: the
K of N x Z windows each camera scores) per fleet step, from the
profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "shortlist")
