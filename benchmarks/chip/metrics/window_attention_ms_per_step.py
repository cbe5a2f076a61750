"""Device self time of the `window_attention` substage of the backbone
(each Swin block's roll, window partition, projections, biased and
masked attention, un-partition and roll back) per fleet step, from the
profiled window of `substages.substage_times`."""
from benchmarks.chip import substages


def read(run):
    return substages.ms_per_step(run, "window_attention")
