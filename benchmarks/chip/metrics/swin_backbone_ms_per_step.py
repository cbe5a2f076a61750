"""Device self time of the `backbone` stage in a Swin cell (Swin-T's
four stages and the merge of the last two onto the head grid, over the
[F*K] scored windows) per fleet step, from the profiled window of
`stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "backbone")
