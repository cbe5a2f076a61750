"""Device self time of the `oracle_grade` stage (the scene teachers
grading every window of every camera, `observe_all_cells`) per fleet
step, from the profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "oracle_grade")
