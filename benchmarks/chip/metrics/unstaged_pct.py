"""Share of the window's device op self time that no stage scope names
(the scan's own loop and carry ops, and anything a scope misses), mean
over chips, from the profiled window of `stages.stage_times`. It guards
the scopes against drift."""
from benchmarks.chip import stages


def read(run):
    t = stages.stage_times(run)
    if not t or t["op_s"] <= 0:
        return None
    return 100.0 * t["stages"][stages.UNSTAGED] / t["op_s"]
