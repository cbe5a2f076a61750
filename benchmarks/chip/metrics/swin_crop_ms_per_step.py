"""Device self time of the `crop_to_tokens` stage in a Swin cell (render
noise, and the crops painted into patch-4 tokens by `crop_patchify`,
eight patches a plane row on the kernel path) per fleet step, from the
profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "crop_to_tokens")
