"""Device self time of the `crop_to_tokens` stage (render noise, crops
and their patch embedding: `crop_patchify`, kernel or jnp path) per
fleet step, from the profiled window of `stages.stage_times`."""
from benchmarks.chip import stages


def read(run):
    return stages.ms_per_step(run, "crop_to_tokens")
