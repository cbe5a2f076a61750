"""Process start to the end of the warm-up call: imports, weights,
provider build, compile (from the persistent cache after the first run)
and one call of the episode."""


def read(run):
    return run.setup_s
