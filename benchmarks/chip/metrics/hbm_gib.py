"""Device memory of the compiled episode on the fullest device:
arguments + outputs + temporaries - aliased, from memory_analysis() of
the executable the window runs."""


def read(run):
    return run.hbm_bytes / 2 ** 30
