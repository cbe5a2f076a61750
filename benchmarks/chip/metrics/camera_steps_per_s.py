"""Camera-steps completed in the window's whole calls over the wall time
of the window: from the first call's dispatch to the end of the last
call in flight (host clock)."""


def read(run):
    return run.window_calls * run.camera_steps_per_call / run.window_s
