"""Host clock around `_episode.lower(...).compile()` in set-up, with the
checkout's persistent compilation cache."""


def read(run):
    return run.compile_s
