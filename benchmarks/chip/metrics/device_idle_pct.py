"""100 x (1 - busy / window) over the traced calls: busy is the union of
each device's op intervals, averaged over the chips used."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
