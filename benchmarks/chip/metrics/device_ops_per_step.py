"""Device-op events in the traced calls, averaged over the chips used,
per fleet step (calls x steps per call)."""


def read(run):
    t = run.trace
    if not t or not t["devices"]:
        return None
    ops = sum(d["n_ops"] for d in t["devices"]) / len(t["devices"])
    if ops == 0:
        return None
    return ops / (t["calls"] * run.traffic["n_steps"])
