"""Share of the windows the detector scored that a decision reads: the
controller gathers one window (the chosen zoom) of each explored cell,
so 100 x sum of `n_explored` / (steps x cameras x K) over the last
window call's FleetStepOut, K being `shortlist_k` or all N x Z windows
without a shortlist. Detector cells only."""


def read(run):
    if "detector" not in run.config or run.outputs is None:
        return None
    n = run.outputs["out"].get("n_explored")
    if n is None or n.size == 0:
        return None
    c = run.spec.grid_obj().n_orientations
    k = run.traffic.get("shortlist_k") or c
    return 100.0 * float(n.sum()) / (n.size * min(k, c))
