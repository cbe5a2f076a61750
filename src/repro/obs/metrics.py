"""In-scan device metrics: a per-step FleetMetrics pytree from inside
the jit'd episode.

MadEye's accuracy is governed by decisions the step outputs alone can't
explain: did the search shortlist actually contain the oracle-best
orientation (paper §3.3)? how far does the distilled detector's ranking
drift from the teacher's (§3.4)? is the budget sending what it planned?
`step_metrics` answers those *inside* the scanned step — everything it
reads is already on device, so metrics cost a handful of [F, N]
reductions and leave the scan as one more `[E, ...]` output, no extra
host transfers.

Gating: a static `MetricsSpec` rides `FleetRunSpec.metrics` (and the jit
cache key). `metrics=None` / `enabled=False` compiles the *exact*
pre-metrics scan — decisions are bit-identical either way, pinned by
tests/test_obs.py.

Emitted keys (each a per-step [F] array, stacked to [E, F] by the scan;
`METRIC_KEYS` maps the MetricsSpec flag that owns each group):

  ewma_label_mean   mean EWMA search label over visited cells — the
                    controller's own running accuracy estimate
  frames_sent       frames actually shipped this step (sum of `sent`)
  k_send            the budget's planned send count
  n_explored        search cells visited this step
  cells_visited     distinct cells ever visited (exploration coverage)
  shortlist_hit     1.0 when the oracle-best cell (argmax of acc_true
                    over all N*Z windows) is in the candidate shortlist
                    this step — always 1.0 for exhaustive providers
  chosen_rank       1-based oracle-accuracy rank of the chosen
                    orientation among the explored cells at their chosen
                    zooms; 0 on degenerate steps (<2 explored cells or
                    an all-zero oracle row). The in-scan version of
                    benchmarks/bench_rank_quality's chosen-rank metric.
  score_mean        mean predicted accuracy over explored cells
  score_max         max predicted accuracy over explored cells

`chosen_rank` is the acceptance instrument for the ROADMAP's in-scan
distillation item (converging toward 1.0 == detector ranks like the
teacher); `shortlist_hit` is the one for adaptive-K (shrinking K is free
until the hit-rate dips).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.obs.trace import staged

# flag on MetricsSpec -> the FleetMetrics keys it owns
METRIC_KEYS = {
    "ewma": ("ewma_label_mean",),
    "budget": ("frames_sent", "k_send", "n_explored", "cells_visited"),
    "shortlist": ("shortlist_hit",),
    "rank": ("chosen_rank", "score_mean", "score_max"),
}


@dataclass(frozen=True)
class MetricsSpec:
    """Static (hashable, jit-cache-keyed) gate for in-scan metrics.

    The default `MetricsSpec()` turns everything on; flags drop metric
    groups from the scan outputs entirely (the pytree shrinks — nothing
    is computed for a disabled group). `enabled=False` is equivalent to
    passing no spec at all."""
    enabled: bool = True
    ewma: bool = True
    budget: bool = True
    shortlist: bool = True
    rank: bool = True

    def keys(self) -> tuple:
        if not self.enabled:
            return ()
        return tuple(k for flag, keys in METRIC_KEYS.items()
                     if getattr(self, flag) for k in keys)


@staged("telemetry")
def step_metrics(spec: MetricsSpec, cfg, provider, state_pre, state_post,
                 obs, out) -> dict:
    """One step's FleetMetrics — a {name: [F] array} pytree.

    Runs inside the episode scan body after `fleet_step`: `state_pre` is
    the controller state the provider observed with (the shortlist is a
    pure function of it, so the candidate set is *recomputed* here
    bit-identically rather than threaded through the provider seam),
    `state_post`/`out` are fleet_step's results, `obs` this step's
    observation tables (for the oracle-best window).
    """
    from repro.core import ewma
    from repro.fleet.step import gather_at_zoom

    m: dict[str, jnp.ndarray] = {}
    f, n = out.explored.shape
    arange_f = jnp.arange(f)

    if spec.ewma:
        lab = ewma.labels(state_post.ewma, delta_weight=cfg.delta_weight)
        seen = state_post.ewma.seen > 0
        m["ewma_label_mean"] = (jnp.where(seen, lab, 0.0).sum(-1)
                                / jnp.maximum(seen.sum(-1), 1))

    if spec.budget:
        from repro.fleet.state import NEVER_VISITED

        m["frames_sent"] = out.sent.sum(-1).astype(jnp.int32)
        m["k_send"] = out.k_send
        m["n_explored"] = out.n_explored
        m["cells_visited"] = jnp.sum(
            state_post.last_visit > NEVER_VISITED, -1).astype(jnp.int32)

    if spec.shortlist:
        z = len(cfg.zoom_levels)
        c = n * z
        acc = jnp.broadcast_to(obs.acc_true, (f, n, z))
        best_cell = jnp.argmax(acc.reshape(f, c), axis=-1) // z
        k = getattr(provider, "shortlist_k", 0)
        if 0 < k < c:
            from repro.fleet.runner import shortlist_windows

            widx = shortlist_windows(cfg, state_pre, provider.nbr8, k)
            kept = widx[:, ::z] // z                    # [F, K/Z] cells
            hit = jnp.any(kept == best_cell[:, None], axis=-1)
        else:
            hit = jnp.ones((f,), bool)
        m["shortlist_hit"] = hit.astype(jnp.float32)

    if spec.rank:
        true_g = gather_at_zoom(obs.acc_true, out.zooms)     # [F, N]
        chosen_val = true_g[arange_f, out.chosen]
        mx = jnp.max(jnp.where(out.explored, true_g, -jnp.inf), -1)
        valid = (out.n_explored >= 2) & (mx > 0)
        rank = 1 + jnp.sum(
            out.explored & (true_g > chosen_val[:, None]), -1)
        m["chosen_rank"] = jnp.where(valid, rank, 0).astype(jnp.int32)
        pred = jnp.where(out.explored, out.pred_acc, 0.0)
        kf = jnp.maximum(out.n_explored, 1).astype(jnp.float32)
        m["score_mean"] = pred.sum(-1) / kf
        m["score_max"] = pred.max(-1)

    return m


# ---------------------------------------------------------------------------
# host-side reductions over the emitted [E, F] metrics
# ---------------------------------------------------------------------------

def median_valid_rank(chosen_rank) -> float:
    """Median of the non-degenerate chosen-rank entries (0 = the step
    was degenerate and is excluded); 0.0 when no step was gradable.
    This is bench_rank_quality's median-rank metric, read directly off
    the emitted FleetMetrics instead of a replay pass."""
    r = np.asarray(chosen_rank).reshape(-1)
    r = r[r > 0]
    return float(np.median(r)) if r.size else 0.0


def summarize_metrics(metrics: dict) -> dict:
    """Reduce stacked [E, F] FleetMetrics to a JSON-native per-camera
    summary dict — what the telemetry event stream and FleetResult
    expose off-device."""
    m = {k: np.asarray(v) for k, v in metrics.items()}
    out: dict = {}
    if "ewma_label_mean" in m:
        out["ewma_label_final"] = m["ewma_label_mean"][-1].tolist()
    if "frames_sent" in m:
        out["frames_sent_total"] = m["frames_sent"].sum(0).tolist()
        out["frames_budget_total"] = m["k_send"].sum(0).tolist()
        out["cells_visited_final"] = m["cells_visited"][-1].tolist()
        out["mean_explored"] = m["n_explored"].mean(0).tolist()
    if "shortlist_hit" in m:
        out["shortlist_hit_rate"] = m["shortlist_hit"].mean(0).tolist()
    if "chosen_rank" in m:
        out["chosen_rank_median"] = [
            median_valid_rank(m["chosen_rank"][:, fi])
            for fi in range(m["chosen_rank"].shape[1])]
        out["score_mean"] = m["score_mean"].mean(0).tolist()
    if "distill_loss" in m:
        # learning runs only (repro.learn): mean loss per camera over
        # the steps it actually updated (-1.0 marks off-cadence/idle)
        loss = m["distill_loss"]
        upd = loss >= 0.0
        n = np.maximum(upd.sum(0), 1)
        out["distill_loss_mean"] = np.where(
            upd.any(0), (loss * upd).sum(0) / n, -1.0).tolist()
        out["distill_update_steps"] = upd.sum(0).tolist()
        out["distill_lr_final"] = m["distill_lr"][-1].tolist()
    return out
