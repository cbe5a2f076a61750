"""Faults planted underneath a run, in what the timed path hands back.

Each takes the last window call's outputs (numpy, [E, F, ...]) and
returns them broken as a faulty program would:

  altered       every step's answer altered where it is produced: the
                chosen orientation moves to the next cell
  half_batch    half of the fleet left out: the second half of the
                cameras repeats the first half's outputs
  wrong_cells   the search explores the wrong cells: every step's
                explored set, path, zooms and scores move one cell on
"""
from __future__ import annotations

import numpy as np


def altered(out: dict) -> dict:
    n = out["explored"].shape[-1]
    return dict(out, chosen=(out["chosen"] + 1) % n)


def half_batch(out: dict) -> dict:
    f = out["chosen"].shape[1]
    src = np.arange(f) % (f // 2)
    return {k: v[:, src] for k, v in out.items()}


def wrong_cells(out: dict) -> dict:
    n = out["explored"].shape[-1]
    moved = {k: np.roll(out[k], 1, axis=-1)
             for k in ("explored", "zooms", "sent", "pred_acc")}
    order = np.where(out["order"] >= 0, (out["order"] + 1) % n, -1)
    return dict(out, **moved, order=order, chosen=(out["chosen"] + 1) % n)


FAULTS = {"altered": altered, "half_batch": half_batch,
          "wrong_cells": wrong_cells}
