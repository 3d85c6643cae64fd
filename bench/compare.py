"""The comparison that decides ``correct``.

A job's answer (seeds, their estimated gains, the score after each round,
the rebuild decisions, the sweeps of the first fixpoint) is held against
the plain reference run on the same graph and sample vector, committing the
answer's own seeds round by round. The numbers compared:

- ``sweeps_diff``: first-fixpoint sweeps, answer against reference (exact);
- ``score_diff``: rounds whose score differs from the reference's (exact);
- ``rebuild_diff``: rounds whose rebuild decision differs (exact);
- ``gain_err``: the widest relative gap between an answered gain and the
  reference's estimate for that seed;
- ``seed_gap``: the widest relative gap by which an answered seed's
  reference estimate lies below the reference's best in its round.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

NAMES = ("sweeps_diff", "score_diff", "rebuild_diff", "gain_err", "seed_gap")


def compare(answer: Dict, ref: Dict, n: int) -> Dict[str, float]:
    seeds = np.asarray(answer["seeds"])
    k = ref["best"].shape[0]
    if seeds.shape != (k,) or np.any((seeds < 0) | (seeds >= n)):
        return {name: float("inf") for name in NAMES}
    est = ref["est_at_seed"].astype(np.float64)
    best = ref["best"].astype(np.float64)
    gains = np.asarray(answer["gains"], np.float64)
    return {
        "sweeps_diff": float(abs(int(answer["build_sweeps"])
                                 - ref["build_sweeps"])),
        "score_diff": float(np.sum(np.asarray(answer["scores"])
                                   != ref["scores"])),
        "rebuild_diff": float(np.sum(np.asarray(answer["rebuilds"])
                                     != ref["rebuilds"])),
        "gain_err": float(np.max(np.abs(gains - est)
                                 / np.maximum(np.abs(est), 1e-30))),
        "seed_gap": float(np.max((best - est) / np.maximum(best, 1e-30))),
    }


def worst(readings) -> Dict[str, float]:
    """Per name, the largest reading over several checked answers."""
    readings = list(readings)
    return {name: max(r[name] for r in readings) for name in NAMES}


def passed(checks: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(checks[name] <= limits[name] for name in NAMES)
