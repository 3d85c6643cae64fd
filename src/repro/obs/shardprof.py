"""Measured per-shard execution profiles — the planner's reality check.

The partition planner predicts, at plan time, how work will spread over the
``(mu_v, mu_s)`` shard grid (``PlanStats`` in :mod:`repro.partition.cost`).
DiFuseR's multi-GPU scaling claim rests on those predictions being right:
the busiest shard bounds every sweep. This module captures what *actually*
happened — per-shard, per-ring-step wall seconds and bucket bytes during
builds and fixpoints — and folds it into a :class:`MeasuredProfile` that is
directly comparable to the predicted stats, closing the loop the ROADMAP's
kernel-autotuning item rides on (measured profiles are the training data a
block-shape/schedule autotuner consumes).

Two capture modes, matching what each backend can physically measure:

  * **serial ring** (``partition/serial.py``) — executes shard-by-shard on
    the host, so every ``(shard, ring step)`` bucket merge gets its own
    measured wall time (``per_step_timed=True``). This is the ground truth
    for "does the degree planner actually beat block on a skewed graph".
  * **mesh** (``core/distributed.py``) — SPMD shards run in lockstep inside
    one XLA program, so per-shard time is not separable host-side; the
    profile carries exact per-(shard, step) *bytes* (off the built
    partition's bucket counts) plus the fixpoint wall time
    (``per_step_timed=False``).

Publication: :func:`publish` registers the profile in a bounded process
ring (:func:`profiles` — the HTML perf report reads it) and, when the
partition carries a plan with predicted stats, emits the
``partition.predicted_vs_measured_edge_imb`` / ``_bucket_imb`` gauges —
measured / predicted imbalance ratios, tagged by strategy and backend. A
ratio well above 1.0 is a misprediction visible the moment the plan runs.

Dependency: numpy only (imported lazily by callers that already hold it);
no jax at module load, same contract as the rest of ``repro.obs``.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from time import perf_counter
from typing import Optional

import numpy as np

from repro.obs import metrics

#: Approximate bytes a bucket edge costs per sweep: 20 B of operand reads
#: (h, w, r, t, l — uint32/int32 each) plus one int8 register-row read and
#: one int8 max-merge write per register lane.
_EDGE_OPERAND_BYTES = 20


def bucket_bytes(edge_count: int, j_loc: int) -> int:
    """Bytes one bucket of ``edge_count`` real edges moves in one sweep."""
    return int(edge_count) * (_EDGE_OPERAND_BYTES + 2 * int(j_loc))


def _imbalance(loads: np.ndarray) -> float:
    loads = np.asarray(loads, dtype=np.float64).reshape(-1)
    mean = loads.mean() if loads.size else 0.0
    return float(loads.max(initial=0.0) / mean) if mean > 0 else 1.0


@dataclasses.dataclass
class MeasuredProfile:
    """What one build/fixpoint actually cost, per shard and per ring step.

    ``step_seconds[v, k]`` / ``step_bytes[v, k]`` aggregate vertex-shard
    ``v``'s ring-step-``k`` bucket merges over all sim shards and all
    sweeps. ``per_step_timed`` is False when the backend cannot separate
    per-shard time (mesh SPMD) — bytes are still exact there.
    """

    backend: str                   # "serial" | "mesh" | ...
    phase: str                     # "build" | "fixpoint" | "select" ...
    strategy: str
    mu_v: int
    mu_s: int
    sweeps: int
    step_seconds: np.ndarray       # float64[mu_v, mu_v]
    step_bytes: np.ndarray         # int64[mu_v, mu_v]
    wall_s: float
    per_step_timed: bool

    # -- reductions --------------------------------------------------------

    def shard_seconds(self) -> np.ndarray:
        return self.step_seconds.sum(axis=1)

    def shard_bytes(self) -> np.ndarray:
        return self.step_bytes.sum(axis=1)

    def time_imbalance(self) -> float:
        """max/mean of per-shard measured seconds (1.0 = perfectly even).
        Falls back to the bytes imbalance when time is not separable."""
        if not self.per_step_timed:
            return self.bytes_imbalance()
        return _imbalance(self.shard_seconds())

    def bytes_imbalance(self) -> float:
        """max/mean of per-shard measured bucket bytes — the measured twin
        of the planner's predicted edge imbalance."""
        return _imbalance(self.shard_bytes())

    def step_imbalance(self) -> float:
        """max/mean over the full (shard, ring step) grid — the measured
        twin of the predicted bucket imbalance (per-step padding means the
        widest bucket of a step stalls every shard at that step)."""
        grid = self.step_seconds if self.per_step_timed else self.step_bytes
        return _imbalance(grid)

    def achieved_gbps(self) -> float:
        """Aggregate bucket bytes / wall — the bandwidth this build actually
        sustained (compare against the chip's ``utils.roofline.PEAKS`` entry)."""
        total = float(self.step_bytes.sum())
        return total / self.wall_s / 1e9 if self.wall_s > 0 else 0.0

    # -- presentation ------------------------------------------------------

    def skew_table(self) -> str:
        """Human-readable per-shard table: seconds, bytes, and each shard's
        load relative to the mean (the straggler column)."""
        secs, byts = self.shard_seconds(), self.shard_bytes()
        mean_b = byts.mean() if byts.size else 0.0
        lines = [f"[{self.backend}:{self.strategy}] {self.phase} "
                 f"mu_v={self.mu_v} mu_s={self.mu_s} sweeps={self.sweeps} "
                 f"wall={self.wall_s:.3f}s "
                 f"time_imb={self.time_imbalance():.2f} "
                 f"bytes_imb={self.bytes_imbalance():.2f}",
                 "shard      seconds         bytes   rel_load"]
        for v in range(self.mu_v):
            rel = byts[v] / mean_b if mean_b > 0 else 1.0
            sec = f"{secs[v]:.4f}" if self.per_step_timed else "   n/a"
            lines.append(f"{v:5d}  {sec:>10s}  {int(byts[v]):12d}   "
                         f"{rel:7.2f}x")
        return "\n".join(lines)

    def summary(self) -> dict:
        """JSON-ready summary (the perf report's row format)."""
        return {
            "backend": self.backend, "phase": self.phase,
            "strategy": self.strategy, "mu_v": self.mu_v, "mu_s": self.mu_s,
            "sweeps": self.sweeps, "wall_s": self.wall_s,
            "per_step_timed": self.per_step_timed,
            "time_imbalance": self.time_imbalance(),
            "bytes_imbalance": self.bytes_imbalance(),
            "step_imbalance": self.step_imbalance(),
            "achieved_gbps": self.achieved_gbps(),
            "shard_seconds": [float(s) for s in self.shard_seconds()],
            "shard_bytes": [int(b) for b in self.shard_bytes()],
        }


class ShardProfiler:
    """Accumulates per-(shard, ring step) measurements during one
    build/fixpoint. The serial ring calls :meth:`record` around every bucket
    merge; the mesh path calls :meth:`add_partition_bytes` once (counts are
    known host-side) and leaves time unseparated."""

    def __init__(self, mu_v: int, mu_s: int, *, backend: str, phase: str,
                 strategy: str = "block"):
        self.mu_v, self.mu_s = mu_v, mu_s
        self.backend, self.phase, self.strategy = backend, phase, strategy
        self.step_seconds = np.zeros((mu_v, mu_v), dtype=np.float64)
        self.step_bytes = np.zeros((mu_v, mu_v), dtype=np.int64)
        self.sweeps = 0
        self.per_step_timed = False
        self._t0 = perf_counter()

    def record(self, v: int, kk: int, seconds: float, nbytes: int) -> None:
        """One measured bucket merge of shard ``v`` at ring step ``kk``."""
        self.step_seconds[v, kk] += seconds
        self.step_bytes[v, kk] += nbytes
        self.per_step_timed = True

    def count_sweep(self) -> None:
        self.sweeps += 1

    def add_partition_bytes(self, counts: np.ndarray, j_loc: int,
                            sweeps: int) -> None:
        """Fold per-bucket real-edge ``counts`` (``int64[mu_v, mu_s, mu_v]``
        — the builder's ``p_counts``) in as bytes, scaled by the sweep count
        the fixpoint actually ran."""
        per_edge = _EDGE_OPERAND_BYTES + 2 * int(j_loc)
        self.step_bytes += counts.sum(axis=1).astype(np.int64) * per_edge * max(sweeps, 1)
        self.sweeps += sweeps

    def finish(self, wall_s: Optional[float] = None) -> MeasuredProfile:
        return MeasuredProfile(
            backend=self.backend, phase=self.phase, strategy=self.strategy,
            mu_v=self.mu_v, mu_s=self.mu_s, sweeps=self.sweeps,
            step_seconds=self.step_seconds, step_bytes=self.step_bytes,
            wall_s=wall_s if wall_s is not None else perf_counter() - self._t0,
            per_step_timed=self.per_step_timed)


# ---------------------------------------------------------------------------
# process-level publication (bounded ring + predicted-vs-measured gauges)
# ---------------------------------------------------------------------------

_PROFILES: deque = deque(maxlen=64)
_LOCK = threading.Lock()
_ENABLED = True


def set_enabled(flag: bool) -> None:
    """Master switch for profile capture (on by default — the numpy-side
    bookkeeping is negligible next to the sweeps it measures)."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def profiles() -> list:
    """Recent :class:`MeasuredProfile`\\ s, oldest first (bounded ring)."""
    with _LOCK:
        return list(_PROFILES)


def last_profile() -> Optional[MeasuredProfile]:
    with _LOCK:
        return _PROFILES[-1] if _PROFILES else None


def clear() -> None:
    with _LOCK:
        _PROFILES.clear()


def publish(profile: MeasuredProfile, predicted=None) -> MeasuredProfile:
    """Register a finished profile and, when the plan's predicted
    ``PlanStats`` is available, emit the closed-loop gauges:

      * ``partition.measured_edge_imb`` / ``partition.measured_time_imb`` —
        the profile's own imbalances;
      * ``partition.predicted_vs_measured_edge_imb`` — measured bytes
        imbalance / predicted edge imbalance (1.0 = the planner's cost
        model was right about shard skew);
      * ``partition.predicted_vs_measured_bucket_imb`` — measured
        (shard, step) imbalance / predicted bucket imbalance.

    All gauges are tagged ``strategy=<plan strategy> backend=<backend>`` so
    planners stay comparable side by side in one snapshot."""
    if not _ENABLED:
        return profile
    with _LOCK:
        _PROFILES.append(profile)
    tags = {"strategy": profile.strategy, "backend": profile.backend}
    metrics.gauge("partition.measured_edge_imb",
                  **tags).set(profile.bytes_imbalance())
    metrics.gauge("partition.measured_time_imb",
                  **tags).set(profile.time_imbalance())
    metrics.gauge("partition.achieved_gbps", **tags).set(profile.achieved_gbps())
    if predicted is not None:
        if predicted.edge_imbalance > 0:
            metrics.gauge("partition.predicted_vs_measured_edge_imb", **tags).set(
                profile.bytes_imbalance() / predicted.edge_imbalance)
        if predicted.bucket_imbalance > 0:
            metrics.gauge("partition.predicted_vs_measured_bucket_imb", **tags).set(
                profile.step_imbalance() / predicted.bucket_imbalance)
    return profile


def profile_for_partition(part, *, backend: str, phase: str) -> ShardProfiler:
    """A profiler pre-shaped for a built ``Partition2D`` (strategy read off
    its plan; ``block`` when the partition was built planless)."""
    strategy = part.plan.strategy if part.plan is not None else "block"
    return ShardProfiler(part.mu_v, part.mu_s, backend=backend, phase=phase,
                         strategy=strategy)
