"""Load-balanced partition planning — vertex-assignment strategies for the
2-D distributed runtime.

The paper's multi-GPU speedup rests on its "smart load-balancing mechanism":
on power-law graphs a contiguous block split (``own = v // n_loc``) hands one
shard the hubs, and every ring sweep then waits on that shard. A
``PartitionPlan`` fixes this *entirely on host*: it is a relabeling
permutation of the vertex ids such that the runtime's unchanged contiguous
split over the *relabeled* ids balances the per-shard edge work. Device
kernels never see the strategy — they consume the same bucketed arrays plus
one extra ``owned_ids`` vector (relabeled row -> original vertex id) that
keeps register hashes, validity masks, and reported seeds in original-id
space, so results are bit-independent of the plan (see
``repro.partition.serial`` tests).

Strategies (registry, pluggable like the diffusion model zoo):

  * ``block``  — today's scheme: identity permutation, bit-compatible with
                 the pre-planner partition (the default-off baseline).
  * ``degree`` — greedy weighted bin-packing (LPT with per-bin capacity
                 ``n_loc``) on the sampled out+in degree of each vertex —
                 the paper's balancing analogue; cf. the kernel-balancing of
                 Göktürk & Kaya (arXiv:2008.03095).
  * ``edge``   — balance the per-(write-shard, ring-step) bucket loads
                 directly: greedy placement minimizing the peak bucket a
                 vertex's already-placed neighborhood would create.
  * ``random`` — seeded balanced random assignment (test/baseline aid:
                 results must be invariant under any relabeling).

Vertex weights honor the sample space: each edge counts once per sim shard
whose edge set holds it (exactly the multiplicity the bucket arrays will
carry): the sets of ``sampled`` where given (the mesh path passes the sets
of its fixed value ranges, :func:`sample_edge_sets_in_ranges`, so the plan
does not depend on X), else those of ``x``'s FASST chunks; without either
every real edge counts once.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.graphs.structs import Graph
from repro.obs import metrics, trace
from repro.partition.cost import PlanStats, predicted_stats


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """A vertex relabeling that the 2-D partition builder keys on.

    ``perm`` maps original ids to relabeled ids over the padded id space
    ``[0, n_pad)`` (``n_pad`` is rounded so ``mu_v | n_pad``); shard
    ``v`` owns relabeled rows ``[v * n_loc, (v+1) * n_loc)``. ``inv_perm``
    is the inverse (relabeled -> original); padding ids (>= n) fill the
    leftover slots so every shard owns exactly ``n_loc`` rows.
    """

    strategy: str
    n: int
    n_pad: int
    n_loc: int
    mu_v: int
    mu_s: int
    perm: np.ndarray       # int32[n_pad] original id -> relabeled id
    inv_perm: np.ndarray   # int32[n_pad] relabeled id -> original id
    predicted: Optional[PlanStats] = None

    def owner_of(self, ids: np.ndarray) -> np.ndarray:
        """Owning vertex-shard of each original vertex id."""
        return (self.perm[np.asarray(ids, dtype=np.int64)] // self.n_loc).astype(np.int32)

    def local_row_of(self, ids: np.ndarray) -> np.ndarray:
        """Row within the owning shard's register block."""
        return (self.perm[np.asarray(ids, dtype=np.int64)] % self.n_loc).astype(np.int32)

    def owned_ids(self) -> np.ndarray:
        """int32[mu_v, n_loc] original vertex id per (shard, local row)."""
        return self.inv_perm.reshape(self.mu_v, self.n_loc)

    def validate(self, g: Graph) -> None:
        if g.n != self.n:
            raise ValueError(f"plan built for n={self.n}, graph has n={g.n}")

    @staticmethod
    def from_permutation(n: int, mu_v: int, mu_s: int, perm: np.ndarray,
                         *, strategy: str = "custom") -> "PartitionPlan":
        """Rebuild a plan from a persisted/explicit permutation (the store
        snapshot path). ``perm`` must be a permutation of [0, len(perm))
        with mu_v | len(perm)."""
        perm = np.asarray(perm, dtype=np.int32)
        n_pad = perm.shape[0]
        if n_pad % mu_v != 0:
            raise ValueError(f"len(perm)={n_pad} not divisible by mu_v={mu_v}")
        inv = np.empty_like(perm)
        inv[perm] = np.arange(n_pad, dtype=np.int32)
        return PartitionPlan(strategy=strategy, n=n, n_pad=n_pad,
                             n_loc=n_pad // mu_v, mu_v=mu_v, mu_s=mu_s,
                             perm=perm, inv_perm=inv)


# ---------------------------------------------------------------------------
# Shared plan/build preprocessing + vertex weights (sampled out+in degree)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SampledEdges:
    """The O(m * mu_s) host preprocessing both the planner and the bucket
    builder need: model edge operands, FASST sample chunks, and each sim
    shard's sampled edge set. Compute once (``sample_edge_sets``) and pass
    to both ``plan_partition`` and ``build_partition_2d``."""

    ep: object             # diffusion EdgeParams (h, lo, thr)
    x_shards: np.ndarray   # uint32[mu_s, j_loc]
    masks: tuple           # per sim shard: int64 indices of its sampled edges
    # uint32[mu_s, 2] value ranges the masks cover (sample_edge_sets_in_ranges);
    # None where the masks are those of x_shards alone
    ranges: Optional[np.ndarray] = None


def sample_edge_sets(g: Graph, x: np.ndarray, mu_s: int, *, seed: int = 0,
                     model: str = "wc", method: str = "fasst") -> SampledEdges:
    from repro.core.fasst import _sampled_by_any, partition_samples
    from repro.diffusion import resolve as resolve_model

    mdl = resolve_model(model)
    ep = mdl.edge_params(g, seed=seed)
    x_shards, _ = partition_samples(np.asarray(x, dtype=np.uint32), mu_s,
                                    method=method)
    masks = tuple(
        np.nonzero(_sampled_by_any(ep.h, ep.thr, x_shards[s], lo=ep.lo,
                                   predicate=mdl.predicate))[0]
        for s in range(mu_s))
    return SampledEdges(ep=ep, x_shards=x_shards, masks=masks)


def sample_edge_sets_in_ranges(g: Graph, x: np.ndarray, mu_s: int, *,
                               seed: int = 0, model: str = "wc",
                               method: str = "fasst"
                               ) -> Optional[SampledEdges]:
    """Each sim shard's edge set fixed by the graph: every edge live for
    *some* value of the shard's :func:`~repro.core.fasst.shard_value_ranges`
    range, a superset of what any X inside the ranges samples. ``x`` only
    supplies the ``x_shards`` of the returned sets. None for a model whose
    predicate has no range test (anything but the plain threshold compare
    with ``lo = 0``: ``lt``'s remixed interval)."""
    from repro.core.fasst import (live_in_range, partition_samples,
                                  shard_value_ranges)
    from repro.core.sampling import fused_predicate
    from repro.diffusion import resolve as resolve_model

    mdl = resolve_model(model)
    if mdl.predicate is not fused_predicate:
        return None
    ep = mdl.edge_params(g, seed=seed)
    if np.any(ep.lo):
        return None
    x = np.asarray(x, dtype=np.uint32)
    x_shards, _ = partition_samples(x, mu_s, method=method)
    ranges = shard_value_ranges(x.shape[0], mu_s, method=method)
    masks = tuple(np.nonzero(live_in_range(ep.h, ep.thr, ranges[s]))[0]
                  for s in range(mu_s))
    return SampledEdges(ep=ep, x_shards=x_shards, masks=masks, ranges=ranges)


def _edge_multiplicity(g: Graph, x: Optional[np.ndarray], mu_s: int, *,
                       seed: int, model: str, method: str,
                       sampled: Optional[SampledEdges]) -> np.ndarray:
    """int64[m_real] per-edge weight: how many sim shards sample the edge
    (the multiplicity the bucket arrays will carry), or 1 per real edge when
    no sample vector is given."""
    if sampled is None:
        if x is None:
            return np.ones(g.m_real, dtype=np.int64)
        sampled = sample_edge_sets(g, x, mu_s, seed=seed, model=model,
                                   method=method)
    c = np.bincount(np.concatenate(sampled.masks), minlength=g.m)
    return c[: g.m_real].astype(np.int64)


def _vertex_weights(g: Graph, c_e: np.ndarray) -> np.ndarray:
    """int64[n] sampled out+in degree (the per-vertex write work a shard
    inherits by owning the vertex: propagate writes by src, cascade by dst)."""
    src = g.src[: g.m_real].astype(np.int64)
    dst = g.dst[: g.m_real].astype(np.int64)
    w = np.bincount(src, weights=c_e, minlength=g.n)
    w += np.bincount(dst, weights=c_e, minlength=g.n)
    return w.astype(np.int64)


# ---------------------------------------------------------------------------
# Assignment strategies: each returns int32[n] owner per real vertex
# ---------------------------------------------------------------------------


def _assign_block(g: Graph, c_e, w_v, mu_v: int, n_loc: int, seed: int) -> np.ndarray:
    return (np.arange(g.n, dtype=np.int64) // n_loc).astype(np.int32)


def _assign_random(g: Graph, c_e, w_v, mu_v: int, n_loc: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    shuffled = rng.permutation(g.n)
    owner = np.empty(g.n, dtype=np.int32)
    owner[shuffled] = (np.arange(g.n, dtype=np.int64) // n_loc).astype(np.int32)
    return owner


def _assign_degree(g: Graph, c_e, w_v, mu_v: int, n_loc: int, seed: int) -> np.ndarray:
    """LPT bin-packing with per-bin capacity: heaviest vertex first into the
    lightest non-full bin. Deterministic (ties break by bin index)."""
    owner = np.empty(g.n, dtype=np.int32)
    counts = np.zeros(mu_v, dtype=np.int64)
    heap = [(0, b) for b in range(mu_v)]  # (load, bin)
    heapq.heapify(heap)
    order = np.argsort(-w_v, kind="stable")
    for v in order:
        while True:
            load, b = heapq.heappop(heap)
            if counts[b] < n_loc:
                break  # a full bin stays full — drop its entry for good
        owner[v] = b
        counts[b] += 1
        heapq.heappush(heap, (load + int(w_v[v]), b))
    return owner


def _assign_edge(g: Graph, c_e, w_v, mu_v: int, n_loc: int, seed: int) -> np.ndarray:
    """Balance the per-(write-shard, ring-step) bucket loads directly.

    Greedy over vertices in descending weight: place each vertex in the
    non-full bin that minimizes the peak load across every bucket the
    vertex's already-placed neighborhood touches — its own write buckets
    (propagate by out-edges, cascade by in-edges) AND the neighbors' write
    buckets its placement lands in. O(n * mu_v^2 + m)."""
    n = g.n
    src = g.src[: g.m_real].astype(np.int64)
    dst = g.dst[: g.m_real].astype(np.int64)
    out_order = np.argsort(src, kind="stable")
    out_ptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))]).astype(np.int64)
    out_nbr = dst[out_order]
    out_w = c_e[out_order].astype(np.float64)
    in_order = np.argsort(dst, kind="stable")
    in_ptr = np.concatenate([[0], np.cumsum(np.bincount(dst, minlength=n))]).astype(np.int64)
    in_nbr = src[in_order]
    in_w = c_e[in_order].astype(np.float64)

    owner = np.full(n, -1, dtype=np.int32)
    counts = np.zeros(mu_v, dtype=np.int64)
    prop = np.zeros((mu_v, mu_v), dtype=np.float64)  # [write shard, ring step]
    casc = np.zeros((mu_v, mu_v), dtype=np.float64)
    steps = np.arange(mu_v)
    # owner o sits at ring step (o - b) % mu_v of bin b's sweep; precompute
    # both index tables once
    own_at_step = (steps[:, None] + steps[None, :]) % mu_v   # [b, k] -> o
    step_of_bin = (steps[None, :] - steps[:, None]) % mu_v   # [o, b] -> k

    for v in np.argsort(-w_v, kind="stable"):
        oo = owner[out_nbr[out_ptr[v]: out_ptr[v + 1]]]
        ow = out_w[out_ptr[v]: out_ptr[v + 1]]
        sel = oo >= 0
        out_by = np.bincount(oo[sel], weights=ow[sel], minlength=mu_v)
        io = owner[in_nbr[in_ptr[v]: in_ptr[v + 1]]]
        iw = in_w[in_ptr[v]: in_ptr[v + 1]]
        sel = io >= 0
        in_by = np.bincount(io[sel], weights=iw[sel], minlength=mu_v)

        # own write rows if v lands in bin b: bucket (b, k) gains the edges
        # to/from neighbors owned by (b + k) % mu_v
        peak_own = np.maximum(prop + out_by[own_at_step],
                              casc + in_by[own_at_step]).max(axis=1)
        # neighbors' write rows: owner o's bucket at step (b - o) % mu_v
        # gains in_by[o] (propagate, u->v writes at owner[u]) resp. out_by[o]
        peak_other = np.maximum(prop[steps[:, None], step_of_bin] + in_by[:, None],
                                casc[steps[:, None], step_of_bin] + out_by[:, None]).max(axis=0)
        peak = np.maximum(peak_own, peak_other)
        tie = prop.sum(axis=1) + casc.sum(axis=1)  # prefer the lighter bin
        peak[counts >= n_loc] = np.inf
        b = int(np.lexsort((steps, tie, peak))[0])

        owner[v] = b
        counts[b] += 1
        prop[b] += out_by[own_at_step[b]]
        casc[b] += in_by[own_at_step[b]]
        np.add.at(prop, (steps, step_of_bin[:, b]), in_by)
        np.add.at(casc, (steps, step_of_bin[:, b]), out_by)
    return owner


_STRATEGIES: Dict[str, Callable] = {}


def register_strategy(name: str, fn: Callable) -> None:
    """Register a vertex-assignment strategy: ``fn(g, c_e, w_v, mu_v, n_loc,
    seed) -> int32[n] owner per real vertex`` (< n_loc vertices per owner)."""
    if name in _STRATEGIES:
        raise ValueError(f"partition strategy {name!r} already registered")
    _STRATEGIES[name] = fn


def available_strategies() -> Tuple[str, ...]:
    return tuple(_STRATEGIES)


register_strategy("block", _assign_block)
register_strategy("degree", _assign_degree)
register_strategy("edge", _assign_edge)
register_strategy("random", _assign_random)


# ---------------------------------------------------------------------------
# Planner entry point
# ---------------------------------------------------------------------------


def plan_partition(g: Graph, mu_v: int, *, mu_s: int = 1,
                   strategy: str = "block", x: Optional[np.ndarray] = None,
                   seed: int = 0, model: str = "wc", method: str = "fasst",
                   sampled: Optional[SampledEdges] = None) -> PartitionPlan:
    """Build a :class:`PartitionPlan` for a ``(mu_v, mu_s)`` device grid.

    ``x`` (the sample vector) sharpens the vertex weights to the edges the
    sim shards actually sample; without it plain degrees are used.
    ``sampled`` passes the :func:`sample_edge_sets` preprocessing in when
    the caller also builds the partition (it is the dominant host cost —
    don't pay it twice). The returned plan carries ``predicted`` cost-model
    stats (edge/bucket imbalance and ring bytes) so callers can compare
    strategies before paying for the full bucket build.
    """
    fn = _STRATEGIES.get(strategy)
    if fn is None:
        raise KeyError(f"unknown partition strategy {strategy!r}; "
                       f"registered: {sorted(_STRATEGIES)}")
    with trace.span("partition.plan", phase="plan", strategy=strategy,
                    mu_v=mu_v, mu_s=mu_s, n=g.n):
        n_pad = g.n_pad + ((-g.n_pad) % mu_v)
        n_loc = n_pad // mu_v
        c_e = _edge_multiplicity(g, x, mu_s, seed=seed, model=model,
                                 method=method, sampled=sampled)
        w_v = _vertex_weights(g, c_e)
        owner = np.asarray(fn(g, c_e, w_v, mu_v, n_loc, seed), dtype=np.int64)
        if owner.shape[0] != g.n:
            raise ValueError(f"strategy {strategy!r} assigned {owner.shape[0]} "
                             f"vertices, expected {g.n}")
        counts = np.bincount(owner, minlength=mu_v)
        if counts.max(initial=0) > n_loc:
            raise ValueError(f"strategy {strategy!r} overfilled a shard: "
                             f"{counts.tolist()} vs capacity {n_loc}")
        # padding ids fill the leftover slots, ascending id into ascending shard
        free = n_loc - counts
        pad_owner = np.repeat(np.arange(mu_v, dtype=np.int64), free)
        owner_all = np.concatenate([owner, pad_owner])
        # stable sort groups ids by owner, keeping ascending original id within
        # each shard — block's identity assignment relabels to the identity
        inv_perm = np.argsort(owner_all, kind="stable").astype(np.int32)
        perm = np.empty_like(inv_perm)
        perm[inv_perm] = np.arange(n_pad, dtype=np.int32)

        if sampled is not None:
            j_loc = int(sampled.x_shards.shape[1])
        else:
            j_loc = (np.asarray(x).shape[0] // mu_s) if x is not None else 0
        stats = predicted_stats(g, strategy, perm, c_e, mu_v, mu_s, n_loc, j_loc)
    metrics.gauge("partition.ring_bytes_per_sweep",
                  strategy=strategy).set(stats.ring_bytes_per_sweep)
    metrics.gauge("partition.edge_imbalance",
                  strategy=strategy).set(stats.edge_imbalance)
    metrics.gauge("partition.bucket_imbalance",
                  strategy=strategy).set(stats.bucket_imbalance)
    return PartitionPlan(strategy=strategy, n=g.n, n_pad=n_pad, n_loc=n_loc,
                         mu_v=mu_v, mu_s=mu_s, perm=perm, inv_perm=inv_perm,
                         predicted=stats)
