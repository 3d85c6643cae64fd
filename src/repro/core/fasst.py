"""FASST — fusing-aware sample-space tasking (paper §4.1).

Because samples are decided by ``(X_r XOR h_e) < thr_e``, permuting the
entries of X changes nothing statistically (each X_r still induces the same
sampled graph) but changes *which* samples land next to each other. FASST
sorts X so that:

  1. consecutive register lanes make correlated sampling decisions for the
     same edge -> higher SIMD/VPU lane fill (paper Table 6),
  2. each device's contiguous chunk of sorted X samples a *small* edge
     subset -> device-local graphs shrink and overlap less (Tables 5/7),
     which is simultaneously the load-balancing / straggler-mitigation
     mechanism (max shard size == straggler bound).

All of this runs once during setup — on the host in numpy, except the
(edges x samples) liveness test, which runs on the default device; the
device code only ever sees the resulting per-shard X slices and padded
edge lists.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampling import edge_hash, weight_to_threshold
from repro.graphs.structs import Graph, pad_to_multiple


@dataclasses.dataclass(frozen=True)
class SamplePartition:
    """Sample-space partition across ``mu`` shards.

    x_shards:    uint32[mu, J_loc]  per-shard X slices (sorted within shard).
    perm:        int32[R]           original-sim id for each (shard, slot).
    edge_index:  int32[mu, E_max]   per-shard device-local edge ids into the
                                    global padded edge arrays (padded with -1
                                    -> replaced by a sentinel edge id).
    edge_counts: int64[mu]          real edge count per shard (pre-padding).
    method:      "fasst" | "naive".
    """

    x_shards: np.ndarray
    perm: np.ndarray
    edge_index: np.ndarray
    edge_counts: np.ndarray
    method: str

    @property
    def mu(self) -> int:
        return self.x_shards.shape[0]

    @property
    def regs_per_shard(self) -> int:
        return self.x_shards.shape[1]


def partition_samples(x: np.ndarray, mu: int, *, method: str = "fasst") -> tuple[np.ndarray, np.ndarray]:
    """Split R samples into mu equal shards.

    fasst: sort X, contiguous chunks of the sorted vector per shard.
    naive: original order, strided chunks (the paper's baseline).
    Returns (x_shards[mu, J_loc], perm[R]) with perm[shard*J_loc + slot] =
    original simulation id.
    """
    r = x.shape[0]
    assert r % mu == 0, (r, mu)
    if method == "fasst":
        perm = np.argsort(x, kind="stable").astype(np.int32)
    elif method == "naive":
        perm = np.arange(r, dtype=np.int32)
    else:
        raise ValueError(method)
    x_shards = x[perm].reshape(mu, r // mu)
    return x_shards, perm


#: Standard deviations by which a sample shard's value range reaches past each
#: of its quantile boundaries (:func:`shard_value_ranges`). A boundary order
#: statistic of J uniform draws lies outside 5 sd about 3e-7 of the time, so
#: the program's own draws leave the ranges far less than once in 10^5 jobs.
RANGE_SIGMAS = 5.0


def shard_value_ranges(registers: int, mu: int, *,
                       method: str = "fasst") -> np.ndarray:
    """uint32[mu, 2]: the inclusive value range ``[lo, hi]`` that holds
    sample shard t's X values for all but a vanishing share of uniform X.

    Under FASST shard t holds the sorted X's t-th of ``mu`` chunks, so its
    values lie near the quantile range ``[t/mu, (t+1)/mu)`` of [0, 2^32);
    each inner boundary is widened by ``RANGE_SIGMAS`` standard deviations
    of its order statistic, ``2^32 sqrt(q (1 - q) / registers)``. The
    ranges depend only on (registers, mu), never on X, so the edge sets
    they select are fixed by the graph. ``naive`` shards stride the
    unsorted X: each spans all of [0, 2^32).
    """
    top = float(2**32 - 1)
    if method == "naive":
        return np.tile(np.array([0, top], np.uint32), (mu, 1))
    q = np.arange(mu + 1, dtype=np.float64) / mu
    delta = RANGE_SIGMAS * 2.0**32 * np.sqrt(q * (1.0 - q) / registers)
    edge = q * 2.0**32
    lo = np.clip(np.floor(edge[:-1] - delta[:-1]), 0.0, top)
    hi = np.clip(np.ceil(edge[1:] + delta[1:]), 0.0, top)
    return np.stack([lo, hi], axis=1).astype(np.uint32)


def in_ranges(x_shards: np.ndarray, ranges: np.ndarray) -> bool:
    """Whether every shard's X values lie inside its value range."""
    return bool(np.all(x_shards.min(axis=1) >= ranges[:, 0])
                and np.all(x_shards.max(axis=1) <= ranges[:, 1]))


@jax.jit
def min_xor_in_range(h, lo, hi):
    """uint32: ``min(v ^ h for v in [lo, hi])`` per element of ``h``.

    Bit-greedy from the top bit: give v the bit of h (an XOR bit of 0)
    wherever some v of the range still has it, else the other bit. A 0 bit
    outweighs every lower bit, and a prefix still inside the range always
    completes to a member of it, so the greedy choice is the least."""
    h = h.astype(jnp.uint32)
    lo, hi = jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32)
    v = jnp.zeros_like(h)
    tight_lo = jnp.ones(h.shape, bool)
    tight_hi = jnp.ones(h.shape, bool)
    for i in range(31, -1, -1):
        s = jnp.uint32(i)
        hb, lb, ub = (h >> s) & 1, (lo >> s) & 1, (hi >> s) & 1
        fits = (~tight_lo | (hb >= lb)) & (~tight_hi | (hb <= ub))
        bit = jnp.where(fits, hb, 1 - hb)
        v = v | (bit << s)
        tight_lo = tight_lo & (bit == lb)
        tight_hi = tight_hi & (bit == ub)
    return v ^ h


def live_in_range(edge_h: np.ndarray, thr: np.ndarray,
                  value_range: np.ndarray) -> np.ndarray:
    """bool[m]: edge live under some X value in the inclusive range, for the
    threshold predicate ``(X ^ h) < thr``: exactly the edges any X of the
    range can sample."""
    least = min_xor_in_range(jnp.asarray(edge_h, jnp.uint32),
                             np.uint32(value_range[0]),
                             np.uint32(value_range[1]))
    return np.asarray(least) < np.asarray(thr, np.uint32)


def _sampled_by_any(edge_h: np.ndarray, thr: np.ndarray, x_chunk: np.ndarray,
                    chunk_edges: int = 1 << 16, *, lo: np.ndarray | None = None,
                    predicate=None) -> np.ndarray:
    """bool[m]: edge live under at least one X value in x_chunk.

    ``lo``/``predicate`` are the diffusion-model hook (repro.diffusion);
    omitted, the legacy threshold compare is used."""
    from repro.core.sampling import fused_predicate

    if predicate is None:
        predicate = fused_predicate
    if lo is None:
        lo = np.zeros_like(thr, dtype=np.uint32)
    m = edge_h.shape[0]
    chunk_edges = max(1, min(chunk_edges, m))
    pad = (-m) % chunk_edges   # thr = 0 filler is never live
    ops = [np.pad(np.asarray(a, np.uint32), (0, pad)).reshape(-1, chunk_edges)
           for a in (edge_h, lo, thr)]
    live = _live_any(*ops, jnp.asarray(x_chunk, jnp.uint32),
                     predicate=predicate)
    return np.asarray(live).reshape(-1)[:m]


@partial(jax.jit, static_argnames=("predicate",))
def _live_any(h, lo, thr, x, *, predicate):
    """(chunks, chunk) edge operands -> bool (chunks, chunk): live under any
    of ``x`` — one (chunk x len(x)) predicate tile at a time, on device."""
    def chunk(ops):
        hh, ll, tt = ops
        return predicate(hh[:, None], ll[:, None], tt[:, None],
                         x[None, :]).any(axis=1)

    return jax.lax.map(chunk, (h, lo, thr))


def build_partition(g: Graph, x: np.ndarray, mu: int, *, method: str = "fasst",
                    seed: int = 0, edge_block: int = 256,
                    model: str = "wc") -> SamplePartition:
    """Build per-shard device-local edge lists (paper §4, lines 1-3 of setup).

    Shards get exactly the edges at least one of their samples uses; the
    lists are padded to a common length (multiple of ``edge_block``) with a
    sentinel edge id pointing at the inert padding edge, so shard_map sees
    equal shapes. The common length *is* the paper's Table-7 metric.
    ``model`` selects the diffusion model whose fused predicate decides
    membership (default ``wc`` — the legacy threshold compare).
    """
    from repro.diffusion import resolve as _resolve_model

    x_shards, perm = partition_samples(x, mu, method=method)
    mdl = _resolve_model(model)
    ep = mdl.edge_params(g, seed=seed)
    eh, lo, thr = ep.h, ep.lo, ep.thr
    # the last padded edge is inert (thr == 0): use it as the pad target
    sentinel_edge = g.m - 1
    assert thr[sentinel_edge] == 0, "graph must carry at least one padding edge"

    masks = [_sampled_by_any(eh, thr, x_shards[t], lo=lo, predicate=mdl.predicate)
             for t in range(mu)]
    counts = np.array([int(msk.sum()) for msk in masks], dtype=np.int64)
    e_max = int(counts.max()) if counts.size else 0
    e_max = max(e_max, 1)
    e_max += (-e_max) % edge_block
    edge_index = np.full((mu, e_max), sentinel_edge, dtype=np.int32)
    for t, msk in enumerate(masks):
        ids = np.nonzero(msk)[0].astype(np.int32)
        edge_index[t, : ids.shape[0]] = ids
    return SamplePartition(x_shards=x_shards, perm=perm, edge_index=edge_index,
                           edge_counts=counts, method=method)


# ---------------------------------------------------------------------------
# Metrics (paper Tables 5, 6, 7)
# ---------------------------------------------------------------------------

def duplication_histogram(g: Graph, part: SamplePartition, *, seed: int = 0) -> np.ndarray:
    """Table 5: fraction of edges appearing in exactly k device-local graphs,
    k = 0..mu (real edges only)."""
    mu = part.mu
    appear = np.zeros(g.m, dtype=np.int32)
    eh = edge_hash(g.src, g.dst, seed=seed)
    thr = weight_to_threshold(g.weight)
    for t in range(mu):
        appear += _sampled_by_any(eh, thr, part.x_shards[t]).astype(np.int32)
    appear = appear[: g.m_real]
    hist = np.bincount(appear, minlength=mu + 1).astype(np.float64)
    return hist / max(g.m_real, 1)


def max_shard_fraction(g: Graph, part: SamplePartition) -> float:
    """Table 7: largest device-local edge count / total edges."""
    return float(part.edge_counts.max() / max(g.m_real, 1))


def lane_fill_rate(g: Graph, x_sorted_or_not: np.ndarray, *, lane_width: int = 128,
                   seed: int = 0, max_edges: int = 1 << 15) -> float:
    """Table 6 analogue: fraction of useful lanes per touched lane-tile.

    For each (edge, lane-tile) pair with >= 1 sampled lane, count sampled
    lanes / lane_width. The paper's warp (32 threads) becomes the VPU lane
    tile; pass lane_width=32 to reproduce the paper's exact metric.
    """
    r = x_sorted_or_not.shape[0]
    assert r % lane_width == 0
    eh = edge_hash(g.src[: g.m_real], g.dst[: g.m_real], seed=seed)[:max_edges]
    thr = weight_to_threshold(g.weight)[: g.m_real][:max_edges]
    sampled_slots = 0
    active_tiles = 0
    x = x_sorted_or_not
    chunk = max(1, (1 << 22) // r)
    for lo in range(0, eh.shape[0], chunk):
        hi = min(lo + chunk, eh.shape[0])
        mask = (eh[lo:hi, None] ^ x[None, :]) < thr[lo:hi, None]  # (c, R)
        tiles = mask.reshape(hi - lo, r // lane_width, lane_width)
        any_tile = tiles.any(axis=2)
        sampled_slots += int(tiles.sum())
        active_tiles += int(any_tile.sum())
    if active_tiles == 0:
        return 0.0
    return sampled_slots / (active_tiles * lane_width)
