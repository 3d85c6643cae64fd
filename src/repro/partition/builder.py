"""Host-side 2-D partition build: plan -> bucketed, padded device arrays.

The builder consumes a :class:`~repro.partition.plan.PartitionPlan` (vertex
relabeling) and each sim shard's edge set, and emits the fixed-shape bucket
arrays the ``shard_map`` body sweeps over. The mesh path builds it once per
(graph, config, mesh), from the edge sets of the sim shards' fixed value
ranges (:func:`~repro.partition.plan.sample_edge_sets_in_ranges`), and
keeps it on the devices for every job whose X falls inside the ranges. An
X outside them, or a model without a range test (``lt``), takes the per-X
build: the edge sets X's own FASST chunks sample
(:func:`~repro.partition.plan.sample_edge_sets`), built for that call
alone. A bucket may hold edges dead for all of its shard's registers: the
per-(edge, register) predicate on the device makes them add nothing. Two
padding modes:

  * ``"global"`` — every bucket padded to one global ``b_max`` (the
    pre-planner behaviour, kept bit-compatible for the golden test);
  * ``"step"``   — each ring step padded to its own rounded max across
    shards (dead-slot work shrinks to what the *widest shard of that step*
    needs; empty steps collapse to width 0 and the runtime skips them).

Bucket arrays are per-step tuples: ``p_h[k]`` has shape
``(mu_v, mu_s, B_k)`` — [write-owner shard, sim shard, slot]. At ring step
``k`` vertex-shard ``v`` reads the register block of shard
``(v + k) % mu_v``. ``owned_ids[v, i]`` is the *original* vertex id of
shard ``v``'s local row ``i``; register hashes, validity masks, and
reported seeds all go through it, which is what makes results independent
of the plan's relabeling.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.graphs.structs import Graph
from repro.obs import trace
from repro.partition.plan import (PartitionPlan, SampledEdges, plan_partition,
                                  sample_edge_sets)


@dataclasses.dataclass(frozen=True)
class Partition2D:
    """Everything the shard_map body consumes, already bucketed + padded."""

    n: int
    n_pad: int                 # padded so mu_v | n_pad
    n_loc: int
    j_loc: int
    mu_v: int
    mu_s: int
    x_shards: np.ndarray       # uint32[mu_s, j_loc] (FASST-sorted chunks)
    owned_ids: np.ndarray      # int32[mu_v, n_loc] original vertex id per row
    # propagate buckets: write row = src (local id), read row = dst (block id)
    p_h: Tuple[np.ndarray, ...]  # k -> uint32[mu_v, mu_s, B_k] edge hash
    p_w: Tuple[np.ndarray, ...]  # int32 — local write row
    p_r: Tuple[np.ndarray, ...]  # int32 — row within the read block
    p_t: Tuple[np.ndarray, ...]  # uint32 — sampling threshold / interval width
    p_l: Tuple[np.ndarray, ...]  # uint32 — interval low endpoint (model zoo)
    # cascade buckets: write row = dst (local id), read row = src (block id)
    c_h: Tuple[np.ndarray, ...]
    c_w: Tuple[np.ndarray, ...]
    c_r: Tuple[np.ndarray, ...]
    c_t: Tuple[np.ndarray, ...]
    c_l: Tuple[np.ndarray, ...]
    edge_counts: np.ndarray    # int64[mu_v, mu_s] real (unpadded) edges per shard
    p_counts: np.ndarray       # int64[mu_v, mu_s, mu_v] real edges per bucket
    c_counts: np.ndarray
    comm_bytes_per_sweep: int  # ring traffic per device per sweep (both phases equal)
    plan: Optional[PartitionPlan] = None
    pad_mode: str = "step"

    @property
    def bucket_slots(self) -> int:
        """Slots of the propagate and cascade buckets, over every ring step
        and shard."""
        return int(sum(a.size for a in self.p_h + self.c_h))

    @property
    def bucket_pad_share(self) -> float:
        """Share of :attr:`bucket_slots` that hold no edge."""
        real = int(self.p_counts.sum() + self.c_counts.sum())
        return 1.0 - real / max(self.bucket_slots, 1)

    def stats(self):
        """Measured cost-model stats (see ``repro.partition.cost``)."""
        from repro.partition.cost import measure_partition

        return measure_partition(self)


def _bucketize_steps(ids: np.ndarray, w_own: np.ndarray, k: np.ndarray,
                     eh: np.ndarray, wrow: np.ndarray, rrow: np.ndarray,
                     thr: np.ndarray, elo: np.ndarray, mu_v: int,
                     widths: np.ndarray):
    """Scatter per-edge data into per-step padded buckets.

    Returns, for each ring step ``k``, five ``(mu_v, widths[k])`` arrays
    ``(h, w, r, t, l)``. In-bucket order is ascending original edge id —
    identical to the historical single-``b_max`` layout, so ``"global"``
    padding reproduces it bit-for-bit."""
    steps = []
    order = np.lexsort((ids, w_own, k))
    w_s, k_s = w_own[order], k[order]
    eh_s, wr_s, rr_s, th_s, lo_s = (eh[order], wrow[order], rrow[order],
                                    thr[order], elo[order])
    keys = k_s.astype(np.int64) * mu_v + w_s
    boundaries = np.searchsorted(keys, np.arange(mu_v * mu_v + 1))
    for kk in range(mu_v):
        b_k = int(widths[kk])
        h_out = np.zeros((mu_v, b_k), dtype=np.uint32)
        w_out = np.zeros((mu_v, b_k), dtype=np.int32)
        r_out = np.zeros((mu_v, b_k), dtype=np.int32)
        t_out = np.zeros((mu_v, b_k), dtype=np.uint32)  # thr=0 padding is inert
        l_out = np.zeros((mu_v, b_k), dtype=np.uint32)
        for v in range(mu_v):
            lo, hi = boundaries[kk * mu_v + v], boundaries[kk * mu_v + v + 1]
            cnt = hi - lo
            if cnt == 0:
                continue
            h_out[v, :cnt] = eh_s[lo:hi]
            w_out[v, :cnt] = wr_s[lo:hi]
            r_out[v, :cnt] = rr_s[lo:hi]
            t_out[v, :cnt] = th_s[lo:hi]
            l_out[v, :cnt] = lo_s[lo:hi]
        steps.append((h_out, w_out, r_out, t_out, l_out))
    return steps


def _round_up(v: np.ndarray, block: int) -> np.ndarray:
    return v + (-v) % block


@trace.traced("partition.build_buckets", phase="plan")
def build_partition_2d(g: Graph, x: np.ndarray, mu_v: int, mu_s: int, *,
                       seed: int = 0, method: str = "fasst",
                       edge_block: int = 256, model: str = "wc",
                       plan: Optional[PartitionPlan] = None,
                       pad_mode: str = "step",
                       sampled: Optional[SampledEdges] = None) -> Partition2D:
    """FASST sample-space split × planned vertex split, fully bucketed.

    ``plan=None`` builds the bit-compatible ``block`` plan (identity
    relabeling). ``pad_mode="global"`` additionally restores the historical
    one-``b_max``-for-everything padding. ``sampled`` passes in the
    :func:`~repro.partition.plan.sample_edge_sets` preprocessing when the
    caller already ran it for the planner.
    """
    if pad_mode not in ("global", "step"):
        raise ValueError(f"pad_mode must be 'global' or 'step', got {pad_mode!r}")
    r = x.shape[0]
    assert r % mu_s == 0
    if sampled is None:
        sampled = sample_edge_sets(g, x, mu_s, seed=seed, model=model,
                                   method=method)
    x_shards, masks = sampled.x_shards, sampled.masks
    j_loc = r // mu_s

    if plan is None:
        plan = plan_partition(g, mu_v, mu_s=mu_s, strategy="block", seed=seed,
                              model=model)
    plan.validate(g)
    if plan.mu_v != mu_v:
        raise ValueError(f"plan built for mu_v={plan.mu_v}, asked for {mu_v}")
    n_pad, n_loc = plan.n_pad, plan.n_loc
    ep = sampled.ep
    eh_all, lo_all, thr_all = ep.h, ep.lo, ep.thr
    rows = plan.perm[g.src.astype(np.int64)].astype(np.int64)
    cols = plan.perm[g.dst.astype(np.int64)].astype(np.int64)
    own_src = (rows // n_loc).astype(np.int32)
    own_dst = (cols // n_loc).astype(np.int32)
    # bucket counts first so every shard pads identically
    counts_p = np.zeros((mu_v, mu_s, mu_v), dtype=np.int64)
    counts_c = np.zeros((mu_v, mu_s, mu_v), dtype=np.int64)
    counts = np.zeros((mu_v, mu_s), dtype=np.int64)
    for s in range(mu_s):
        ids = masks[s]
        kp = (own_dst[ids] - own_src[ids]) % mu_v
        kc = (own_src[ids] - own_dst[ids]) % mu_v
        bp = np.bincount(own_src[ids].astype(np.int64) * mu_v + kp,
                         minlength=mu_v * mu_v).reshape(mu_v, mu_v)
        bc = np.bincount(own_dst[ids].astype(np.int64) * mu_v + kc,
                         minlength=mu_v * mu_v).reshape(mu_v, mu_v)
        counts_p[:, s, :] = bp
        counts_c[:, s, :] = bc
        counts[:, s] = bp.sum(axis=1)
    if pad_mode == "global":
        b_max = int(max(counts_p.max(initial=0), counts_c.max(initial=0), 1))
        b_max += (-b_max) % edge_block
        widths_p = np.full(mu_v, b_max, dtype=np.int64)
        widths_c = widths_p
    else:
        # per-step padding: each ring step pays for its own widest bucket
        widths_p = _round_up(counts_p.max(axis=(0, 1)), edge_block)
        widths_c = _round_up(counts_c.max(axis=(0, 1)), edge_block)

    p_parts, c_parts = [], []
    for s in range(mu_s):
        ids = masks[s]
        e_h, e_t, e_l = eh_all[ids], thr_all[ids], lo_all[ids]
        wsrc, wdst = own_src[ids], own_dst[ids]
        kp = (wdst - wsrc) % mu_v
        kc = (wsrc - wdst) % mu_v
        src_loc = (rows[ids] % n_loc).astype(np.int32)
        dst_loc = (cols[ids] % n_loc).astype(np.int32)
        p_parts.append(_bucketize_steps(ids, wsrc, kp, e_h, src_loc, dst_loc,
                                        e_t, e_l, mu_v, widths_p))
        c_parts.append(_bucketize_steps(ids, wdst, kc, e_h, dst_loc, src_loc,
                                        e_t, e_l, mu_v, widths_c))

    def stack(parts, i):
        # parts[s][k][i] is (mu_v, B_k); stack sim shards -> (mu_v, mu_s, B_k)
        return tuple(np.stack([parts[s][k][i] for s in range(mu_s)], axis=1)
                     for k in range(mu_v))

    comm = (mu_v - 1) * n_loc * j_loc  # int8 register block ring traffic / sweep
    return Partition2D(
        n=g.n, n_pad=n_pad, n_loc=n_loc, j_loc=j_loc, mu_v=mu_v, mu_s=mu_s,
        x_shards=x_shards, owned_ids=plan.owned_ids(),
        p_h=stack(p_parts, 0), p_w=stack(p_parts, 1), p_r=stack(p_parts, 2),
        p_t=stack(p_parts, 3), p_l=stack(p_parts, 4),
        c_h=stack(c_parts, 0), c_w=stack(c_parts, 1), c_r=stack(c_parts, 2),
        c_t=stack(c_parts, 3), c_l=stack(c_parts, 4),
        edge_counts=counts, p_counts=counts_p, c_counts=counts_c,
        comm_bytes_per_sweep=comm, plan=plan, pad_mode=pad_mode)
