"""Serial-ring executor: the 2-D distributed algorithm on one host.

Runs the exact bucketed ring schedule the ``shard_map`` runtime executes —
fill, ring propagate to fixpoint, K rounds of {select, cascade, score,
lazy-rebuild} — but serially over the ``(mu_v, mu_s)`` shard grid in numpy.
Three jobs:

  * **planner invariance tests** — seed sets and spread estimates must be
    identical across every :mod:`repro.partition.plan` strategy (and any
    random relabeling), and they must match the single-device ``find_seeds``
    path; this executor makes that testable without a multi-device mesh;
  * **benchmarks** — ``benchmarks/partition_balance.py`` times real bucket
    sweeps per planner without device multiplexing noise;
  * **reference** — a readable spelling of the ring schedule (the
    ``shard_map`` body in ``core/distributed.py`` is its device twin).

Numerics mirror the device path: int8 registers, float32 estimator sums
accumulated per sim shard in shard order (the psum), min-original-id
tie-breaking in selection.
"""
from __future__ import annotations

from time import perf_counter
from typing import Optional

import numpy as np

from repro.core.difuser import DiFuserConfig, InfluenceResult, resolve_model
from repro.core.sampling import clz32, make_x_vector, register_hash
from repro.core.sketch import C_HARMONIC, PHI_FM, VISITED
from repro.graphs.structs import Graph
from repro.obs import shardprof, trace
from repro.partition.builder import Partition2D, build_partition_2d
from repro.partition.plan import (PartitionPlan, plan_partition,
                                  sample_edge_sets)


def _est_from_sums_np(stat, cnt, total_regs: int, estimator: str):
    """numpy float32 mirror of ``sketch.estimate_from_sums``."""
    frac = cnt / np.float32(total_regs)
    if estimator == "hll":
        est = np.float32(C_HARMONIC) * cnt / np.maximum(stat, np.float32(1e-30))
    elif estimator == "fm_mean":
        mean = stat / np.maximum(cnt, np.float32(1.0))
        est = np.exp2(mean) / np.float32(PHI_FM)
    else:
        raise ValueError(f"unknown estimator: {estimator}")
    return np.where(cnt > 0, est * frac, np.float32(0.0))


class _RingState:
    """Shard-grid register state + the bucket sweeps over it.

    ``reg_offset`` offsets the register hash slots (sample-space store
    banks — same contract as ``ops.sketch_fill``); ``matrix`` warm-starts
    the state from an existing ``(mu_v, mu_s, n_loc, j_loc)`` grid instead
    of a fresh fill (the shard-restricted delta-repair path).
    """

    def __init__(self, part: Partition2D, g: Graph, cfg: DiFuserConfig, *,
                 reg_offset: int = 0, matrix: Optional[np.ndarray] = None):
        self.part, self.cfg = part, cfg
        #: Optional :class:`repro.obs.shardprof.ShardProfiler`: when set,
        #: every (shard, ring step) bucket merge is individually timed —
        #: the serial ring is the one executor where per-shard time is
        #: physically separable, so this is the measured ground truth the
        #: predicted PlanStats are checked against.
        self.profiler = None
        #: Extra comm-free sweeps before each full ring sweep (the serial
        #: twin of ``DistributedConfig.local_sweeps``): only the kk=0
        #: self-shard buckets are merged, so no cross-shard block is read.
        #: Result-invariant — extra monotone sweeps toward the unique
        #: max-merge fixpoint (repro.tune may raise it; 0 = historical).
        self.local_sweeps = 0
        #: Fused prologue routing (the serial twin of
        #: ``DistributedConfig.fuse_sweeps``/``lane_fill``): when on, the
        #: comm-free prologue runs all ``local_sweeps`` iterations through
        #: one ``ops.fused_sweep`` launch per shard (kernels/fused_sweep —
        #: register block resident across sweeps) instead of re-running the
        #: numpy ``sweep_local`` merge per sweep. Bit-identical by the
        #: kernel contract; repro.tune flips these from measured winners.
        self.fuse_sweeps = False
        self.lane_fill = 0
        self.pred = resolve_model(cfg.model).predicate
        self.owned = part.owned_ids                        # (mu_v, n_loc)
        self.valid = self.owned < g.n                      # padding rows
        mu_v, mu_s = part.mu_v, part.mu_s
        n_loc, j_loc = part.n_loc, part.j_loc
        grid_shape = (mu_v, mu_s, n_loc, j_loc)
        if matrix is not None:
            # warm start (shard-restricted repair): the O(n_pad * J) fresh
            # hash fill is only needed by refill(), which the repair path
            # never calls — skip the dominant cost of a small repair
            assert matrix.shape == grid_shape, (matrix.shape, grid_shape)
            self.fresh = None
            self.m = np.array(matrix, dtype=np.int8)
            return
        fresh = np.empty(grid_shape, dtype=np.int8)
        for v in range(mu_v):
            for s in range(mu_s):
                j_ids = (np.arange(j_loc, dtype=np.uint32)
                         + np.uint32(s * j_loc + reg_offset))
                h = register_hash(self.owned[v].astype(np.uint32)[:, None],
                                  j_ids[None, :], seed=cfg.seed)
                fresh[v, s] = clz32(h).astype(np.int8)
        self.fresh = fresh
        self.m = np.where(self.valid[:, None, :, None], fresh,
                          np.int8(VISITED))

    def canonical_matrix(self, n_pad: int) -> np.ndarray:
        """Un-permute the shard grid to the canonical single-device layout:
        ``int8[n_pad, mu_s * j_loc]`` with rows in original-id order and
        columns in canonical x order (sim-shard blocks are contiguous chunks
        of the sorted sample vector)."""
        p = self.part
        planned = self.m.transpose(0, 2, 1, 3).reshape(
            p.mu_v * p.n_loc, p.mu_s * p.j_loc)
        return planned[p.plan.perm[:n_pad]]

    def _mask(self, kk: int, v: int, s: int, bufs):
        bh = bufs[0][kk][v, s]
        bl, bt = bufs[4][kk][v, s], bufs[3][kk][v, s]
        return self.pred(bh[:, None], bl[:, None], bt[:, None],
                         self.part.x_shards[s][None, :])

    def sweep_local(self) -> bool:
        """One comm-free propagate sweep: merge only the kk=0 buckets (edges
        whose read block is the writing shard's own rows). The device twin
        is the ``local_sweeps`` prologue of the shard_map ring body."""
        p = self.part
        bufs = (p.p_h, p.p_w, p.p_r, p.p_t, p.p_l)
        if bufs[0][0].shape[-1] == 0:
            return False
        out = self.m.copy()
        for v in range(p.mu_v):
            for s in range(p.mu_s):
                acc = self.m[v, s].copy()
                bw, br = bufs[1][0][v, s], bufs[2][0][v, s]
                contrib = np.where(self._mask(0, v, s, bufs),
                                   self.m[v, s][br], np.int8(VISITED))
                np.maximum.at(acc, bw, contrib)
                out[v, s] = np.where(self.m[v, s] == VISITED, self.m[v, s], acc)
        changed = bool((out != self.m).any())
        self.m = out
        return changed

    def sweep_local_fused(self, num_sweeps: int) -> bool:
        """The fused spelling of ``num_sweeps`` x :meth:`sweep_local`: per
        (vertex, sim) shard, one :func:`ops.fused_sweep` launch runs every
        prologue sweep over the kk=0 bucket with the shard's register block
        resident between sweeps. Results are bit-identical to the looped
        numpy path (Jacobi max-merge; the fused kernel's contract) — only
        the launch/traffic pattern changes."""
        import jax.numpy as jnp

        from repro.kernels import ops

        p = self.part
        bufs = (p.p_h, p.p_w, p.p_r, p.p_t, p.p_l)
        if num_sweeps <= 0 or bufs[0][0].shape[-1] == 0:
            return False
        changed = False
        for v in range(p.mu_v):
            for s in range(p.mu_s):
                out = np.asarray(ops.fused_sweep(
                    jnp.asarray(self.m[v, s]),
                    jnp.asarray(bufs[1][0][v, s]),      # bw: write rows
                    jnp.asarray(bufs[2][0][v, s]),      # br: read rows
                    jnp.asarray(bufs[3][0][v, s]),      # thr (interval width)
                    jnp.asarray(p.x_shards[s]),
                    h=jnp.asarray(bufs[0][0][v, s]),
                    lo=jnp.asarray(bufs[4][0][v, s]),
                    num_sweeps=int(num_sweeps), impl=self.cfg.impl,
                    edge_chunk=self.cfg.edge_chunk,
                    lane_fill=int(self.lane_fill), predicate=self.pred))
                changed = changed or bool((out != self.m[v, s]).any())
                self.m[v, s] = out
        return changed

    def sweep_propagate(self) -> bool:
        if self.fuse_sweeps and self.local_sweeps:
            self.sweep_local_fused(self.local_sweeps)
        else:
            for _ in range(self.local_sweeps):   # comm-free prologue (tunable)
                if not self.sweep_local():
                    break
        p = self.part
        prof = self.profiler
        bufs = (p.p_h, p.p_w, p.p_r, p.p_t, p.p_l)
        out = self.m.copy()
        for v in range(p.mu_v):
            for s in range(p.mu_s):
                acc = self.m[v, s].copy()
                for kk in range(p.mu_v):
                    if bufs[0][kk].shape[-1] == 0:
                        continue
                    t0 = perf_counter() if prof is not None else 0.0
                    bw, br = bufs[1][kk][v, s], bufs[2][kk][v, s]
                    block = self.m[(v + kk) % p.mu_v, s]
                    contrib = np.where(self._mask(kk, v, s, bufs), block[br],
                                       np.int8(VISITED))
                    np.maximum.at(acc, bw, contrib)
                    if prof is not None:
                        prof.record(v, kk, perf_counter() - t0,
                                    shardprof.bucket_bytes(
                                        p.p_counts[v, s, kk], p.j_loc))
                out[v, s] = np.where(self.m[v, s] == VISITED, self.m[v, s], acc)
        if prof is not None:
            prof.count_sweep()
        changed = bool((out != self.m).any())
        self.m = out
        return changed

    def sweep_propagate_restricted(self, read_dirty) -> set:
        """One propagate sweep over only the buckets whose *read* block
        belongs to a shard in ``read_dirty``; returns the set of vertex
        shards whose rows changed (the next sweep's dirty set).

        This is the frontier-restricted repair sweep: starting from a sound
        lower bound of the fixpoint (e.g. the pre-delta matrix), changes can
        only originate at rows the dirtied shards feed, so sweeping buckets
        that read from clean shards is provably a no-op and skipped.
        """
        p = self.part
        bufs = (p.p_h, p.p_w, p.p_r, p.p_t, p.p_l)
        read_dirty = set(int(v) for v in read_dirty)
        out = self.m.copy()
        for v in range(p.mu_v):
            for s in range(p.mu_s):
                acc = self.m[v, s].copy()
                hit = False
                for kk in range(p.mu_v):
                    if (v + kk) % p.mu_v not in read_dirty:
                        continue
                    if bufs[0][kk].shape[-1] == 0:
                        continue
                    hit = True
                    bw, br = bufs[1][kk][v, s], bufs[2][kk][v, s]
                    block = self.m[(v + kk) % p.mu_v, s]
                    contrib = np.where(self._mask(kk, v, s, bufs), block[br],
                                       np.int8(VISITED))
                    np.maximum.at(acc, bw, contrib)
                if hit:
                    out[v, s] = np.where(self.m[v, s] == VISITED,
                                         self.m[v, s], acc)
        changed = {v for v in range(p.mu_v)
                   if (out[v] != self.m[v]).any()}
        self.m = out
        return changed

    def sweep_cascade(self) -> bool:
        p = self.part
        prof = self.profiler
        bufs = (p.c_h, p.c_w, p.c_r, p.c_t, p.c_l)
        out = self.m.copy()
        for v in range(p.mu_v):
            for s in range(p.mu_s):
                acc = (self.m[v, s] == VISITED).astype(np.uint8)
                for kk in range(p.mu_v):
                    if bufs[0][kk].shape[-1] == 0:
                        continue
                    t0 = perf_counter() if prof is not None else 0.0
                    bw, br = bufs[1][kk][v, s], bufs[2][kk][v, s]
                    block = self.m[(v + kk) % p.mu_v, s]
                    newly = (self._mask(kk, v, s, bufs)
                             & (block[br] == VISITED)).astype(np.uint8)
                    np.maximum.at(acc, bw, newly)
                    if prof is not None:
                        prof.record(v, kk, perf_counter() - t0,
                                    shardprof.bucket_bytes(
                                        p.c_counts[v, s, kk], p.j_loc))
                out[v, s] = np.where(acc.astype(bool), np.int8(VISITED),
                                     self.m[v, s])
        if prof is not None:
            prof.count_sweep()
        changed = bool((out != self.m).any())
        self.m = out
        return changed

    def fixpoint(self, sweep, max_iters: int) -> int:
        it, changed = 0, True
        while changed and it < max_iters:
            changed = sweep()
            it += 1
        return it

    def select(self, total_regs: int, n_big: int):
        """Min-original-id tie-broken argmax over finished estimates."""
        m = self.m
        vld = m != VISITED
        stat = np.zeros(m.shape[:1] + m.shape[2:3], dtype=np.float32)
        cnt = np.zeros_like(stat)
        for s in range(self.part.mu_s):   # psum over sim shards, shard order
            mf = m[:, s].astype(np.float32)
            if self.cfg.estimator == "hll":
                term = np.where(vld[:, s], np.exp2(-mf), np.float32(0.0))
            else:
                term = np.where(vld[:, s], mf, np.float32(0.0))
            stat += term.sum(axis=-1, dtype=np.float32)
            cnt += vld[:, s].sum(axis=-1).astype(np.float32)
        est = _est_from_sums_np(stat, cnt, total_regs, self.cfg.estimator)
        est = np.where(self.valid, est, np.float32(-1.0))
        best = est.max()
        seed_v = int(np.where(est == best, self.owned, n_big).min())
        return seed_v, np.float32(best)

    def commit(self, seed_v: int) -> None:
        hit = self.owned == seed_v                        # (mu_v, n_loc)
        self.m = np.where(hit[:, None, :, None], np.int8(VISITED), self.m)

    def visited_count(self) -> int:
        return int(((self.m == VISITED) & self.valid[:, None, :, None]).sum())

    def refill(self) -> None:
        assert self.fresh is not None, "refill() needs a cold-started state"
        self.m = np.where(self.m == VISITED, self.m, self.fresh)


def _find_seeds_ring_serial(g: Graph, k: int,
                            config: Optional[DiFuserConfig] = None,
                            *, mu_v: int = 2, mu_s: int = 2,
                            strategy: str = "block",
                            plan: Optional[PartitionPlan] = None,
                            x: Optional[np.ndarray] = None,
                            pad_mode: str = "step", local_sweeps: int = 0,
                            fuse_sweeps: bool = False, lane_fill: int = 0):
    """Serial-ring Alg. 4 driver (the ``serial`` runtime backend's body).

    Returns ``(InfluenceResult, Partition2D)`` like the distributed path;
    seeds are original vertex ids regardless of the plan's relabeling.
    """
    cfg = config or DiFuserConfig()
    g = g.sorted_by_dst()
    if x is None:
        x = make_x_vector(cfg.num_registers, seed=cfg.seed)
    x = np.asarray(x, dtype=np.uint32)
    sampled = sample_edge_sets(g, x, mu_s, seed=cfg.seed, model=cfg.model)
    if plan is None:
        plan = plan_partition(g, mu_v, mu_s=mu_s, strategy=strategy,
                              seed=cfg.seed, model=cfg.model, sampled=sampled)
    part = build_partition_2d(g, x, mu_v, mu_s, seed=cfg.seed, model=cfg.model,
                              plan=plan, pad_mode=pad_mode, sampled=sampled)
    st = _RingState(part, g, cfg)
    st.local_sweeps = int(local_sweeps)
    st.fuse_sweeps = bool(fuse_sweeps)
    st.lane_fill = int(lane_fill)
    if shardprof.enabled():
        st.profiler = shardprof.profile_for_partition(
            part, backend="serial", phase="fixpoint")
    total_regs = part.mu_s * part.j_loc
    with trace.span("serial.build_fixpoint", phase="fixpoint",
                    mu_v=mu_v, mu_s=mu_s) as sp:
        build_iters = st.fixpoint(st.sweep_propagate, cfg.max_propagate_iters)
        sp.annotate(iters=build_iters)
    if st.profiler is not None:
        # null spans report duration 0.0 (tracing off) -> let the profiler
        # fall back to its own wall clock
        shardprof.publish(st.profiler.finish(sp.duration_s or None),
                          predicted=plan.predicted)
        st.profiler = None   # rounds reuse the state; profile is the build's

    seeds = np.zeros(k, dtype=np.int32)
    gains = np.zeros(k, dtype=np.float32)
    scores = np.zeros(k, dtype=np.float32)
    rebuilds = np.zeros(k, dtype=bool)
    casc_iters = np.zeros(k, dtype=np.int32)
    rebuild_iters = np.zeros(k, dtype=np.int32)
    oldscore = np.float32(0.0)
    for i in range(k):
        with trace.span("serial.round", phase="select", round=i) as rsp:
            s_v, gain = st.select(total_regs, part.n_pad)
            st.commit(s_v)
            with trace.span("serial.cascade_fixpoint", phase="ring", round=i):
                casc_iters[i] = st.fixpoint(st.sweep_cascade,
                                            cfg.max_cascade_iters)
            new_score = np.float32(st.visited_count()) / np.float32(total_regs)
            rel = (new_score - oldscore) / np.maximum(new_score,
                                                      np.float32(1e-9))
            do_rebuild = bool(rel > np.float32(cfg.rebuild_threshold))
            if do_rebuild:
                with trace.span("serial.rebuild", phase="build", round=i):
                    st.refill()
                    rebuild_iters[i] = st.fixpoint(st.sweep_propagate,
                                                   cfg.max_propagate_iters)
                oldscore = new_score
            rsp.annotate(seed=s_v, rebuild=do_rebuild)
        seeds[i], gains[i], scores[i], rebuilds[i] = s_v, gain, new_score, do_rebuild
    res = InfluenceResult(seeds=seeds, est_gains=gains, scores=scores,
                          rebuilds=rebuilds, propagate_iters=build_iters,
                          x=np.sort(x), cascade_iters=casc_iters,
                          rebuild_iters=rebuild_iters)
    return res, part


def find_seeds_ring_serial(g: Graph, k: int,
                           config: Optional[DiFuserConfig] = None,
                           *, mu_v: int = 2, mu_s: int = 2,
                           strategy: str = "block",
                           plan: Optional[PartitionPlan] = None,
                           x: Optional[np.ndarray] = None,
                           pad_mode: str = "step"):
    """Deprecated entry point — prefer the unified runtime facade::

        from repro.runtime import InfluenceSession, RunSpec
        spec = RunSpec(backend="serial", mu_v=2, mu_s=2, partition=strategy)
        InfluenceSession(g, spec).find_seeds(k)

    Kept as a thin shim through the ``serial`` backend; results are
    bit-identical to the historical direct call (golden-tested). Returns
    (InfluenceResult, Partition2D) like before."""
    from repro.runtime import run, warn_deprecated
    from repro.runtime.spec import RunSpec

    warn_deprecated("repro.partition.serial.find_seeds_ring_serial",
                    "repro.runtime.InfluenceSession.find_seeds")
    spec = RunSpec.from_config(config, backend="serial", mu_v=mu_v, mu_s=mu_s,
                               partition=strategy, pad_mode=pad_mode)
    report = run(g, k, spec, x=x, plan=plan)
    return report.result, report.partition


def build_matrix_ring_serial(g: Graph, config: Optional[DiFuserConfig] = None,
                             x: Optional[np.ndarray] = None, *,
                             mu_v: int = 2, mu_s: int = 1,
                             strategy: str = "block",
                             plan: Optional[PartitionPlan] = None,
                             pad_mode: str = "step", reg_offset: int = 0,
                             local_sweeps: int = 0, fuse_sweeps: bool = False,
                             lane_fill: int = 0):
    """Alg. 4 lines 3-6 on the serial ring: fill + propagate-to-fixpoint.

    Expects ``g`` dst-sorted and ``x`` canonical (sorted). Returns
    ``(matrix int8[g.n_pad, len(x)], iters, Partition2D)`` with ``matrix``
    in the canonical single-device layout — bit-identical to
    ``core.difuser.build_sketch_matrix`` with the same ``reg_offset``, which
    is what lets :class:`~repro.service.store.SketchStore` banks build
    through the ``serial`` backend.
    """
    cfg = config or DiFuserConfig()
    if x is None:
        x = make_x_vector(cfg.num_registers, seed=cfg.seed)
        x = np.sort(np.asarray(x, dtype=np.uint32))
    x = np.asarray(x, dtype=np.uint32)
    sampled = sample_edge_sets(g, x, mu_s, seed=cfg.seed, model=cfg.model)
    if plan is None:
        plan = plan_partition(g, mu_v, mu_s=mu_s, strategy=strategy,
                              seed=cfg.seed, model=cfg.model, sampled=sampled)
    part = build_partition_2d(g, x, mu_v, mu_s, seed=cfg.seed, model=cfg.model,
                              plan=plan, pad_mode=pad_mode, sampled=sampled)
    with trace.span("serial.build_matrix", phase="build", mu_v=mu_v,
                    mu_s=mu_s, reg_offset=reg_offset) as sp:
        st = _RingState(part, g, cfg, reg_offset=reg_offset)
        st.local_sweeps = int(local_sweeps)
        st.fuse_sweeps = bool(fuse_sweeps)
        st.lane_fill = int(lane_fill)
        if shardprof.enabled():
            st.profiler = shardprof.profile_for_partition(
                part, backend="serial", phase="build")
        iters = st.fixpoint(st.sweep_propagate, cfg.max_propagate_iters)
        sp.annotate(iters=iters)
    if st.profiler is not None:
        shardprof.publish(st.profiler.finish(sp.duration_s or None),
                          predicted=plan.predicted)
    return st.canonical_matrix(g.n_pad), iters, part


def repair_plan_shards(g: Graph, config: DiFuserConfig, x: np.ndarray,
                       planned_m: np.ndarray, plan: PartitionPlan,
                       touched, *, pad_mode: str = "step"):
    """Shard-restricted monotone insertion repair on the serial ring.

    ``planned_m`` is the pre-delta register matrix in the plan's row order
    (``StoreEntry.planned_matrix()``), a sound lower bound of the post-delta
    fixpoint; ``g`` is the *new* (post-delta, dst-sorted) graph; ``touched``
    is ``DeltaReport.plan_shards_touched`` — the vertex shards the delta's
    endpoints land in.

    Sweeps start restricted to buckets reading from the touched shards and
    widen only as changes actually spread (``sweep_propagate_restricted``),
    so a localized delta re-propagates exactly its ``plan_shards_touched``
    and leaves every other shard's buckets un-swept. Returns
    ``(planned_matrix, sweeps, shards_swept)`` with the matrix bit-identical
    to a full rebuild (max-merge fixpoints are unique above a sound lower
    bound — the same soundness argument as service.delta's repair).
    """
    x = np.asarray(x, dtype=np.uint32)
    part = build_partition_2d(g, x, plan.mu_v, plan.mu_s, seed=config.seed,
                              model=config.model, plan=plan, pad_mode=pad_mode)
    grid = np.asarray(planned_m, dtype=np.int8).reshape(
        plan.mu_v, plan.n_loc, part.mu_s, part.j_loc).transpose(0, 2, 1, 3)
    st = _RingState(part, g, config, matrix=grid)
    dirty = set(int(v) for v in touched)
    sweeps = 0
    swept: set = set()
    with trace.span("serial.repair", phase="repair",
                    touched=len(dirty)) as sp:
        while dirty and sweeps < config.max_propagate_iters:
            swept |= dirty
            with trace.span("serial.repair_sweep", dirty=len(dirty),
                            sweep=sweeps):
                dirty = st.sweep_propagate_restricted(dirty)
            sweeps += 1
        sp.annotate(sweeps=sweeps, shards_swept=len(swept))
    planned = st.m.transpose(0, 2, 1, 3).reshape(
        plan.mu_v * plan.n_loc, part.mu_s * part.j_loc)
    return planned, sweeps, tuple(sorted(swept))
