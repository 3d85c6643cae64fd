"""Distributed DiFuseR (paper §4) on a JAX mesh, scaled past the paper.

Two partition modes, both SPMD under one ``shard_map``:

* ``sim`` — the paper's scheme. The sample space (registers) is sharded
  over the ``model`` axis; every shard holds all vertices plus its FASST
  device-local edge list. Zero communication in fill/propagate/cascade; one
  psum of the (2, n_pad) estimator statistics + one scalar psum per seed
  round (the paper's Fig. 3 reduction; its MPI BROADCAST disappears because
  every shard computes the identical argmax).

* ``2d`` — beyond the paper (its §6 names the O(n) reduction as the
  thousand-node blocker). Registers are sharded over ``model`` AND vertices
  over ``data``. Propagation needs remote registers, so each shard's edges
  are bucketed by the *read*-owner shard and a ring schedule walks the
  ``data`` axis: at step k the shard processes the bucket whose reads live
  in the register block that just arrived, then ``ppermute``s the block on.
  Compute overlaps communication; peak memory is two (n/P, J/S) blocks; the
  selection reduce shrinks from O(n) to O(n/P) + P scalars.

The host-side partition build lives in :mod:`repro.partition`: a
``PartitionPlan`` (``DistributedConfig.partition`` selects the strategy —
``block`` is the historical contiguous split, ``degree``/``edge`` balance
the per-shard work via a vertex relabeling permutation) feeds
``build_partition_2d``, which emits per-ring-step bucket arrays. The job
and the build run on a partition fixed by the graph: each sim shard takes
every edge live for some value of its fixed range of X values
(``fasst.shard_value_ranges``), so one partition, placed on the mesh once,
and one program per shape serve every X inside the ranges; an X outside
them, or a model without a range test, builds its own. The body
here stays plan-agnostic: it sweeps whatever buckets it is handed, and the
``owned_ids`` array (local row -> original vertex id) keeps register
hashes, validity masks, and the reported seeds in original-id space — so
seed sets are bit-identical across planners, and "un-permuting" on exit is
free.

The pod axis (multi-pod mesh) extends the sample space: ``pod × model``
shards form one flat sim axis (more simulations, same algorithm).

Bucket edges carry the precomputed fused-predicate operands (h, lo, thr) of
the configured diffusion model (hash once per edge instead of once per
sweep — legal for *every* registered model because h is sample-independent;
the fused decision still happens per (edge, register) on device through the
model's predicate).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import weakref
from time import perf_counter
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import sketch
from repro.core.difuser import (DiFuserConfig, InfluenceResult, annotate_job,
                                resolve_model)
from repro.core.fasst import in_ranges, partition_samples
from repro.core.sampling import fused_predicate, make_x_vector
from repro.core.sketch import VISITED
from repro.graphs.structs import Graph
from repro.obs import metrics, shardprof, trace
# host-side partition build moved to repro.partition; re-exported here for
# backward compatibility (tests and dryrun historically imported from core)
from repro.partition import (Partition2D, build_partition_2d,  # noqa: F401
                             plan_partition, sample_edge_sets,
                             sample_edge_sets_in_ranges)

# ---------------------------------------------------------------------------
# Device-side shard_map body
# ---------------------------------------------------------------------------


def _bucket_sweep_propagate(acc, block, h, w, r, t, x_loc, lo=None, predicate=None):
    """Jacobi max-merge for one bucket: acc[w] <- max(acc[w], masked block[r])."""
    if lo is None:
        lo = jnp.zeros(t.shape, jnp.uint32)
    if predicate is None:
        predicate = fused_predicate
    mask = predicate(h[:, None].astype(jnp.uint32), lo[:, None].astype(jnp.uint32),
                     t[:, None].astype(jnp.uint32), x_loc[None, :].astype(jnp.uint32))
    vals = block[r]
    contrib = jnp.where(mask, vals, jnp.int8(VISITED))
    return acc.at[w].max(contrib)


def _bucket_sweep_cascade(acc_vis, block, h, w, r, t, x_loc, lo=None, predicate=None):
    if lo is None:
        lo = jnp.zeros(t.shape, jnp.uint32)
    if predicate is None:
        predicate = fused_predicate
    mask = predicate(h[:, None].astype(jnp.uint32), lo[:, None].astype(jnp.uint32),
                     t[:, None].astype(jnp.uint32), x_loc[None, :].astype(jnp.uint32))
    newly = jnp.logical_and(mask, block[r] == VISITED).astype(jnp.uint8)
    return acc_vis.at[w].max(newly)


def _make_distributed_fn(part: Partition2D, *, k: int, vertex_axis: str,
                         sim_axes: Sequence[str], estimator: str,
                         rebuild_threshold: float, max_prop: int, max_casc: int,
                         seed: int, schedule: str = "ring", local_sweeps: int = 0,
                         fuse_sweeps: bool = False,
                         predicate=None, warm: bool = False):
    """Returns the shard_map body running the full Alg. 4 loop.

    Bucket arrays arrive as per-ring-step tuples (``bh[kk]`` is step kk's
    bucket, possibly width 0 — those steps skip their merge at trace time
    but still forward the ring block).

    ``warm=True`` makes the body take each shard's already-propagated
    register block as its first argument and skip fill + the initial
    propagate fixpoint — the device twin of ``core.difuser.find_seeds_warm``
    (the K seed rounds are the identical program either way, so warm mesh
    seeds are bit-identical to cold mesh seeds, which are bit-identical to
    the single-device reference).

    The phases carry the single-device driver's named scopes
    (``difuser.fixpoint``/``select``/``cascade``/``rebuild``), and the body
    returns per-round cascade and rebuild sweep counts after the seeds,
    gains, scores, rebuild flags and first-fixpoint sweeps.
    """
    mu_v, mu_s = part.mu_v, part.mu_s
    n_loc, j_loc, n_real = part.n_loc, part.j_loc, part.n
    total_regs = mu_s * j_loc
    all_axes = (vertex_axis, *sim_axes)
    pred = predicate if predicate is not None else fused_predicate

    def local_sweep(m_loc, bh, bw, br, bt, bl, x_loc, merge):
        """Sweep only the k=0 bucket (reads own register block; no comm)."""
        init = m_loc if merge is _bucket_sweep_propagate else (m_loc == VISITED).astype(jnp.uint8)
        acc = init
        if bh[0].shape[0]:
            acc = merge(acc, m_loc, bh[0], bw[0], br[0], bt[0], x_loc, bl[0], pred)
        if merge is _bucket_sweep_propagate:
            return jnp.where(m_loc == VISITED, m_loc, acc)
        return jnp.where(acc.astype(bool), jnp.int8(VISITED), m_loc)

    def ring_sweep(m_loc, bh, bw, br, bt, bl, x_loc, merge):
        """One full sweep: mu_v ring steps over the data axis."""
        init = m_loc if merge is _bucket_sweep_propagate else (m_loc == VISITED).astype(jnp.uint8)
        acc = init
        if schedule == "allgather" and mu_v > 1:
            # baseline schedule: materialize all blocks, no overlap
            blocks = jax.lax.all_gather(m_loc, vertex_axis)  # (mu_v, n_loc, j_loc)
            me = jax.lax.axis_index(vertex_axis)
            for kk in range(mu_v):
                if bh[kk].shape[0] == 0:
                    continue
                owner = jax.lax.rem(me + kk, mu_v)
                acc = merge(acc, blocks[owner], bh[kk], bw[kk], br[kk], bt[kk],
                            x_loc, bl[kk], pred)
        else:
            block = m_loc
            for kk in range(mu_v):
                if bh[kk].shape[0]:
                    acc = merge(acc, block, bh[kk], bw[kk], br[kk], bt[kk],
                                x_loc, bl[kk], pred)
                if kk + 1 < mu_v:
                    perm = [(i, (i - 1) % mu_v) for i in range(mu_v)]
                    block = jax.lax.ppermute(block, vertex_axis, perm)
        if merge is _bucket_sweep_propagate:
            return jnp.where(m_loc == VISITED, m_loc, acc)
        return jnp.where(acc.astype(bool), jnp.int8(VISITED), m_loc)

    def fixpoint(m_loc, bh, bw, br, bt, bl, x_loc, merge, max_iters):
        def cond(c):
            return jnp.logical_and(c[1], c[2] < max_iters)

        def body(c):
            m_cur, _, it = c
            # block-Jacobi: drain intra-shard propagation before paying for
            # a ring exchange (edges FASST-placed mostly intra-shard, so a
            # few local sweeps kill most of the frontier; §Perf difuser)
            if fuse_sweeps and local_sweeps:
                # fused prologue: one rolled loop region instead of
                # local_sweeps unrolled program segments — the register
                # block stays loop-carried (resident) across every sweep
                m_cur = jax.lax.fori_loop(
                    0, local_sweeps,
                    lambda _i, mm: local_sweep(mm, bh, bw, br, bt, bl,
                                               x_loc, merge),
                    m_cur)
            else:
                for _ in range(local_sweeps):
                    m_cur = local_sweep(m_cur, bh, bw, br, bt, bl, x_loc, merge)
            m_new = ring_sweep(m_cur, bh, bw, br, bt, bl, x_loc, merge)
            changed = jax.lax.psum(jnp.any(m_new != m_cur).astype(jnp.int32), all_axes) > 0
            return m_new, changed, it + 1

        m_out, _, iters = jax.lax.while_loop(cond, body, (m_loc, jnp.bool_(True), jnp.int32(0)))
        return m_out, iters

    def body(*all_args):
        if warm:
            m_in, x_loc, owned, *bufs = all_args
        else:
            m_in = None
            x_loc, owned, *bufs = all_args

        # regroup the flat per-step bucket args: 10 fields x mu_v steps
        def grp(i):
            return tuple(bufs[i * mu_v + kk][0, 0] for kk in range(mu_v))

        ph, pw, pr, pt, pl = grp(0), grp(1), grp(2), grp(3), grp(4)
        ch, cw, cr, ct, cl = grp(5), grp(6), grp(7), grp(8), grp(9)
        x_loc = x_loc[0]
        owned = owned[0]                 # (n_loc,) original vertex ids
        # local shard coordinates; sim axes flatten row-major (pod major)
        si = jnp.int32(0)
        mult = 1
        for ax in reversed(sim_axes):
            si = si + jax.lax.axis_index(ax) * mult
            mult *= _axis_size(ax)
        reg_offset = si * j_loc
        valid_row = owned < n_real

        # ---- fill + initial propagate (Alg. 4 lines 3-6) ----
        # register hashes key on the ORIGINAL vertex id, so the sketch
        # content — and everything downstream — is independent of the plan's
        # relabeling permutation
        j_ids = (jnp.arange(j_loc, dtype=jnp.uint32)[None, :] + reg_offset.astype(jnp.uint32))
        from repro.core.sampling import register_hash

        fresh = jax.lax.clz(register_hash(owned.astype(jnp.uint32)[:, None], j_ids, seed=seed))

        def refill(m_cur):
            return jnp.where(m_cur == VISITED, m_cur, fresh.astype(jnp.int8))

        if warm:
            # warm start: the caller's block IS the propagated fixpoint
            # (fresh is still needed above for the lazy-rebuild refill)
            m_loc, build_iters = m_in, jnp.int32(0)
        else:
            with jax.named_scope("difuser.fixpoint"):
                m_loc = jnp.where(valid_row[:, None], fresh.astype(jnp.int8),
                                  jnp.int8(VISITED))
                m_loc, build_iters = fixpoint(m_loc, ph, pw, pr, pt, pl,
                                              x_loc, _bucket_sweep_propagate,
                                              max_prop)

        # ---- K seed rounds ----
        def round_fn(carry, _):
            m_cur, score, oldscore = carry
            with jax.named_scope("difuser.select"):
                # selection: psum stats over sim axes -> exact for owned rows
                stats = jnp.stack([
                    jnp.sum(jnp.where(m_cur != VISITED, jnp.exp2(-m_cur.astype(jnp.float32)), 0.0), axis=-1),
                    jnp.sum(m_cur != VISITED, axis=-1).astype(jnp.float32)])
                stats = jax.lax.psum(stats, tuple(sim_axes)) if sim_axes else stats
                est = sketch.estimate_from_sums(stats, total_regs, estimator=estimator)
                est = jnp.where(valid_row, est, -1.0)
                # min-original-id tie-break: under a relabeling plan, ids are
                # scattered across shards, so plain argmax (lowest local row)
                # would break bit-identity between planners on est ties
                loc_best = jnp.max(est)
                loc_seed = jnp.min(jnp.where(est == loc_best, owned,
                                             jnp.int32(part.n_pad)))
                # cross-shard argmax: P scalars instead of the paper's O(n)
                bests = jax.lax.all_gather(loc_best, vertex_axis)    # (mu_v,)
                seeds_g = jax.lax.all_gather(loc_seed, vertex_axis)  # (mu_v,)
                gain = jnp.max(bests)
                s_global = jnp.min(jnp.where(bests == gain, seeds_g,
                                             jnp.int32(part.n_pad)))
            with jax.named_scope("difuser.cascade"):
                # commit + cascade
                m_cur = jnp.where((owned == s_global)[:, None], jnp.int8(VISITED), m_cur)
                m_cur, casc_iters = fixpoint(m_cur, ch, cw, cr, ct, cl, x_loc,
                                             _bucket_sweep_cascade, max_casc)
                visited = jnp.sum(jnp.logical_and(m_cur == VISITED, valid_row[:, None]).astype(jnp.int32))
                visited = jax.lax.psum(visited, all_axes).astype(jnp.float32)
            new_score = visited / jnp.float32(total_regs)
            rel = (new_score - oldscore) / jnp.maximum(new_score, 1e-9)

            def rebuild(mm):
                with jax.named_scope("difuser.rebuild"):
                    mm = refill(mm)
                    mm, iters = fixpoint(mm, ph, pw, pr, pt, pl, x_loc,
                                         _bucket_sweep_propagate, max_prop)
                return mm, new_score, iters

            def keep(mm):
                return mm, oldscore, jnp.int32(0)

            m_cur, oldscore, rebuild_iters = jax.lax.cond(
                rel > rebuild_threshold, rebuild, keep, m_cur)
            return (m_cur, new_score, oldscore), (
                s_global, gain, new_score, rel > rebuild_threshold,
                casc_iters, rebuild_iters)

        (_, _, _), outs = jax.lax.scan(round_fn, (m_loc, jnp.float32(0.0), jnp.float32(0.0)),
                                       None, length=k)
        seeds_out, gains, scores, rebuilds, casc, reb = outs
        return seeds_out, gains, scores, rebuilds, build_iters, casc, reb

    # helper resolved at trace time inside shard_map
    _axis_sizes: dict[str, int] = {}

    def _axis_size(ax: str) -> int:
        return _axis_sizes[ax]

    def with_sizes(mesh):
        for ax in (vertex_axis, *sim_axes):
            _axis_sizes[ax] = mesh.shape[ax]
        return body

    return with_sizes


@dataclasses.dataclass(frozen=True)
class DistributedConfig(DiFuserConfig):
    vertex_axis: str = "data"
    sim_axes: tuple = ("model",)
    schedule: str = "ring"          # "ring" | "allgather"
    fasst: bool = True              # False -> naive sample partition
    local_sweeps: int = 0           # extra comm-free sweeps per exchange
    fuse_sweeps: bool = False       # fused (rolled) local-sweep prologue
    lane_fill: int = 0              # fused-kernel register slab width
    #   (consumed by the kernels/fused_sweep launches; the shard_map body
    #   itself keeps full-width panes — its shards are already lane-sized)
    partition: str = "block"        # vertex-assignment strategy (repro.partition)
    pad_mode: str = "step"          # "step" | "global" bucket padding


def _publish_mesh_profile(part, *, phase: str, sweeps: int,
                          wall_s: float) -> None:
    """Measured-profile publication for the SPMD paths. Mesh shards execute
    in lockstep inside one XLA program, so per-shard wall time is not
    separable host-side — the profile carries exact per-(shard, ring step)
    bucket *bytes* (off the built partition's counts, scaled by the sweep
    count the fixpoint ran) plus the overall wall time
    (``per_step_timed=False``; the serial twin supplies measured times)."""
    if not shardprof.enabled():
        return
    prof = shardprof.profile_for_partition(part, backend="mesh", phase=phase)
    prof.add_partition_bytes(np.asarray(part.p_counts), part.j_loc, sweeps)
    predicted = part.plan.predicted if part.plan is not None else None
    shardprof.publish(prof.finish(wall_s), predicted=predicted)


def _sample_edge_sets(g: Graph, x: np.ndarray, mu_s: int,
                      cfg: DistributedConfig):
    """``sample_edge_sets`` under the ``partition.sample_edge_sets`` span.
    Returns (sampled, method)."""
    method = "fasst" if cfg.fasst else "naive"
    with trace.span("partition.sample_edge_sets", phase="plan", mu_s=mu_s,
                    method=method):
        return sample_edge_sets(g, x, mu_s, seed=cfg.seed, model=cfg.model,
                                method=method), method


def _partition_for_x(gs: Graph, x: np.ndarray, mu_v: int, mu_s: int,
                     cfg: DistributedConfig, plan=None) -> Partition2D:
    """The per-X partition of dst-sorted ``gs``: the edge sets ``x``'s own
    FASST chunks sample, planned (unless ``plan`` is given) and bucketed
    for this call alone."""
    sampled, method = _sample_edge_sets(gs, x, mu_s, cfg)
    if plan is None:
        plan = plan_partition(gs, mu_v, mu_s=mu_s, strategy=cfg.partition,
                              seed=cfg.seed, model=cfg.model, method=method,
                              sampled=sampled)
    metrics.counter("mesh.partitions_built").inc()
    return build_partition_2d(gs, x, mu_v, mu_s, seed=cfg.seed, method=method,
                              model=cfg.model, plan=plan, pad_mode=cfg.pad_mode,
                              sampled=sampled)


def _bucket_arrays(part: Partition2D) -> list:
    """The bucket arrays in the bodies' argument order: ten fields (the
    propagate five, then the cascade five), each one array per ring step."""
    return [step for field in (part.p_h, part.p_w, part.p_r, part.p_t,
                               part.p_l, part.c_h, part.c_w, part.c_r,
                               part.c_t, part.c_l) for step in field]


@dataclasses.dataclass
class _MeshPartition:
    """The partition of one (graph, config, mesh, register count), fixed by
    the graph: the sim shards' value ranges, their edge sets bucketed once
    and placed on the mesh, and the jitted programs built on its shapes."""

    graph: Graph                 # the dst-sorted graph it was built from
    part: Partition2D            # x_shards of the call that built it
    ranges: np.ndarray           # uint32[mu_s, 2]
    owned: jax.Array             # owned_ids, placed on the mesh
    buckets: tuple               # _bucket_arrays, placed on the mesh
    programs: dict = dataclasses.field(default_factory=dict)

    def program(self, key, make):
        if key not in self.programs:
            self.programs[key] = make()
        return self.programs[key]


def _same_graph(a: Graph, b: Graph) -> bool:
    return a is b or (a.n == b.n and a.n_pad == b.n_pad
                      and a.m_real == b.m_real and a.m == b.m
                      and np.array_equal(a.dst, b.dst)
                      and np.array_equal(a.src, b.src)
                      and np.array_equal(a.weight, b.weight))


class PartitionCache:
    """The mesh path's per-graph partitions (the ``mesh`` backend holds
    one). An entry is found by the graph object it was built for, or by a
    graph equal to its dst-sorted one (the build path is handed a fresh
    sorted copy), and is dropped with that graph object, or past ``SIZE``
    entries, oldest first."""

    SIZE = 4

    def __init__(self):
        self._tokens = itertools.count()
        self._entries: "collections.OrderedDict[int, tuple]" = (
            collections.OrderedDict())

    def find(self, g: Graph, key) -> Optional[_MeshPartition]:
        for token, (k, source, entry) in self._entries.items():
            if k == key and (source() is g or _same_graph(g, entry.graph)):
                self._entries.move_to_end(token)
                return entry
        return None

    def add(self, g: Graph, key, entry: _MeshPartition) -> None:
        token = next(self._tokens)
        self._entries[token] = (key, weakref.ref(g), entry)
        weakref.finalize(g, self._entries.pop, token, None)
        while len(self._entries) > self.SIZE:
            self._entries.popitem(last=False)


def _fixed_partition(g: Graph, mesh, cfg: DistributedConfig, x: np.ndarray,
                     mu_v: int, mu_s: int, partitions: PartitionCache):
    """The partition of (``g``, ``cfg``, ``mesh``, ``len(x)``) fixed by the
    graph, built and placed on the mesh on first demand under the
    ``mesh.partition`` span. Returns (partition, built in this call); the
    partition is None for a model without a range test."""
    key = (cfg, mesh, x.shape[0])
    found = partitions.find(g, key)
    if found is not None:
        return found, False
    if resolve_model(cfg.model).predicate is not fused_predicate:
        return None, False
    method = "fasst" if cfg.fasst else "naive"
    with trace.span("mesh.partition", phase="plan", mu_v=mu_v,
                    mu_s=mu_s) as sp:
        gs = g.sorted_by_dst()
        with trace.span("partition.sample_edge_sets", phase="plan",
                        mu_s=mu_s, method=f"{method}-ranges"):
            sampled = sample_edge_sets_in_ranges(
                gs, x, mu_s, seed=cfg.seed, model=cfg.model, method=method)
        if sampled is None:
            return None, False
        plan = plan_partition(gs, mu_v, mu_s=mu_s, strategy=cfg.partition,
                              seed=cfg.seed, model=cfg.model, method=method,
                              sampled=sampled)
        part = build_partition_2d(gs, x, mu_v, mu_s, seed=cfg.seed,
                                  method=method, model=cfg.model, plan=plan,
                                  pad_mode=cfg.pad_mode, sampled=sampled)
        sim_spec = _sim_spec(cfg.sim_axes)
        bucket = NamedSharding(mesh, P(cfg.vertex_axis, sim_spec, None))
        arrays = _bucket_arrays(part)
        owned, buckets = sp.sync(jax.device_put(
            (part.owned_ids, arrays),
            (NamedSharding(mesh, P(cfg.vertex_axis, None)),
             [bucket] * len(arrays))))
        sp.annotate(bucket_slots=part.bucket_slots,
                    bucket_pad_share=part.bucket_pad_share,
                    ring_bytes_per_sweep=part.comm_bytes_per_sweep)
    metrics.counter("mesh.partitions_built").inc()
    entry = _MeshPartition(graph=gs, part=part, ranges=sampled.ranges,
                           owned=owned, buckets=tuple(buckets))
    partitions.add(g, key, entry)
    return entry, True


def _partition_and_args(g: Graph, mesh, cfg: DistributedConfig,
                        x: np.ndarray, plan, mu_v: int, mu_s: int,
                        partitions: Optional[PartitionCache], kind: tuple,
                        make_program):
    """The partition, the program and the arguments of one mesh call.

    An X whose shards fall inside the fixed value ranges runs on the
    graph's resident partition and its program of ``kind``, built once;
    any other X (or a model without a range test) builds its own
    partition and program for this call and is counted in
    ``mesh.partition_fallbacks``. An explicit ``plan`` takes the per-X
    build too, uncounted. Without ``partitions`` the fixed partition
    serves this call alone. Returns (part, fn, args, job span flags)."""
    method = "fasst" if cfg.fasst else "naive"
    x_shards, _ = partition_samples(x, mu_s, method=method)
    fixed, built = (None, False)
    if plan is None:
        fixed, built = _fixed_partition(g, mesh, cfg, x, mu_v, mu_s,
                                        partitions or PartitionCache())
    if fixed is not None and in_ranges(x_shards, fixed.ranges):
        part = dataclasses.replace(fixed.part, x_shards=x_shards)
        fn = fixed.program(kind, lambda: make_program(part))
        args = [x_shards, fixed.owned, *fixed.buckets]
        return part, fn, args, {"partition_reused": int(not built),
                                "partition_fallback": 0}
    if plan is None:
        metrics.counter("mesh.partition_fallbacks").inc()
    gs = fixed.graph if fixed is not None else g.sorted_by_dst()
    part = _partition_for_x(gs, x, mu_v, mu_s, cfg, plan)
    args = [part.x_shards, part.owned_ids, *_bucket_arrays(part)]
    return part, make_program(part), args, {
        "partition_reused": 0, "partition_fallback": int(plan is None)}


def _job_program(part: Partition2D, k: int, mesh, cfg: DistributedConfig):
    """The jitted shard_map of the full Alg. 4 loop on ``part``'s shapes."""
    maker = _make_distributed_fn(
        part, k=k, vertex_axis=cfg.vertex_axis, sim_axes=tuple(cfg.sim_axes),
        estimator=cfg.estimator, rebuild_threshold=cfg.rebuild_threshold,
        max_prop=cfg.max_propagate_iters, max_casc=cfg.max_cascade_iters,
        seed=cfg.seed, schedule=cfg.schedule, local_sweeps=cfg.local_sweeps,
        fuse_sweeps=cfg.fuse_sweeps,
        predicate=resolve_model(cfg.model).predicate)
    sim_spec = _sim_spec(cfg.sim_axes)
    bucket_spec = P(cfg.vertex_axis, sim_spec, None)
    in_specs = ((P(sim_spec, None), P(cfg.vertex_axis, None))
                + (bucket_spec,) * (10 * part.mu_v))
    metrics.counter("mesh.programs_built").inc()
    return jax.jit(jax.shard_map(
        maker(mesh), mesh=mesh, in_specs=in_specs, out_specs=(P(),) * 7,
        check_vma=False))


def _find_seeds_distributed(g: Graph, k: int, mesh,
                            config: Optional[DistributedConfig] = None,
                            x: Optional[np.ndarray] = None, plan=None,
                            partitions: Optional[PartitionCache] = None):
    """shard_map Alg. 4 driver (the ``mesh`` runtime backend's body).
    Returns (InfluenceResult, Partition2D).

    Seeds/estimates come back in original vertex ids for every
    ``cfg.partition`` strategy (the relabeling is un-permuted on device via
    ``owned_ids``). ``plan`` overrides the ``cfg.partition``-derived
    :class:`PartitionPlan` (results are plan-invariant either way). The
    ``mesh.find_seeds`` span covers the whole call, a partition built in it
    included, and carries the job's sweep counters, compiles,
    ``bucket_pad_share``, ``partition_reused`` and ``partition_fallback``.
    """
    cfg = config or DistributedConfig()
    mu_v = mesh.shape[cfg.vertex_axis]
    mu_s = math.prod(mesh.shape[ax] for ax in cfg.sim_axes)
    compiles0 = metrics.counter("jit.compiles").value
    with trace.span("mesh.find_seeds", phase="select", k=k, mu_v=mu_v,
                    mu_s=mu_s, schedule=cfg.schedule) as sp:
        if x is None:
            x = make_x_vector(cfg.num_registers, seed=cfg.seed)
        x = np.asarray(x, dtype=np.uint32)
        part, fn, args, flags = _partition_and_args(
            g, mesh, cfg, x, plan, mu_v, mu_s, partitions, ("job", k),
            lambda p: _job_program(p, k, mesh, cfg))
        t0 = perf_counter()
        seeds, gains, scores, rebuilds, build_iters, casc, reb = (
            jax.device_get(fn(*args)))
        _publish_mesh_profile(part, phase="select", sweeps=int(build_iters),
                              wall_s=perf_counter() - t0)
        res = InfluenceResult(
            seeds=seeds, est_gains=gains, scores=scores, rebuilds=rebuilds,
            propagate_iters=int(build_iters),
            x=np.sort(x) if cfg.fasst else x,
            cascade_iters=casc, rebuild_iters=reb)
        annotate_job(sp, res, compiles0,
                     bucket_pad_share=part.bucket_pad_share, **flags)
    return res, part


def find_seeds_distributed(g: Graph, k: int, mesh,
                           config: Optional[DistributedConfig] = None,
                           x: Optional[np.ndarray] = None):
    """Deprecated entry point — prefer the unified runtime facade::

        from repro.runtime import InfluenceSession, RunSpec
        InfluenceSession(g, RunSpec.from_config(config), mesh=mesh).find_seeds(k)

    Kept as a thin shim through the ``mesh`` backend; results are
    bit-identical to the historical direct call (golden-tested). Returns
    (InfluenceResult, Partition2D) like before."""
    from repro.runtime import run, warn_deprecated
    from repro.runtime.spec import RunSpec

    warn_deprecated("repro.core.distributed.find_seeds_distributed",
                    "repro.runtime.InfluenceSession.find_seeds")
    spec = RunSpec.from_config(config or DistributedConfig(), backend="mesh")
    report = run(g, k, spec, x=x, mesh=mesh)
    return report.result, report.partition


# ---------------------------------------------------------------------------
# Build-only shard_map path (store banks on a mesh)
# ---------------------------------------------------------------------------


def _make_build_matrix_fn(part: Partition2D, *, vertex_axis: str,
                          sim_axes: Sequence[str], max_prop: int, seed: int,
                          schedule: str = "ring", local_sweeps: int = 0,
                          fuse_sweeps: bool = False,
                          predicate=None, reg_offset: int = 0):
    """Returns the shard_map body running only Alg. 4 lines 3-6 (fill +
    propagate-to-fixpoint) and handing back each shard's register block.

    The sweep/fixpoint machinery mirrors ``_make_distributed_fn`` (its
    device twin is the full loop); ``reg_offset`` offsets the register hash
    slots so sample-space store banks concatenate bit-identically to one
    monolithic build (same contract as ``ops.sketch_fill``).
    """
    mu_v = part.mu_v
    j_loc, n_real = part.j_loc, part.n
    pred = predicate if predicate is not None else fused_predicate

    def ring_sweep(m_loc, bh, bw, br, bt, bl, x_loc):
        acc = m_loc
        if schedule == "allgather" and mu_v > 1:
            blocks = jax.lax.all_gather(m_loc, vertex_axis)
            me = jax.lax.axis_index(vertex_axis)
            for kk in range(mu_v):
                if bh[kk].shape[0] == 0:
                    continue
                owner = jax.lax.rem(me + kk, mu_v)
                acc = _bucket_sweep_propagate(acc, blocks[owner], bh[kk], bw[kk],
                                              br[kk], bt[kk], x_loc, bl[kk], pred)
        else:
            block = m_loc
            for kk in range(mu_v):
                if bh[kk].shape[0]:
                    acc = _bucket_sweep_propagate(acc, block, bh[kk], bw[kk],
                                                  br[kk], bt[kk], x_loc, bl[kk],
                                                  pred)
                if kk + 1 < mu_v:
                    perm = [(i, (i - 1) % mu_v) for i in range(mu_v)]
                    block = jax.lax.ppermute(block, vertex_axis, perm)
        return jnp.where(m_loc == VISITED, m_loc, acc)

    def local_sweep(m_loc, bh, bw, br, bt, bl, x_loc):
        acc = m_loc
        if bh[0].shape[0]:
            acc = _bucket_sweep_propagate(acc, m_loc, bh[0], bw[0], br[0],
                                          bt[0], x_loc, bl[0], pred)
        return jnp.where(m_loc == VISITED, m_loc, acc)

    def body(x_loc, owned, *bufs):
        def grp(i):
            return tuple(bufs[i * mu_v + kk][0, 0] for kk in range(mu_v))

        ph, pw, pr, pt, pl = grp(0), grp(1), grp(2), grp(3), grp(4)
        x_loc = x_loc[0]
        owned = owned[0]
        all_axes = (vertex_axis, *sim_axes)
        si = jnp.int32(0)
        mult = 1
        for ax in reversed(sim_axes):
            si = si + jax.lax.axis_index(ax) * mult
            mult *= _axis_sizes[ax]
        valid_row = owned < n_real
        from repro.core.sampling import register_hash

        j_ids = (jnp.arange(j_loc, dtype=jnp.uint32)[None, :]
                 + (si * j_loc + reg_offset).astype(jnp.uint32))
        fresh = jax.lax.clz(register_hash(owned.astype(jnp.uint32)[:, None],
                                          j_ids, seed=seed))
        m_loc = jnp.where(valid_row[:, None], fresh.astype(jnp.int8),
                          jnp.int8(VISITED))

        def cond(c):
            return jnp.logical_and(c[1], c[2] < max_prop)

        def loop_body(c):
            m_cur, _, it = c
            if fuse_sweeps and local_sweeps:
                m_cur = jax.lax.fori_loop(
                    0, local_sweeps,
                    lambda _i, mm: local_sweep(mm, ph, pw, pr, pt, pl, x_loc),
                    m_cur)
            else:
                for _ in range(local_sweeps):
                    m_cur = local_sweep(m_cur, ph, pw, pr, pt, pl, x_loc)
            m_new = ring_sweep(m_cur, ph, pw, pr, pt, pl, x_loc)
            changed = jax.lax.psum(jnp.any(m_new != m_cur).astype(jnp.int32),
                                   all_axes) > 0
            return m_new, changed, it + 1

        m_loc, _, iters = jax.lax.while_loop(
            cond, loop_body, (m_loc, jnp.bool_(True), jnp.int32(0)))
        return m_loc, iters

    _axis_sizes: dict[str, int] = {}

    def with_sizes(mesh):
        for ax in (vertex_axis, *sim_axes):
            _axis_sizes[ax] = mesh.shape[ax]
        return body

    return with_sizes


def _build_program(part: Partition2D, mesh, cfg: DistributedConfig,
                   reg_offset: int):
    """The jitted shard_map of fill + propagate-to-fixpoint on ``part``'s
    shapes; it takes the propagate half of :func:`_bucket_arrays`."""
    maker = _make_build_matrix_fn(
        part, vertex_axis=cfg.vertex_axis, sim_axes=tuple(cfg.sim_axes),
        max_prop=cfg.max_propagate_iters, seed=cfg.seed, schedule=cfg.schedule,
        local_sweeps=cfg.local_sweeps, fuse_sweeps=cfg.fuse_sweeps,
        predicate=resolve_model(cfg.model).predicate, reg_offset=reg_offset)
    sim_spec = _sim_spec(cfg.sim_axes)
    bucket_spec = P(cfg.vertex_axis, sim_spec, None)
    in_specs = ((P(sim_spec, None), P(cfg.vertex_axis, None))
                + (bucket_spec,) * (5 * part.mu_v))
    out_specs = (P(cfg.vertex_axis, sim_spec), P())
    metrics.counter("mesh.programs_built").inc()
    return jax.jit(jax.shard_map(
        maker(mesh), mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))


def build_matrix_distributed(g: Graph, mesh,
                             config: Optional[DistributedConfig] = None,
                             x: Optional[np.ndarray] = None, *,
                             reg_offset: int = 0, plan=None,
                             partitions: Optional[PartitionCache] = None):
    """Alg. 4 lines 3-6 under shard_map: fill + propagate-to-fixpoint on the
    2-D partition, gathered back to the canonical layout.

    Expects ``g`` dst-sorted and ``x`` canonical (sorted when FASST) — the
    normalized inputs the store/backend layer already holds. Runs on the
    graph's resident partition and program where ``x`` falls inside its
    value ranges, as the job does. Returns ``(matrix int8[g.n_pad,
    len(x)], iters, Partition2D)`` where ``matrix`` rows are in
    original-id order (the plan's relabeling is un-permuted on host),
    bit-identical to the single-device ``build_sketch_matrix``.
    """
    cfg = config or DistributedConfig()
    mu_v = mesh.shape[cfg.vertex_axis]
    mu_s = math.prod(mesh.shape[ax] for ax in cfg.sim_axes)
    if x is None:
        x = make_x_vector(cfg.num_registers, seed=cfg.seed)
        if cfg.fasst:
            x = np.sort(x)
    x = np.asarray(x, dtype=np.uint32)
    part, fn, args, flags = _partition_and_args(
        g, mesh, cfg, x, plan, mu_v, mu_s, partitions, ("build", reg_offset),
        lambda p: _build_program(p, mesh, cfg, reg_offset))
    t0 = perf_counter()
    with trace.span("mesh.build_matrix", phase="build", mu_v=mu_v,
                    mu_s=mu_s, reg_offset=reg_offset, **flags) as sp:
        m_planned, iters = sp.sync(fn(*args[:2 + 5 * mu_v]))
        # un-permute planned rows back to original-id (canonical) order
        m_canon = sp.sync(m_planned[jnp.asarray(part.plan.perm[: g.n_pad])])
    _publish_mesh_profile(part, phase="build", sweeps=int(iters),
                          wall_s=perf_counter() - t0)
    return m_canon, int(iters), part


# ---------------------------------------------------------------------------
# Device-resident serving paths: warm seed rounds + shard-restricted repair
# on a plan-order matrix that already lives on the mesh (docs/service.md,
# "Sharded serving"). Both consume the matrix through an
# ``in_specs=P(vertex_axis, sim_spec)`` slot, so a bank placed with
# ``NamedSharding`` is used where it sits — no gather to host order.
# ---------------------------------------------------------------------------


def _sim_spec(sim_axes):
    """PartitionSpec entry for the sample-space dim: the axis tuple, a bare
    axis name, or None for a vertex-only serving mesh."""
    if len(sim_axes) > 1:
        return tuple(sim_axes)
    return sim_axes[0] if sim_axes else None


def _partition_for_plan(g: Graph, mesh, cfg: DistributedConfig,
                        x: np.ndarray, plan):
    """Build the bucket arrays of ``plan`` for ``mesh``'s shard grid."""
    mu_v = mesh.shape[cfg.vertex_axis]
    mu_s = math.prod(mesh.shape[ax] for ax in cfg.sim_axes)
    if plan.mu_v != mu_v:
        raise ValueError(f"plan has mu_v={plan.mu_v} but the mesh's "
                         f"{cfg.vertex_axis!r} axis is {mu_v}-way")
    x = np.asarray(x, dtype=np.uint32)
    sampled, method = _sample_edge_sets(g, x, mu_s, cfg)
    return build_partition_2d(g, x, mu_v, mu_s, seed=cfg.seed, method=method,
                              model=cfg.model, plan=plan,
                              pad_mode=cfg.pad_mode, sampled=sampled)


def find_seeds_warm_distributed(g: Graph, k: int, mesh,
                                config: Optional[DistributedConfig],
                                planned_m, plan,
                                x: np.ndarray, *,
                                part: Optional[Partition2D] = None
                                ) -> InfluenceResult:
    """Warm-start Alg. 4 under shard_map: skip fill + propagate and run the
    K seed rounds from an already-propagated plan-order register matrix
    (``StoreEntry.planned_matrix()``) sharded — or shardable — over the
    mesh's vertex axis. The round program is the warm twin of
    ``_find_seeds_distributed``'s, so seeds are bit-identical to the
    single-device ``find_seeds_warm`` (backend-invariance contract).
    ``part`` passes a pre-built bucket partition of the same (graph, plan,
    x) in — the O(m · mu_s) host preprocessing is the dominant warm-serving
    cost, so repeat callers (the store's device TopKSeeds path) cache it
    against the entry version instead of paying it per query.
    """
    cfg = config or DistributedConfig()
    if part is None:
        part = _partition_for_plan(g, mesh, cfg, x, plan)
    x = np.asarray(x, dtype=np.uint32)
    maker = _make_distributed_fn(
        part, k=k, vertex_axis=cfg.vertex_axis, sim_axes=tuple(cfg.sim_axes),
        estimator=cfg.estimator, rebuild_threshold=cfg.rebuild_threshold,
        max_prop=cfg.max_propagate_iters, max_casc=cfg.max_cascade_iters,
        seed=cfg.seed, schedule=cfg.schedule, local_sweeps=cfg.local_sweeps,
        fuse_sweeps=cfg.fuse_sweeps,
        predicate=resolve_model(cfg.model).predicate, warm=True)
    body = maker(mesh)

    sim_spec = _sim_spec(cfg.sim_axes)
    bucket_spec = P(cfg.vertex_axis, sim_spec, None)
    in_specs = ((P(cfg.vertex_axis, sim_spec), P(sim_spec, None),
                 P(cfg.vertex_axis, None)) + (bucket_spec,) * (10 * part.mu_v))
    out_specs = (P(),) * 7
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))
    args = [jnp.asarray(planned_m, jnp.int8), jnp.asarray(part.x_shards),
            jnp.asarray(part.owned_ids)]
    for field in (part.p_h, part.p_w, part.p_r, part.p_t, part.p_l,
                  part.c_h, part.c_w, part.c_r, part.c_t, part.c_l):
        for step in field:
            args.append(jnp.asarray(step))
    with trace.span("mesh.warm_rounds", phase="select", k=k,
                    mu_v=part.mu_v, mu_s=part.mu_s) as sp:
        seeds, gains, scores, rebuilds, _, casc, reb = sp.sync(fn(*args))
    return InfluenceResult(
        seeds=np.asarray(seeds), est_gains=np.asarray(gains),
        scores=np.asarray(scores), rebuilds=np.asarray(rebuilds),
        propagate_iters=0, x=np.sort(x) if cfg.fasst else x,
        cascade_iters=np.asarray(casc), rebuild_iters=np.asarray(reb))


def _make_repair_fn(part: Partition2D, *, vertex_axis: str,
                    sim_axes: Sequence[str], max_prop: int, predicate=None):
    """Returns the shard_map body of the frontier-restricted repair — the
    device twin of ``partition.serial._RingState.sweep_propagate_restricted``.

    Carries a replicated ``dirty`` bool[mu_v] vector: each ring-step merge
    is applied only when the block being read belongs to a dirty shard
    (sound, because starting from a lower bound of the fixpoint, changes can
    only originate at rows the dirtied shards feed); the per-sweep changed
    flags (one psum over the sim axes + one all_gather over the vertex axis)
    become the next sweep's dirty set, so the repair widens exactly where
    changes actually spread and stops when nothing moved.
    """
    mu_v = part.mu_v
    pred = predicate if predicate is not None else fused_predicate

    def body(m_in, dirty0, x_loc, *bufs):
        def grp(i):
            return tuple(bufs[i * mu_v + kk][0, 0] for kk in range(mu_v))

        ph, pw, pr, pt, pl = grp(0), grp(1), grp(2), grp(3), grp(4)
        x_loc = x_loc[0]
        me = jax.lax.axis_index(vertex_axis)

        def cond(c):
            _, dirty, _, it = c
            return jnp.logical_and(jnp.any(dirty), it < max_prop)

        def sweep(c):
            m_cur, dirty, swept, it = c
            swept = jnp.logical_or(swept, dirty)
            acc = m_cur
            block = m_cur
            for kk in range(mu_v):
                if ph[kk].shape[0]:
                    owner = jax.lax.rem(me + kk, mu_v)
                    merged = _bucket_sweep_propagate(
                        acc, block, ph[kk], pw[kk], pr[kk], pt[kk], x_loc,
                        pl[kk], pred)
                    acc = jnp.where(dirty[owner], merged, acc)
                if kk + 1 < mu_v:
                    perm = [(i, (i - 1) % mu_v) for i in range(mu_v)]
                    block = jax.lax.ppermute(block, vertex_axis, perm)
            m_new = jnp.where(m_cur == VISITED, m_cur, acc)
            changed = jnp.any(m_new != m_cur).astype(jnp.int32)
            if sim_axes:   # OR across this vertex shard's sim siblings
                changed = jax.lax.psum(changed, tuple(sim_axes))
            dirty_new = jax.lax.all_gather(changed > 0, vertex_axis)
            return m_new, dirty_new, swept, it + 1

        zeros = jnp.zeros((mu_v,), jnp.bool_)
        m_out, _, swept, sweeps = jax.lax.while_loop(
            cond, sweep, (m_in, dirty0, zeros, jnp.int32(0)))
        return m_out, swept, sweeps

    return body


def repair_plan_shards_distributed(g: Graph, mesh,
                                   config: Optional[DistributedConfig],
                                   x: np.ndarray, planned_m, plan, touched):
    """Shard-restricted monotone insertion repair under shard_map — the
    ``mesh`` backend's twin of ``partition.serial.repair_plan_shards``.

    ``planned_m`` is the pre-delta plan-order matrix (device-resident banks
    pass straight through; the in_spec matches their ``NamedSharding`` row
    placement so no cross-host gather happens), ``g`` the post-delta
    dst-sorted graph, ``touched`` the plan shards the delta's endpoints land
    in. Returns ``(planned_matrix, sweeps, shards_swept)`` with the matrix
    still sharded over the vertex axis, bit-identical to a full rebuild (and
    to the serial repair) by fixpoint uniqueness above a sound lower bound.
    """
    cfg = config or DistributedConfig()
    part = _partition_for_plan(g, mesh, cfg, x, plan)
    body = _make_repair_fn(
        part, vertex_axis=cfg.vertex_axis, sim_axes=tuple(cfg.sim_axes),
        max_prop=cfg.max_propagate_iters,
        predicate=resolve_model(cfg.model).predicate)

    sim_spec = _sim_spec(cfg.sim_axes)
    bucket_spec = P(cfg.vertex_axis, sim_spec, None)
    in_specs = ((P(cfg.vertex_axis, sim_spec), P(None), P(sim_spec, None))
                + (bucket_spec,) * (5 * part.mu_v))
    out_specs = (P(cfg.vertex_axis, sim_spec), P(), P())
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))
    dirty0 = np.zeros(part.mu_v, dtype=bool)
    dirty0[np.asarray(list(touched), dtype=np.int64)] = True
    args = [jnp.asarray(planned_m, jnp.int8), jnp.asarray(dirty0),
            jnp.asarray(part.x_shards)]
    for field in (part.p_h, part.p_w, part.p_r, part.p_t, part.p_l):
        for step in field:
            args.append(jnp.asarray(step))
    with trace.span("mesh.repair", phase="repair",
                    touched=len(tuple(touched))) as sp:
        m_out, swept, sweeps = sp.sync(fn(*args))
        sp.annotate(sweeps=int(sweeps))
    swept_t = tuple(int(v) for v in np.nonzero(np.asarray(swept))[0])
    return m_out, int(sweeps), swept_t
