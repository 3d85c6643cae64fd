"""The measuring half of :mod:`repro.tune`: time candidates, pick winners.

Measurement goes through the same instruments the rest of the repo trusts:
each candidate runs inside a ``trace.span(..., timed=True)`` (device output
synced *inside* the span, so queueing is not mistaken for execution), and
its achieved GB/s is computed from its best time. Winners are the candidate
with the best min-of-N wall time; every trial also lands in the ``tune.*`` metric
namespace so the perf report can show what the tuner saw.

``resolve_spec`` is the one hook the runtime backends call: with
``spec.tuning="off"`` it returns the spec untouched (zero overhead, exact
historical behaviour); ``"cached"`` applies persisted winners and falls
back deterministically to the spec's own values on a miss; ``"auto"``
measures on a miss against the *actual* graph, persists the winner, then
applies it. All of it is performance-only — the kernels are
chunk/tile/schedule-invariant by contract, so seeds and matrices are
bit-identical across every mode (tier-1 property-tested).

The ring-schedule family (``bucket_propagate``) closes the PR-7 loop:
candidates come from :func:`repro.tune.config.schedule_candidates`, which
reads the planner's :class:`PlanStats` and the last published
:class:`MeasuredProfile` instead of brute-forcing the grid, and the probe
run itself publishes a fresh measured profile.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

from repro.obs import metrics, shardprof, trace
from repro.tune.cache import TuningCache, cache_key, default_cache
from repro.tune.config import (KernelConfig, default_config,
                               fused_candidates, schedule_candidates,
                               spec_overrides, sweep_candidates)
from repro.utils import roofline

#: timing repetitions per candidate (min-of-N; first call also warms jit)
TRIALS = 3

#: canonical prologue depth the fused_sweep family is timed at: the
#: candidate grid compares "this many back-to-back sweeps" fused vs looped,
#: matching the local_sweeps values schedule_candidates ever offers (1-2)
FUSED_PROBE_SWEEPS = 2


def _time_grid(fns, labels, *, family: str, nbytes: int,
               trials: int = TRIALS, warmup: int = 1):
    """min-of-N wall seconds per candidate, trials interleaved round-robin.

    Every trial runs inside a ``trace.span(..., timed=True)`` with the
    candidate's output declared via ``sp.sync`` — device work lands inside
    the measurement — so tuning trials show up as their own Perfetto lanes
    next to the workload they tuned. Interleaving matters: warm-up drift (allocator, caches,
    CPU frequency) is monotone within a process, so timing candidates
    back-to-back in blocks would systematically favor whichever ran last.
    Round-robin rounds spread the drift evenly; min-per-candidate then
    compares like with like. Returns ``[(seconds, gbps), ...]``.
    """
    for fn in fns:
        for _ in range(max(warmup, 0)):
            fn()
    best = [math.inf] * len(fns)
    for _ in range(max(trials, 1)):
        for i, fn in enumerate(fns):
            with trace.span("tune.trial", phase="other", timed=True,
                            family=family, candidate=labels[i]) as sp:
                sp.sync(fn())
            best[i] = min(best[i], sp.duration_s)
    return [(s, (nbytes / s / 1e9) if s > 0 and nbytes > 0 else 0.0)
            for s in best]


def _publish(family: str, backend: str, label: str, seconds: float,
             gbps: float) -> None:
    metrics.counter("tune.trials", family=family, backend=backend).inc()
    metrics.gauge("tune.candidate_us", family=family, backend=backend,
                  candidate=label).set(seconds * 1e6)
    if gbps:
        metrics.gauge("tune.candidate_gbps", family=family, backend=backend,
                      candidate=label).set(round(gbps, 3))


def _measurement_record(family: str, backend: str, results) -> dict:
    """The cache-persisted evidence: per-candidate timings + the default/
    winner comparison the report surfaces. ``results`` is a list of
    ``(config, label, seconds, gbps)`` with the *first* entry the default."""
    default_s = results[0][2]
    best = min(results, key=lambda r: r[2])
    peaks = roofline.device_peaks()
    return {
        "family": family, "backend": backend,
        "default_us": round(default_s * 1e6, 3),
        "tuned_us": round(best[2] * 1e6, 3),
        "tuned_gbps": round(best[3], 3),
        "speedup": round(default_s / best[2], 4) if best[2] > 0 else 1.0,
        # fraction of the HBM roof, only for a chip in roofline.PEAKS
        "frac_of_roof": (round(best[3] * 1e9 / peaks.hbm_bw, 6)
                         if peaks is not None else None),
        "candidates": [
            {"label": lab, "config": cfg.to_dict(),
             "us": round(s * 1e6, 3), "gbps": round(g, 3)}
            for cfg, lab, s, g in results],
    }


# ---------------------------------------------------------------------------
# Family measurement: single-device sweeps
# ---------------------------------------------------------------------------


def _sweep_operands(g, spec):
    """Device operands + a filled register matrix for the sweep families."""
    import jax.numpy as jnp

    from repro.core import difuser as _difuser
    from repro.kernels import ops

    cfg = spec.difuser_config()
    g2, x = _difuser.normalize_inputs(g, cfg)
    src, dst, h, lo, thr = _difuser.edge_operands(g2, cfg)
    xj = jnp.asarray(np.asarray(x, np.uint32))
    m = ops.sketch_fill(jnp.zeros((g2.n_pad, xj.shape[0]), jnp.int8),
                        seed=cfg.seed)
    pred = _difuser.resolve_model(cfg.model).predicate
    return cfg, (src, dst, h, lo, thr), xj, m, pred


def measure_sweep_family(g, spec, family: str, *,
                         backend: str = "single",
                         candidates=None) -> Tuple[KernelConfig, dict]:
    """Time one sweep of ``family`` per candidate on the actual graph.

    Returns ``(winning config, measurement record)``. The default config is
    always candidate 0, so the record's ``speedup`` is tuned-vs-today.
    """
    import jax

    from repro.kernels import ops

    cfg, (src, dst, h, lo, thr), xj, m, pred = _sweep_operands(g, spec)
    num_edges = int(src.shape[0])
    if candidates is None:
        if family == "fused_sample" and cfg.impl == "ref":
            candidates = ()          # ref fused_sample has no tiling knob
        else:
            candidates = sweep_candidates(num_edges, impl=cfg.impl,
                                          default_chunk=cfg.edge_chunk)
    cands = [default_config(family)] + [c for c in candidates
                                        if c != default_config(family)]
    nbytes = shardprof.bucket_bytes(num_edges, int(xj.shape[0]))
    if family == "cascade_step":
        m = m.at[0].set(-1)          # a visited row so the sweep has work

    def make_fn(c: KernelConfig):
        # jit each candidate closure (chunk/tiles baked in as statics) —
        # the production drivers run these sweeps jitted, so un-jitted
        # timings would rank dispatch overhead, not kernels
        chunk = c.edge_block or cfg.edge_chunk
        kw = dict(seed=cfg.seed, impl=cfg.impl, predicate=pred,
                  edge_chunk=chunk, edge_block=c.edge_block,
                  reg_tile=c.reg_tile)
        if family == "sketch_propagate":
            call = jax.jit(lambda m_, h_, lo_: ops.propagate_sweep(
                m_, src, dst, thr, xj, h=h_, lo=lo_, **kw))
        elif family == "cascade_step":
            # the jobs build the pull layout once and sweep it many times:
            # time the sweep, not the build
            layout = None if cfg.impl != "ref" else ops.cascade_layout(
                src, dst, thr, n_pad=m.shape[0], seed=cfg.seed,
                edge_chunk=chunk, h=h, lo=lo)
            call = jax.jit(lambda m_, h_, lo_: ops.cascade_sweep(
                m_, src, dst, thr, xj, h=h_, lo=lo_, layout=layout, **kw))
        elif family == "fused_sample":   # no scan chunk — tiles only
            kw.pop("edge_chunk")
            call = jax.jit(lambda m_, h_, lo_: ops.fused_sample(
                src, dst, thr, xj, h=h_, lo=lo_, **kw))
        else:
            raise ValueError(f"unknown sweep family {family!r}")
        return lambda: jax.block_until_ready(call(m, h, lo))

    labels = [f"eb{c.edge_block or 0}.rt{c.reg_tile or 0}" for c in cands]
    timings = _time_grid([make_fn(c) for c in cands], labels,
                         family=family, nbytes=nbytes)
    results = []
    for c, label, (sec, gbps) in zip(cands, labels, timings):
        _publish(family, backend, label, sec, gbps)
        results.append((c, label, sec, gbps))
    record = _measurement_record(family, backend, results)
    winner = min(results, key=lambda r: r[2])[0]
    metrics.gauge("tune.speedup", family=family,
                  backend=backend).set(record["speedup"])
    return winner, record


# ---------------------------------------------------------------------------
# Family measurement: fused multi-sweep kernel (fused_sweep)
# ---------------------------------------------------------------------------


def measure_fused_family(g, spec, *, backend: str = "serial",
                         candidates=None) -> Tuple[KernelConfig, dict]:
    """Time ``FUSED_PROBE_SWEEPS`` back-to-back propagate sweeps per
    candidate on the actual graph.

    Candidate 0 is today's behaviour — one jitted ``propagate_sweep`` launch
    per sweep, the register matrix materialized between launches (the
    ``sweep_local()`` / mesh-prologue re-launch pattern). Fused candidates
    run the same sweeps through :func:`ops.fused_sweep` in one launch at a
    given lane fill; candidates are seeded model-aware from the register
    width and the last measured profile (:func:`fused_candidates`).
    """
    import jax

    from repro.kernels import ops

    cfg, (src, dst, h, lo, thr), xj, m, pred = _sweep_operands(g, spec)
    num_regs = int(xj.shape[0])
    if candidates is None:
        candidates = fused_candidates(None, shardprof.last_profile(),
                                      model=cfg.model, num_regs=num_regs)
    base = default_config("fused_sweep")         # fuse_sweeps=False: the loop
    cands = [base] + [c for c in candidates if c != base]
    sweeps = FUSED_PROBE_SWEEPS
    nbytes = shardprof.bucket_bytes(int(src.shape[0]), num_regs) * sweeps
    kw = dict(seed=cfg.seed, impl=cfg.impl, predicate=pred,
              edge_chunk=cfg.edge_chunk)

    def make_fn(c: KernelConfig):
        if not c.fuse_sweeps:
            step = jax.jit(lambda m_, h_, lo_: ops.propagate_sweep(
                m_, src, dst, thr, xj, h=h_, lo=lo_, **kw))

            def loop():
                mm = m
                for _ in range(sweeps):
                    mm = step(mm, h, lo)
                return jax.block_until_ready(mm)

            return loop
        call = jax.jit(lambda m_, h_, lo_: ops.fused_sweep(
            m_, src, dst, thr, xj, h=h_, lo=lo_, num_sweeps=sweeps,
            lane_fill=c.lane_fill, **kw))
        return lambda: jax.block_until_ready(call(m, h, lo))

    labels = [f"fused.lf{c.lane_fill or 0}" if c.fuse_sweeps else "loop"
              for c in cands]
    timings = _time_grid([make_fn(c) for c in cands], labels,
                         family="fused_sweep", nbytes=nbytes)
    results = []
    for c, label, (sec, gbps) in zip(cands, labels, timings):
        _publish("fused_sweep", backend, label, sec, gbps)
        results.append((c, label, sec, gbps))
    record = _measurement_record("fused_sweep", backend, results)
    winner = min(results, key=lambda r: r[2])[0]
    metrics.gauge("tune.speedup", family="fused_sweep",
                  backend=backend).set(record["speedup"])
    return winner, record


# ---------------------------------------------------------------------------
# Family measurement: ring schedule (bucket_propagate)
# ---------------------------------------------------------------------------


def measure_schedule_family(g, spec, *, backend: str = "serial",
                            candidates=None) -> Tuple[KernelConfig, dict]:
    """Time the ring build per ``(local_sweeps, pad_mode)`` candidate.

    The probe is the serial-ring executor — the one place ring-step time is
    physically separable (its shard_map device twin runs the identical
    bucket schedule, so the ranking transfers). Candidates are seeded from
    the planner's predicted :class:`PlanStats` and the last published
    measured profile (:func:`schedule_candidates`); the default
    ``(local_sweeps=0, spec.pad_mode)`` is always candidate 0.
    """
    from repro.core.sampling import make_x_vector
    from repro.partition.plan import plan_partition
    from repro.partition.serial import build_matrix_ring_serial

    cfg = spec.difuser_config()
    g2 = g.sorted_by_dst()
    mu_v, mu_s = max(spec.mu_v, 1), max(spec.mu_s, 1)
    x = np.sort(np.asarray(make_x_vector(cfg.num_registers, seed=cfg.seed),
                           dtype=np.uint32))
    plan = plan_partition(g2, mu_v, mu_s=mu_s, strategy=spec.partition,
                          seed=cfg.seed, model=cfg.model)
    if candidates is None:
        candidates = schedule_candidates(plan.predicted,
                                         shardprof.last_profile(),
                                         pad_mode=spec.pad_mode)
    base = KernelConfig(local_sweeps=0, pad_mode=spec.pad_mode)
    cands = [base] + [c for c in candidates if c != base]
    nbytes = shardprof.bucket_bytes(int(g2.m), int(cfg.num_registers))

    def make_fn(c: KernelConfig):
        # pad_mode changes the bucket layout, so each candidate re-buckets;
        # the plan (and therefore results) is shared across candidates
        return lambda: build_matrix_ring_serial(
            g2, cfg, x, mu_v=mu_v, mu_s=mu_s, strategy=spec.partition,
            plan=plan, pad_mode=c.pad_mode, local_sweeps=c.local_sweeps)

    labels = [f"ls{c.local_sweeps}.{c.pad_mode}" for c in cands]
    timings = _time_grid([make_fn(c) for c in cands], labels,
                         family="bucket_propagate", nbytes=nbytes,
                         trials=2, warmup=0)
    results = []
    for c, label, (sec, gbps) in zip(cands, labels, timings):
        _publish("bucket_propagate", backend, label, sec, gbps)
        results.append((c, label, sec, gbps))
    record = _measurement_record("bucket_propagate", backend, results)
    winner = min(results, key=lambda r: r[2])[0]
    metrics.gauge("tune.speedup", family="bucket_propagate",
                  backend=backend).set(record["speedup"])
    return winner, record


# ---------------------------------------------------------------------------
# The runtime hook
# ---------------------------------------------------------------------------


def families_for(spec, backend: str) -> Tuple[str, ...]:
    """Which kernel families a backend's execution actually dispatches."""
    if backend == "single":
        return ("sketch_propagate", "cascade_step")
    if backend in ("serial", "mesh") and spec.num_shards > 1:
        # bucket_propagate picks (local_sweeps, pad_mode); fused_sweep then
        # decides whether those prologue sweeps run fused and at what lane
        # fill (disjoint spec fields, so the override merge is order-free)
        return ("bucket_propagate", "fused_sweep")
    return ()


def _measure_family(family: str, g, spec, backend: str):
    if family in ("sketch_propagate", "cascade_step", "fused_sample"):
        return measure_sweep_family(g, spec, family, backend=backend)
    if family == "bucket_propagate":
        return measure_schedule_family(g, spec, backend=backend)
    if family == "fused_sweep":
        return measure_fused_family(g, spec, backend=backend)
    raise ValueError(f"unknown kernel family {family!r}")


def resolve_spec(g, spec, *, backend: str,
                 cache: Optional[TuningCache] = None):
    """Apply the spec's ``tuning`` mode: return a spec whose tile/schedule
    fields carry the measured winners for this (graph, backend) workload.

    ``"off"`` (default) returns ``spec`` unchanged. ``"cached"`` overlays
    cache winners; a miss deterministically keeps the spec's own values.
    ``"auto"`` measures misses on the actual graph, persists the winners,
    then overlays. Results are invariant either way — only wall time moves.
    """
    mode = getattr(spec, "tuning", "off")
    if mode == "off" or g is None:
        return spec
    if mode not in ("cached", "auto"):
        raise ValueError(f"unknown tuning mode {mode!r} "
                         "(expected 'off' | 'cached' | 'auto')")
    cache = cache if cache is not None else default_cache()
    overrides: Dict[str, object] = {}
    for family in families_for(spec, backend):
        key = cache_key(family, backend=backend, impl=spec.impl,
                        model=spec.model, num_edges=int(g.m))
        cfg = cache.lookup(key)
        if cfg is None:
            metrics.counter("tune.cache_miss", family=family,
                            backend=backend).inc()
            if mode != "auto":
                continue                       # deterministic fallback
            with trace.span("tune.measure", phase="plan", family=family,
                            backend=backend, timed=True):
                cfg, record = _measure_family(family, g, spec, backend)
            cache.put(key, cfg, measurement=record)
            cache.save()
        else:
            metrics.counter("tune.cache_hit", family=family,
                            backend=backend).inc()
        overrides.update(spec_overrides(family, cfg, spec))
    # never let a tuned override change the tuning mode itself
    return spec.with_(**overrides) if overrides else spec


def autotune(g, spec, *, backend: str = "single",
             families: Optional[Tuple[str, ...]] = None,
             cache: Optional[TuningCache] = None) -> Dict[str, dict]:
    """Measure every ``families`` entry now and persist the winners.

    The explicit entry point benchmarks and CI use (``resolve_spec`` with
    ``tuning="auto"`` does the same lazily). Returns family -> measurement
    record (default vs tuned time, GB/s, per-candidate trials).
    """
    cache = cache if cache is not None else default_cache()
    out: Dict[str, dict] = {}
    for family in families or families_for(spec, backend):
        winner, record = _measure_family(family, g, spec, backend)
        key = cache_key(family, backend=backend, impl=spec.impl,
                        model=spec.model, num_edges=int(g.m))
        cache.put(key, winner, measurement=record)
        out[family] = record
    cache.save()
    return out
