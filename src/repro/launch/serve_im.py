"""Influence serving driver: one sketch build amortized over a query stream.

    PYTHONPATH=src python -m repro serve --graph rmat:12 \
        --registers 512 --queries 1000 --topk 10

Builds the SketchStore index once (the cold cost) through the ``--backend``
of choice (repro.runtime — any registered backend can build the banks),
then pushes a mixed workload of TopKSeeds / SpreadEstimate / MarginalGain /
CoverageProbe requests through the batched InfluenceEngine and reports qps,
p50/p99, and the amortized per-query latency against the cold
``find_seeds`` cost.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch.common import add_common_im_args, make_graph, observe
from repro.service import (CoverageProbe, InfluenceEngine, MarginalGain,
                           SketchStore, SpreadEstimate, TopKSeeds,
                           summarize_latencies)


def make_workload(n: int, num_queries: int, *, k: int, seed: int,
                  mix=(0.05, 0.45, 0.35, 0.15)) -> list:
    """A mixed query stream: (topk, spread, marginal, probe) fractions."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice(4, size=num_queries, p=np.asarray(mix) / sum(mix))
    out = []
    for kind in kinds:
        if kind == 0:
            out.append(TopKSeeds(k))
        elif kind == 1:
            size = int(rng.integers(1, 9))
            out.append(SpreadEstimate(rng.integers(0, n, size)))
        elif kind == 2:
            size = int(rng.integers(0, 6))
            out.append(MarginalGain(int(rng.integers(0, n)),
                                    rng.integers(0, n, size)))
        else:
            out.append(CoverageProbe(rng.integers(0, n, int(rng.integers(1, 5)))))
    return out


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    add_common_im_args(ap, registers_default=512)
    ap.add_argument("--banks", type=int, default=1)
    ap.add_argument("--attach-plan", action="store_true",
                    help="attach a vertex-shard plan of the --partition "
                         "strategy to the index even for the default "
                         "'block' (a non-block --partition always attaches "
                         "one); the store then serves planned_matrix() row "
                         "blocks and deltas report the plan shards they "
                         "touch")
    ap.add_argument("--plan-shards", type=int, default=8,
                    help="vertex shards of the attached plan (and the row "
                         "blocks of a device-resident placement)")
    ap.add_argument("--residency", default="auto",
                    choices=["auto", "host", "device"],
                    help="where the index banks live for serving: 'device' "
                         "pins plan-order row blocks on a mesh "
                         "(shard-local query reductions); 'auto' follows "
                         "the resolved --backend (mesh -> device)")
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--topk", type=int, default=10, help="k for TopKSeeds queries")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--async", dest="serve_async", action="store_true",
                    help="serve through AsyncInfluenceEngine: futures + "
                         "deadline-driven micro-batching, builds/repairs "
                         "off the serving path (results bit-identical to "
                         "the synchronous engine)")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="per-query end-to-end SLO for --async (flush "
                         "window = deadline/4; misses are counted and "
                         "watchdogged)")
    ap.add_argument("--max-resident", type=float, default=0.0,
                    help="device budget in MB for --async multi-graph "
                         "tenancy (0 = unbounded); cost-aware eviction "
                         "keeps resident store bytes under it")
    ap.add_argument("--save", default="", help="persist the index npz here")
    args = ap.parse_args(argv)
    # --trace/--metrics wrap the whole serve run: build + query spans land
    # in the Chrome trace, the registry snapshot is written at exit
    with observe(args):
        return _run(args)


def _run(args) -> dict:
    from repro.runtime import InfluenceSession, RunSpec

    g = make_graph(args.graph, args.setting, args.seed)
    print(f"graph n={g.n:,} m={g.m_real:,} model={args.model}")
    # a sharded spec (mu_v = --plan-shards) only when device serving was
    # asked for — the default stays the historical single-device cold path
    wants_device = args.backend == "mesh" or args.residency == "device"
    spec = RunSpec(num_registers=args.registers, seed=args.seed,
                   model=args.model, backend=args.backend,
                   residency=args.residency,
                   mu_v=args.plan_shards if wants_device else 1, mu_s=1,
                   partition=args.partition if args.partition else "block",
                   tuning=args.tuning)
    sess = InfluenceSession(g, spec,
                            store=SketchStore(num_banks=args.banks, spec=spec))

    # cold reference: what every query would pay without the store
    t0 = time.perf_counter()
    cold = sess.find_seeds(args.topk)
    cold_s = time.perf_counter() - t0
    print(f"cold find_seeds [{sess.last_report.backend}]: {cold_s:.2f}s "
          f"(build fixpoint {cold.propagate_iters} sweeps)")

    store = sess.store
    engine = InfluenceEngine(store, max_batch=args.max_batch)
    key = engine.register(g, spec.difuser_config())
    entry = sess.entry()   # routes spec.residency: mesh serving pins blocks
    print(f"store build: {entry.build_time_s:.2f}s "
          f"({entry.num_banks} bank(s), {entry.build_iters} sweeps)")

    if entry.residency == "device":
        pm = entry.planned_matrix()
        shard_bytes = pm.shape[0] // entry.plan.mu_v * pm.shape[1]
        print(f"device-resident: {entry.plan.mu_v} row blocks x "
              f"{shard_bytes} B on mesh {dict(entry.mesh.shape)} "
              f"(serving {entry.serving_backend})")
    elif args.attach_plan or args.partition != "block":
        from repro.partition import plan_partition

        plan = plan_partition(entry.graph, args.plan_shards, mu_s=1,
                              strategy=args.partition, x=entry.x,
                              seed=args.seed, model=args.model)
        store.attach_plan(key, plan)
        pm = entry.planned_matrix()
        shard_bytes = pm.shape[0] // plan.mu_v * pm.shape[1]
        print(f"plan attached: {plan.predicted.describe()} "
              f"({plan.mu_v} row blocks x {shard_bytes} B resident)")

    workload = make_workload(g.n, args.queries, k=args.topk, seed=args.seed + 7)
    admission = {}
    if getattr(args, "serve_async", False):
        from repro.service import AsyncInfluenceEngine

        import dataclasses as _dc
        spec = _dc.replace(spec, serve_async=True,
                           deadline_ms=args.deadline_ms,
                           max_resident_mb=args.max_resident)
        aeng = AsyncInfluenceEngine(engine, deadline_ms=args.deadline_ms,
                                    max_resident_mb=args.max_resident,
                                    spec=spec)
        t0 = time.perf_counter()
        futures = [aeng.submit(key, q) for q in workload]
        aeng.drain()
        wall_s = time.perf_counter() - t0
        results = [f.result() for f in futures]
        admission = aeng.admission_summary()
        print(f"async: deadline {args.deadline_ms:.0f}ms  "
              f"e2e p99 {admission['e2e_p99_ms']:.2f}ms  "
              f"miss rate {admission['deadline_miss_rate']:.1%}  "
              f"flushes {admission['flushes']}")
        aeng.close()
    else:
        for q in workload:
            engine.submit(key, q)
        t0 = time.perf_counter()
        results = engine.run()
        wall_s = time.perf_counter() - t0
    stats = summarize_latencies(results)

    amortized = wall_s / max(args.queries, 1)
    speedup = cold_s / amortized if amortized > 0 else float("inf")
    print(f"served {args.queries} queries in {wall_s:.2f}s "
          f"({args.queries / wall_s:.0f} qps)")
    print(f"p50 {stats['p50_ms']:.2f}ms  p99 {stats['p99_ms']:.2f}ms  "
          f"topk cache hits {stats['cache_hits']}")
    print(f"amortized {amortized * 1e3:.2f}ms/query vs cold {cold_s:.2f}s "
          f"-> {speedup:.0f}x")

    if args.save:
        store.save(args.save, key)
        print(f"index saved to {args.save}")
    # **stats first: its amortized-based "qps" (memo hits cost 0s) must not
    # clobber the wall-clock qps reported here and printed above
    out = {**stats, "cold_s": cold_s, "build_s": entry.build_time_s,
           "wall_s": wall_s, "qps": args.queries / wall_s,
           "amortized_s": amortized, "speedup": speedup,
           "backend": sess.last_report.backend,
           "residency": entry.residency,
           "serving": entry.serving_backend}
    if admission:
        admission.pop("queue_depth_timeline", None)
        out["admission"] = admission
    return out


if __name__ == "__main__":
    from repro.launch.common import enable_compile_cache

    enable_compile_cache()
    run()
