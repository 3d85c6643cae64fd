"""Service throughput: cold-build vs warm-store serving on an RMAT graph,
host-order vs device-resident (shard-local) serving side by side.

    PYTHONPATH=src python -m benchmarks.service_throughput [--scale 14] \
        [--backend auto|host|mesh] [--mu-v 8]

Emits the repo's standard ``name,us_per_call,derived`` CSV rows (the
benchmarks/run.py schema) plus one ``service.json`` row whose derived field
is the full JSON stats blob. Two acceptance metrics:

  * ``service.speedup`` — amortized per-query cost of the 2nd..Nth warm
    query vs repeated cold runs (the PR 1 store claim);
  * ``service.device_vs_host`` — amortized per-query cost of the
    gather-to-host path vs shard-local serving off mesh-placed row blocks
    (> 1 means device residency wins; needs a multi-device mesh, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

``--out-json BENCH_service.json`` records both for the CI trend gate
(``benchmarks/run.py --fast`` + ``benchmarks/trend.py``).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import emit
from repro.core.difuser import DiFuserConfig
from repro.graphs.structs import GraphDelta
from repro.obs import flight
from repro.runtime import RunSpec, run as run_im
from repro.graphs import rmat_graph
from repro.launch.serve_im import make_workload
from repro.service import (AsyncInfluenceEngine, InfluenceEngine, SketchStore,
                           TopKSeeds, apply_delta, summarize_latencies)


def _serve_workload(engine, key, g, num_queries, k, seed):
    """Push the standard mixed workload through the engine; returns
    (wall_s, stats). Warms the jit caches with one TopKSeeds first and
    clears the memo so the timed top-k queries execute for real."""
    warm = engine(key, TopKSeeds(k)).value
    engine.clear_topk_memo()
    for q in make_workload(g.n, num_queries, k=k, seed=seed):
        engine.submit(key, q)
    t0 = time.perf_counter()
    results = engine.run()
    wall_s = time.perf_counter() - t0
    return warm, wall_s, summarize_latencies(results)


def _device_placement_ok(mu_v: int):
    """(ok, reason) for shard-local serving on this host."""
    import jax

    if len(jax.devices()) < mu_v:
        return False, (f"{mu_v} row blocks need {mu_v} devices, have "
                       f"{len(jax.devices())} (export XLA_FLAGS="
                       f"--xla_force_host_platform_device_count={mu_v})")
    return True, ""


def _same_value(a, b) -> bool:
    """Bit-identity of two QueryResult values across query classes."""
    if isinstance(a, dict):
        return (np.array_equal(a["est"], b["est"])
                and np.array_equal(a["max_register"], b["max_register"]))
    if isinstance(a, float):
        return a == b
    return np.array_equal(np.asarray(a.seeds), np.asarray(b.seeds))


def _warm_engine(engine, keys, n, k):
    """Compile every query-class jit and clear the top-k memo so both the
    async and sync open-loop runs measure warm serving, not compilation."""
    for key in keys:
        for q in make_workload(n, 8, k=k, seed=1234):
            engine.submit(key, q)
        engine.run()
    engine.clear_topk_memo()


def async_open_loop(scale: int = 11, *, registers: int = 128, k: int = 8,
                    qps: float = 2000.0, duration_s: float = 0.75,
                    deadline_ms: float = 50.0, seed: int = 0) -> dict:
    """The mixed open-loop acceptance workload: two resident graphs with
    interleaved query classes under Poisson arrivals, one mid-run
    ``apply_delta`` and one cold build, served by the async engine and then
    replayed (same arrival schedule, same routing) through the blocking
    synchronous engine. Reports sustained qps + e2e p50/p95/p99 for both,
    verifies every result bit-identical, and counts query batches whose
    flight-ring spans overlap the build/repair spans (the
    serve-N-while-N+1-builds evidence).

    Arrivals are precomputed (open loop: the schedule does not slow down
    when the server falls behind); while the mid-run mutations are in
    flight, graph-2 traffic is routed to graph 1 — recorded per request so
    the sync replay serves the *identical* sequence and per-query results
    are comparable without racing the swap.
    """
    g1 = rmat_graph(scale, edge_factor=8, seed=seed, setting="w1")
    g2 = rmat_graph(scale, edge_factor=8, seed=seed + 1, setting="w1")
    g3 = rmat_graph(scale, edge_factor=8, seed=seed + 2,
                    setting="w1")   # the mid-run cold admit
    cfg = DiFuserConfig(num_registers=registers, seed=seed)
    rng = np.random.default_rng(seed + 100)
    arrive = np.cumsum(rng.exponential(1.0 / qps,
                                       size=max(int(qps * duration_s * 2), 8)))
    arrive = arrive[arrive < duration_s]
    num = len(arrive)
    queries = make_workload(g1.n, num, k=k, seed=seed + 7)
    wants = rng.integers(0, 2, size=num)       # 0 -> g1, 1 -> g2
    delta = GraphDelta.make(add=(rng.integers(0, g2.n, 64),
                                 rng.integers(0, g2.n, 64)))
    mut_at = max(num // 3, 1)

    # ---- async run -------------------------------------------------------
    def run_async():
        engine_a = InfluenceEngine(SketchStore())
        ka = [engine_a.register(g1, cfg), engine_a.register(g2, cfg)]
        aeng = AsyncInfluenceEngine(engine_a, deadline_ms=deadline_ms)
        flight.get_flight_recorder().clear()
        routed = np.array(wants)               # actual routing, for replay
        futures = [None] * num
        mut_futs = None
        t0 = time.perf_counter()
        for i in range(num):
            lag = t0 + arrive[i] - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            if i == mut_at:
                # barrier: queries admitted before the delta must resolve
                # against the pre-delta version in both runs — drain first
                aeng.drain()
                mut_futs = (aeng.apply_delta_async(ka[1], delta),
                            aeng.register_async(g3, cfg))
            if (routed[i] == 1 and mut_futs is not None
                    and not all(f.done() for f in mut_futs)):
                routed[i] = 0   # g2 is mid-swap: serve its traffic from g1
            futures[i] = aeng.submit(ka[routed[i]], queries[i])
        aeng.drain()
        wall = time.perf_counter() - t0
        results = [f.result() for f in futures]
        report = mut_futs[0].result()
        assert mut_futs[1].result() in aeng.store
        summary = aeng.admission_summary()
        aeng.close()
        return routed, results, report, summary, wall

    # pass 1 warms the jit cache with exactly the (batch, length) shapes the
    # micro-batcher produces (process-global cache — a steady-state server
    # never pays these compiles per query); pass 2 is the measurement
    run_async()
    routed, results_a, delta_report, admission, async_wall = run_async()

    # overlap evidence: query batches whose spans intersect a build/repair
    # span interval in the flight ring (serving continued during mutation)
    evs = flight.get_flight_recorder().events()
    mut_spans = [(e["ts_s"], e["ts_s"] + e["dur_s"]) for e in evs
                 if e["name"] in ("async.build", "async.repair",
                                  "async.rebuild")]
    qnames = ("engine.spread_batch", "engine.marginal_batch",
              "engine.probe_batch", "engine.topk_batch", "async.cross_spread")
    overlapped = sum(
        1 for e in evs if e["name"] in qnames
        and any(e["ts_s"] < hi and lo < e["ts_s"] + e["dur_s"]
                for lo, hi in mut_spans))

    # ---- sync replay: same arrivals, same routing, blocking server ------
    engine_s = InfluenceEngine(SketchStore())
    ks = [engine_s.register(g1, cfg), engine_s.register(g2, cfg)]
    _warm_engine(engine_s, ks, g1.n, k)
    results_s = [None] * num
    e2e_s = np.zeros(num)
    t0 = time.perf_counter()
    for i in range(num):
        lag = t0 + arrive[i] - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        if i == mut_at:                         # blocking repair + cold build
            apply_delta(engine_s.store, ks[1], delta)
            engine_s.register(g3, cfg)
        results_s[i] = engine_s(ks[routed[i]], queries[i])
        e2e_s[i] = time.perf_counter() - (t0 + arrive[i])
    sync_wall = time.perf_counter() - t0

    mismatches = sum(not _same_value(a.value, s.value)
                     for a, s in zip(results_a, results_s))
    pct = lambda xs, q: float(np.percentile(xs, q) * 1e3) if len(xs) else 0.0
    out = {
        "num_queries": num, "qps_target": qps, "duration_s": duration_s,
        "deadline_ms": deadline_ms,
        "sustained_qps": num / async_wall,
        "p50_ms": admission["e2e_p50_ms"],
        "p95_ms": admission["e2e_p95_ms"],
        "p99_ms": admission["e2e_p99_ms"],
        "deadline_miss_rate": admission["deadline_miss_rate"],
        "flushes": admission["flushes"],
        "cross_entry_batches": admission["cross_entry_batches"],
        "queue_depth_timeline": admission["queue_depth_timeline"][-256:],
        "overlapped_query_batches": overlapped,
        "mutation_spans": len(mut_spans),
        "delta_added": delta_report.added,
        "sync": {"sustained_qps": num / sync_wall,
                 "p50_ms": pct(e2e_s, 50), "p95_ms": pct(e2e_s, 95),
                 "p99_ms": pct(e2e_s, 99)},
        "speedup_vs_sync": sync_wall / async_wall,
        "mismatches": mismatches,
    }
    assert mismatches == 0, f"{mismatches} async/sync result mismatches"
    return out


def main(scale: int = 14, *, registers: int = 256, k: int = 10,
         num_queries: int = 200, seed: int = 0, backend: str = "auto",
         mu_v: int = 8, qps: float = 2000.0, duration_s: float = 0.75,
         out_json: str = "") -> dict:
    g = rmat_graph(scale, edge_factor=8, seed=seed, setting="w1")
    cfg = DiFuserConfig(num_registers=registers, seed=seed)

    # cold: what every query costs without the store (build + rounds)
    t0 = time.perf_counter()
    cold = run_im(g, k, RunSpec.from_config(cfg, backend="single")).result
    cold_s = time.perf_counter() - t0
    emit(f"service.cold_find_seeds.n{g.n}", cold_s * 1e6, cold.propagate_iters)

    store = SketchStore()
    engine = InfluenceEngine(store)
    t0 = time.perf_counter()
    key = engine.register(g, cfg)
    build_s = time.perf_counter() - t0
    emit(f"service.store_build.n{g.n}", build_s * 1e6,
         store.entry(key).build_iters)

    # ---- host-order serving (the single/serial fallback path) ----
    host_stats = device_stats = None
    device_skip = ""
    if backend != "mesh":
        warm, host_wall, host_stats = _serve_workload(
            engine, key, g, num_queries, k, seed + 7)
        assert np.array_equal(warm.seeds, cold.seeds), "warm/cold seed mismatch"
        host_amort = host_wall / num_queries
        emit(f"service.warm_query.n{g.n}", host_amort * 1e6,
             f"{host_stats['qps']:.0f}qps")
        emit(f"service.p50.n{g.n}", host_stats["p50_ms"] * 1e3, "")
        emit(f"service.p99.n{g.n}", host_stats["p99_ms"] * 1e3, "")
        emit(f"service.speedup.n{g.n}", host_amort * 1e6,
             f"{cold_s / host_amort:.1f}x")
        host_stats = {**host_stats, "wall_s": host_wall,
                      "amortized_s": host_amort,
                      "qps": num_queries / host_wall,
                      "speedup_vs_cold": cold_s / host_amort}

    # ---- device-resident serving (shard-local reductions on the mesh) ----
    if backend in ("auto", "mesh"):
        ok, why = _device_placement_ok(mu_v)
        if not ok:
            device_skip = why
            emit(f"service.device.n{g.n}", 0.0, f"skipped: {why}")
            if backend == "mesh":
                raise SystemExit(f"--backend mesh: {why}")
        else:
            from repro.launch.mesh import make_serving_mesh
            from repro.partition import plan_partition

            entry = store.entry(key)
            t0 = time.perf_counter()
            plan = plan_partition(entry.graph, mu_v, mu_s=1, x=entry.x,
                                  seed=seed, model=cfg.model)
            store.attach_plan(key, plan)
            entry.place_on_mesh(make_serving_mesh(mu_v))
            place_s = time.perf_counter() - t0
            emit(f"service.device_place.n{g.n}", place_s * 1e6,
                 f"{mu_v} row blocks")
            engine.clear_topk_memo()
            warm_d, dev_wall, device_stats = _serve_workload(
                engine, key, g, num_queries, k, seed + 7)
            assert np.array_equal(warm_d.seeds, cold.seeds), \
                "device warm/cold seed mismatch"
            dev_amort = dev_wall / num_queries
            emit(f"service.device.warm_query.n{g.n}", dev_amort * 1e6,
                 f"{device_stats['qps']:.0f}qps")
            emit(f"service.device.p50.n{g.n}",
                 device_stats["p50_ms"] * 1e3, "")
            emit(f"service.device.p99.n{g.n}",
                 device_stats["p99_ms"] * 1e3, "")
            device_stats = {**device_stats, "wall_s": dev_wall,
                            "amortized_s": dev_amort,
                            "qps": num_queries / dev_wall,
                            "speedup_vs_cold": cold_s / dev_amort,
                            "mu_v": mu_v, "place_s": place_s}
            if host_stats is not None:
                ratio = host_stats["amortized_s"] / dev_amort
                emit(f"service.device_vs_host.n{g.n}", dev_amort * 1e6,
                     f"{ratio:.2f}x")

    # ---- async open-loop serving (admission pipeline acceptance) ----
    async_stats = None
    if qps > 0 and duration_s > 0:
        async_stats = async_open_loop(
            max(scale - 2, 9), registers=max(registers // 2, 64), k=k,
            qps=qps, duration_s=duration_s, seed=seed)
        emit(f"service.async.sustained_qps.n{1 << max(scale - 2, 9)}",
             1e6 / max(async_stats["sustained_qps"], 1e-9),
             f"{async_stats['sustained_qps']:.0f}qps")
        emit(f"service.async.p99.n{1 << max(scale - 2, 9)}",
             async_stats["p99_ms"] * 1e3,
             f"miss={async_stats['deadline_miss_rate']:.1%}")
        emit(f"service.async.vs_sync.n{1 << max(scale - 2, 9)}",
             async_stats["p99_ms"] * 1e3,
             f"{async_stats['speedup_vs_sync']:.2f}x "
             f"overlap={async_stats['overlapped_query_batches']}")

    out = {"n": g.n, "m": g.m_real, "registers": registers, "k": k,
           "num_queries": num_queries, "cold_s": cold_s, "build_s": build_s,
           "host": host_stats, "device": device_stats, "async": async_stats,
           "device_skip": device_skip}
    if host_stats is not None:
        # the legacy top-level fields (older BENCH baselines / table tooling)
        out.update(wall_s=host_stats["wall_s"],
                   amortized_s=host_stats["amortized_s"],
                   speedup=host_stats["speedup_vs_cold"],
                   qps=host_stats["qps"])
    if host_stats is not None and device_stats is not None:
        out["device_vs_host"] = (host_stats["amortized_s"]
                                 / device_stats["amortized_s"])
    emit("service.json", (out.get("wall_s", 0.0)) * 1e6, json.dumps(out))
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        emit("service.out_json", 0.0, out_json)
    return out


if __name__ == "__main__":
    from repro.launch.common import add_obs_args, observe

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--registers", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "host", "mesh"],
                    help="auto: host rows + device rows when a mesh is "
                         "available; host/mesh: that path only")
    ap.add_argument("--mu-v", type=int, default=8,
                    help="row blocks (devices) of the serving mesh")
    ap.add_argument("--qps", type=float, default=2000.0,
                    help="open-loop Poisson arrival rate for the async "
                         "serving section (0 disables it); the default "
                         "saturates the sync baseline so the batching "
                         "advantage is measurable")
    ap.add_argument("--duration", type=float, default=0.75,
                    help="open-loop workload duration in seconds")
    ap.add_argument("--out-json", default="")
    add_obs_args(ap)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    with observe(args):
        main(args.scale, registers=args.registers, k=args.k,
             num_queries=args.queries, backend=args.backend, mu_v=args.mu_v,
             qps=args.qps, duration_s=args.duration,
             out_json=args.out_json)
