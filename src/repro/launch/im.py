"""DiFuseR driver — the paper's workload end-to-end.

    PYTHONPATH=src python -m repro im --graph rmat:14 --setting 0.1 \
        --k 50 --registers 1024 --devices 8 --validate

Execution is selected by ``--backend`` (repro.runtime registry):
``auto`` resolves to the jitted single-device driver for an unsharded run
and to the ``shard_map`` mesh runtime when ``--devices > 1`` (export
XLA_FLAGS=--xla_force_host_platform_device_count=N for a host mesh). With
too few devices it runs the serial-ring executor on CPU and raises on an
accelerator — all three return bit-identical seed sets.
"""
from __future__ import annotations

import argparse
import time

from repro.baselines import influence_score, ris_find_seeds
from repro.launch.common import (add_common_im_args, make_graph,  # noqa: F401
                                 observe)
# make_graph is re-exported: serve_im and the benchmarks import it from here


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    add_common_im_args(ap)
    ap.add_argument("--k", type=int, default=50)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--schedule", default="ring", choices=["ring", "allgather"])
    ap.add_argument("--mu-v", type=int, default=0,
                    help="vertex shards of the (data, model) mesh "
                         "(0 = historical default: 2 when --devices is even)")
    ap.add_argument("--no-fasst", action="store_true")
    ap.add_argument("--validate", action="store_true", help="score seeds with the MC oracle")
    ap.add_argument("--ris", action="store_true", help="also run the RIS/IMM baseline")
    args = ap.parse_args(argv)
    with observe(args):
        return _run(args)


def _run(args) -> dict:
    from repro.runtime import RunSpec, run as run_im

    g = make_graph(args.graph, args.setting, args.seed)
    print(f"graph n={g.n:,} m={g.m_real:,}")
    out = {}

    # shard grid: --devices keeps its historical meaning (mesh size); an
    # explicit sharded backend without --devices gets the 2x2 test grid
    if args.devices > 1:
        mu_v = args.mu_v if args.mu_v > 0 else (2 if args.devices % 2 == 0 else 1)
        if args.devices % mu_v != 0:
            raise SystemExit(f"--devices {args.devices} not divisible by mu_v={mu_v}")
        mu_s = args.devices // mu_v
    elif args.backend in ("serial", "mesh"):
        mu_v = args.mu_v if args.mu_v > 0 else 2
        mu_s = 2
    else:
        mu_v = mu_s = 1

    spec = RunSpec(
        num_registers=args.registers, seed=args.seed, model=args.model,
        sort_x=not args.no_fasst, fasst=not args.no_fasst,
        backend=args.backend, mu_v=mu_v, mu_s=mu_s,
        partition=args.partition, schedule=args.schedule,
        tuning=args.tuning)

    t0 = time.time()
    report = run_im(g, args.k, spec)
    res = report.result
    out["backend"] = report.backend
    if report.partition is not None:
        part = report.partition
        out["max_shard_edges"] = int(part.edge_counts.max())
        stats = part.stats()
        out["edge_imbalance"] = stats.edge_imbalance
        print(f"backend={report.backend} partition: {stats.describe()}")
    else:
        print(f"backend={report.backend}")
        if args.partition != "block":
            # no shard grid requested, but the planner's cost model still
            # answers "how would this graph shard" — print it for free
            from repro.partition import plan_partition

            plan = plan_partition(g.sorted_by_dst(), 8, mu_s=1,
                                  strategy=args.partition, x=res.x,
                                  seed=args.seed, model=args.model)
            out["predicted_edge_imbalance"] = plan.predicted.edge_imbalance
            print(f"partition plan (hypothetical 8-shard): "
                  f"{plan.predicted.describe()}")
    dt = time.time() - t0
    out.update(time_s=round(dt, 2), seeds=res.seeds.tolist(),
               difuser_score=float(res.scores[-1]), rebuilds=int(res.rebuilds.sum()))
    print(f"difuser: {dt:.2f}s influence(est)={res.scores[-1]:.1f} "
          f"rebuilds={int(res.rebuilds.sum())}/{args.k}")

    if args.validate:
        oracle = influence_score(g, res.seeds, num_sims=100, rng_seed=args.seed + 99,
                                 model=args.model)
        out["oracle_score"] = oracle
        print(f"oracle(difuser seeds) = {oracle:.1f}")
    if args.ris:
        t0 = time.time()
        rs, rest = ris_find_seeds(g, args.k, num_rr_sets=4000, rng_seed=args.seed)
        rt = time.time() - t0
        roracle = influence_score(g, rs, num_sims=100, rng_seed=args.seed + 99)
        out.update(ris_time_s=round(rt, 2), ris_oracle=roracle)
        print(f"ris/imm: {rt:.2f}s oracle={roracle:.1f} "
              f"(quality ratio {out.get('oracle_score', roracle)/max(roracle,1e-9):.3f})")
    return out


if __name__ == "__main__":
    from repro.launch.common import enable_compile_cache

    enable_compile_cache()
    run()
