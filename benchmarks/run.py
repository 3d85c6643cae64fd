"""Benchmark harness: one module per paper table + kernel micro + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only tableX] [--fast] \
        [--baseline-dir bench-baseline]

Prints ``name,us_per_call,derived`` CSV rows (assignment contract). With a
baseline directory (``--baseline-dir`` or the ``BENCH_BASELINE_DIR`` env
var — CI points it at the previous run's artifact), the fresh
``BENCH_runtime.json`` / ``BENCH_service.json`` records are compared via
:mod:`benchmarks.trend` and the process exits non-zero on a >20% seeds/sec
or qps regression.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true", help="smaller graphs (CI)")
    ap.add_argument("--baseline-dir",
                    default=os.environ.get("BENCH_BASELINE_DIR", ""),
                    help="previous CI artifact dir with BENCH_*.json to "
                         "trend against (empty: no gate)")
    ap.add_argument("--regression-threshold", type=float, default=0.2)
    args = ap.parse_args()

    from benchmarks import (kernels_micro, model_zoo, partition_balance,
                            roofline_report, runtime_bench, service_throughput,
                            table8_scaling, table9_comm,
                            table34_quality_speed, table567_fasst)

    jobs = {
        "runtime": lambda: runtime_bench.main(
            scale=9 if args.fast else 10,
            registers=128 if args.fast else 256,
            k=4 if args.fast else 8,
            out_json="BENCH_runtime.json"),
        "partition": lambda: partition_balance.main(
            scale=9 if args.fast else 11,
            registers=128 if args.fast else 256,
            k=2 if args.fast else 4),
        "service": lambda: service_throughput.main(
            scale=11 if args.fast else 14,
            num_queries=50 if args.fast else 200,
            mu_v=4 if args.fast else 8,
            out_json="BENCH_service.json"),
        "model_zoo": lambda: model_zoo.main(
            scale=9 if args.fast else None,          # None -> preset graphs
            k=8 if args.fast else None,
            registers=256 if args.fast else None,
            num_sims=40 if args.fast else 120),
        "table34": lambda: table34_quality_speed.main(scale=9 if args.fast else 10),
        "table567": lambda: table567_fasst.main(scale=10 if args.fast else 11),
        "table8": lambda: table8_scaling.main(scale=10 if args.fast else 11),
        "table9": lambda: table9_comm.main(scale=10 if args.fast else 11),
        # register-heavy shape: the scan-chunk working set (chunk x R) is
        # what the tuner actually gets to move, so give it a workload where
        # the default chunk is measurably cache-hostile
        "kernels": lambda: kernels_micro.main(
            scale=10 if args.fast else 12,
            registers=2048 if args.fast else 512,
            out_json="BENCH_kernels.json"),
        "roofline": roofline_report.main,
    }
    # --fast (the CI sweep) records the run's spans + metrics as artifacts
    # next to the BENCH_*.json records: BENCH_trace.json opens in Perfetto,
    # BENCH_metrics.jsonl is the registry snapshot
    recorder = None
    if args.fast:
        from repro.obs import trace as obs_trace

        recorder = obs_trace.get_recorder().start()

    # reuse the previous run's tuning cache (CI artifact) so jobs running
    # with tuning="cached" skip re-measuring; the kernels job refreshes the
    # winners and the updated cache is uploaded with this run's artifacts
    if args.baseline_dir:
        import shutil

        base_cache = os.path.join(args.baseline_dir, "TUNE_cache.json")
        if os.path.exists(base_cache) and not os.path.exists("TUNE_cache.json"):
            shutil.copy(base_cache, "TUNE_cache.json")
            print("tune.cache,0,reused baseline TUNE_cache.json")

    print("name,us_per_call,derived")
    failed = []
    for name, job in jobs.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            job()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail at exit
            traceback.print_exc()
            print(f"{name}.ERROR,0,{type(e).__name__}: {e}", file=sys.stdout)
            failed.append(name)
        print(f"{name}.total_s,{(time.time()-t0)*1e6:.0f},done")

    if recorder is not None:
        from repro.obs import metrics as obs_metrics
        from repro.obs import report as obs_report

        recorder.stop()
        n_spans = recorder.save_chrome_trace("BENCH_trace.json")
        n_series = obs_metrics.registry().write_jsonl("BENCH_metrics.jsonl")
        print(f"obs.trace,0,{n_spans} spans -> BENCH_trace.json")
        print(f"obs.metrics,0,{n_series} series -> BENCH_metrics.jsonl")
        # the self-contained HTML perf report CI uploads with the BENCH
        # artifacts: trajectory tiles, phase breakdown, measured shard skew
        rpt = obs_report.write_report_from_artifacts(
            "BENCH_report.html", recorder=recorder,
            generated=time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()))
        print(f"obs.report,0,{rpt}")

    if args.baseline_dir:
        from benchmarks import trend

        regressed = trend.compare(args.baseline_dir,
                                  threshold=args.regression_threshold)
        if regressed:
            print(f"trend gate: {regressed} metric(s) regressed > "
                  f"{args.regression_threshold:.0%} vs {args.baseline_dir}",
                  file=sys.stderr)
            sys.exit(1)
    if failed:
        print(f"{len(failed)} job(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
