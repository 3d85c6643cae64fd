"""Shared CLI surface of the IM launchers.

``launch/im.py`` and ``launch/serve_im.py`` historically copy-pasted the
``--graph/--setting/--model/--partition/--seed`` group and let the help
strings drift apart; this module is the single copy. It also owns
``make_graph`` (the graph-spec parser both drivers and the benchmarks use)
and the ``--backend`` flag that selects a :mod:`repro.runtime` backend
instead of hand-rolled mesh setup.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from pathlib import Path

import jax

from repro.graphs import barabasi_albert_graph, erdos_renyi_graph, rmat_graph
from repro.graphs.io import load_snap_edgelist
from repro.obs import metrics, trace


#: the checkout's own persistent compilation cache: a fixed path, because
#: the path is part of the cache key — a directory that moves never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already
    taken it as its cache directory and no other is set here; otherwise
    the cache lives in :data:`COMPILE_CACHE_DIR`. Every program is cached,
    however quick its compile. Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


@trace.traced("launch.make_graph", phase="other")
def make_graph(spec: str, setting: str, seed: int):
    """Parse ``--graph`` specs: rmat:<scale> | rmat-skew:<scale> | er:<n> |
    ba:<n> | snap:<path>."""
    kind, _, arg = spec.partition(":")
    if kind == "rmat":
        return rmat_graph(int(arg), setting=setting, seed=seed)
    if kind == "rmat-skew":
        # heavier Kronecker tail + raw (unpermuted) ids: hubs cluster at low
        # ids — the regime the partition planners exist for
        return rmat_graph(int(arg), edge_factor=8, a=0.65, b=0.15, c=0.15,
                          setting=setting, seed=seed, permute_ids=False)
    if kind == "er":
        return erdos_renyi_graph(int(arg), setting=setting, seed=seed)
    if kind == "ba":
        return barabasi_albert_graph(int(arg), setting=setting, seed=seed)
    if kind == "snap":
        return load_snap_edgelist(arg, setting=setting, seed=seed)
    raise ValueError(spec)


def add_common_im_args(ap: argparse.ArgumentParser, *,
                       graph_default: str = "rmat:12",
                       registers_default: int = 1024) -> argparse.ArgumentParser:
    """The shared ``--graph/--setting/--model/--partition/--seed`` group
    (plus ``--registers`` and ``--backend``) of every IM driver."""
    grp = ap.add_argument_group("workload (shared IM driver surface)")
    grp.add_argument("--graph", default=graph_default,
                     help="rmat:<scale>|rmat-skew:<scale>|er:<n>|ba:<n>|snap:<path>")
    grp.add_argument("--setting", default="0.1",
                     help="0.005|0.01|0.1|N0.05|U0.1|wc (paper §5)")
    grp.add_argument("--model", default="wc",
                     help="diffusion model spec: wc|ic[:p]|lt|dic[:lambda] "
                          "(repro.diffusion registry; wc = backward-"
                          "compatible default)")
    grp.add_argument("--partition", default="block",
                     help="vertex-assignment strategy for the 2-D partition: "
                          "block|degree|edge|random (repro.partition "
                          "registry; seed sets are identical across "
                          "strategies)")
    grp.add_argument("--registers", type=int, default=registers_default)
    grp.add_argument("--backend", default="auto",
                     help="execution backend: auto|single|serial|mesh "
                          "(repro.runtime registry; 'auto' picks single for "
                          "an unsharded run and mesh for a sharded one — "
                          "serial stands in for a missing mesh on CPU only)")
    add_tuning_arg(grp)
    grp.add_argument("--seed", type=int, default=0)
    add_obs_args(ap)
    return ap


def add_tuning_arg(ap) -> None:
    """The shared ``--tuning`` flag (``RunSpec.tuning`` / :mod:`repro.tune`).

    Accepts an ``ArgumentParser`` or an argument group; drivers that build
    their own workload flags (dryrun, runtime_bench) call this directly."""
    ap.add_argument("--tuning", default="off",
                    choices=("off", "cached", "auto"),
                    help="measured kernel tuning (repro.tune): off = "
                         "hard-coded defaults; cached = apply TUNE_cache."
                         "json winners (a miss falls back to the defaults); "
                         "auto = measure misses on the actual graph and "
                         "persist winners. Performance-only: results are "
                         "bit-identical across modes")


def add_obs_args(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Just the ``--trace``/``--metrics`` observability group — for drivers
    (benchmarks, dryrun) that have their own workload flags but share the
    :func:`observe` context manager."""
    obs = ap.add_argument_group("observability (repro.obs)")
    obs.add_argument("--trace", default=None, metavar="OUT.json",
                     help="record spans and write Chrome trace-event JSON "
                          "(open in ui.perfetto.dev; one lane per phase)")
    obs.add_argument("--metrics", default=None, metavar="OUT.jsonl",
                     help="write a JSONL metrics snapshot (counters/gauges/"
                          "histograms) at exit")
    return ap


@contextlib.contextmanager
def observe(args):
    """Wrap a driver run in the observability surface ``--trace`` /
    ``--metrics`` request: start the span recorder when a trace path is
    given, and at exit write the Chrome trace + metrics snapshot and print a
    one-line span-coverage summary (top-level span seconds / wall seconds —
    the "spans account for the run" acceptance number). No flags -> exact
    historical behaviour (recorder stays off, nothing written)."""
    trace_path = getattr(args, "trace", None)
    metrics_path = getattr(args, "metrics", None)
    rec = trace.get_recorder()
    if trace_path:
        rec.start()
    t0 = time.perf_counter()
    try:
        yield rec
    finally:
        wall = time.perf_counter() - t0
        if trace_path:
            rec.stop()
            n = rec.save_chrome_trace(trace_path)
            cov = rec.top_level_seconds() / wall if wall > 0 else 0.0
            print(f"trace: {n} spans -> {trace_path} "
                  f"(lanes: {', '.join(sorted(rec.phases_seen()))}; "
                  f"span coverage {cov * 100:.1f}% of {wall:.2f}s wall)")
        if metrics_path:
            n = metrics.registry().write_jsonl(metrics_path)
            print(f"metrics: {n} series -> {metrics_path}")
