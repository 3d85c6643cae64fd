"""One benchmark cell in one process: set up, measure, check, report.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file of
its own, found by its name:

- ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives): the
  graph generator's parameters, ``k``, the program's ``RunSpec`` fields, the
  reference's name and the limits of the comparison;
- ``bench/traffic/<mix>.json``: the parameters of the closed job loop;
- ``bench/references/<reference>.py``: the plain reference, ``run(...)``;
- ``bench/metrics/<metric>.py``: ``read(readings)``, a per-layer metric from
  the traced run, or None where there is nothing to read.

The program is handed a generated graph and sample vectors, nothing else.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import compare, graphgen, peaks, tracereduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_file(path: Path, prefix: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _load_file(BENCH / "metrics" / f"{name}.py", "bench_metric_").read


def reference(name: str):
    return _load_file(BENCH / "references" / f"{name}.py", "bench_reference_")


def require_chips(chips: int):
    """The devices of a measuring run; raises :class:`NoChip` off a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set
    (JAX reads it itself), else ``.jax_cache/`` at the checkout's root."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Backend compiles, and how many of them the persistent cache served."""

    def __init__(self):
        import jax

        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.hits += 1

    def reset(self):
        self.compiles = self.hits = 0


def draw_x(seed: int, job: int, registers: int) -> np.ndarray:
    """Job ``job``'s sample vector: ``registers`` uniform uint32 values, as
    the program draws its own, from the run's seed and the job's index."""
    rng = np.random.default_rng([seed % 2**64, job])
    return rng.integers(0, 2**32, registers, dtype=np.uint64).astype(np.uint32)


class System:
    """The program under test: one ``InfluenceSession`` over the graph."""

    def __init__(self, config: dict, graph):
        from repro.runtime import InfluenceSession, RunSpec

        self.k = int(config["k"])
        self.graph = graph
        self.session = InfluenceSession(graph, RunSpec(**config["spec"]))

    @classmethod
    def from_edges(cls, config: dict, edges: graphgen.EdgeList) -> "System":
        from repro.graphs.structs import Graph

        return cls(config, Graph.from_edges(edges.n, edges.src, edges.dst,
                                            edges.weight))

    def star_twin(self, config: dict) -> "System":
        """A graph of the same shapes whose edges all leave vertex 0 with
        probability 1: it runs the program the job compiles, in about 20
        sweeps (vertex 0 reaches everything in round 1, so no later round
        rebuilds)."""
        import dataclasses

        g = self.graph
        real = np.arange(g.m) < g.m_real
        star = dataclasses.replace(
            g, src=np.where(real, 0, g.src).astype(g.src.dtype),
            dst=np.where(real, 1 + np.arange(g.m) % (g.n - 1),
                         g.dst).astype(g.dst.dtype),
            weight=np.where(real, 1.0, 0.0).astype(np.float32))
        return System(config, star)

    def job(self, x: np.ndarray) -> Dict:
        r = self.session.find_seeds(self.k, x=x)
        return {"seeds": r.seeds, "gains": r.est_gains, "scores": r.scores,
                "rebuilds": r.rebuilds, "build_sweeps": r.propagate_iters}

    def build(self, x: np.ndarray) -> int:
        import jax

        m, iters, _ = self.session.build_sketch_matrix(x=x)
        jax.block_until_ready(m)
        return int(iters)


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader gets from the traced run."""

    config: dict
    n: int
    m: int
    registers: int
    build_s: float                 # host clock around the traced build
    job_s: float                   # host clock around the traced job
    answer: Dict                   # the traced job's answer
    ref_build_sweeps: Optional[int]
    program_spans: List[dict]      # repro.obs.trace spans of the traced job
    trace: Optional[tracereduce.Reduction]
    peaks: Optional[peaks.ChipPeaks]


def _memory_peak(devices) -> int:
    """The fullest device's peak: its buffers (``peak_bytes_in_use``) plus
    what the runtime reserved for the compiled programs' temporaries
    (``peak_bytes_reserved``), where the job's register panes live."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def _check(cell: Cell, edges, jobs, devices, seed: int, err):
    """Hold a sample of the answered jobs against the reference. Returns
    (checks, limits, failed answers, reference outputs of the last)."""
    ref_mod = reference(cell.config["reference"])
    answered = [j for j in jobs if j[1] is not None]
    count = min(int(cell.traffic["check_jobs"]), len(answered))
    rng = np.random.default_rng([seed % 2**64, 2**32])
    picked = sorted(rng.choice(len(answered), size=count, replace=False))
    limits = {k: float(v) for k, v in cell.config["limits"].items()}
    readings, bad, ref = [], 0, None
    for i in picked:
        x, answer = answered[i]
        t = time.perf_counter()
        ref = ref_mod.run(edges, cell.config, x, devices[:cell.chips],
                          seeds=answer["seeds"])
        reading = compare.compare(answer, ref, edges.n)
        print(f"bench: checked job {i + 1} in {time.perf_counter() - t:.3f} s",
              file=err)
        bad += not compare.passed(reading, limits)
        readings.append(reading)
    checks = compare.worst(readings) if readings else None
    return checks, limits, bad, ref


def _emit(line: dict, checks, limits, out, err) -> None:
    if checks is not None:
        line["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                          for k in compare.NAMES}
        for k in compare.NAMES:
            print(f"check {k} {checks[k]!r} limit {limits[k]!r}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, out=sys.stdout,
        err=sys.stderr) -> int:
    """Set up, warm up, measure, check and print the result line."""
    import jax

    import repro.runtime  # noqa: F401  (the program must be importable)

    devices = require_chips(cell.chips) if require_tpu else jax.devices()
    enable_compile_cache()
    counter = CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    if traffic["loop"] != "closed" or int(traffic["clients"]) != 1:
        raise ValueError(f"unsupported traffic {traffic}")
    registers = int(cfg["spec"]["num_registers"])

    clock = [("start", t_start), ("devices", time.perf_counter())]
    edges = graphgen.for_config(cfg)
    clock.append(("graph", time.perf_counter()))
    system = System.from_edges(cfg, edges)
    clock.append(("program graph", time.perf_counter()))
    warm = system.star_twin(cfg) if cfg["warmup"] == "star" else system
    x0 = draw_x(seed, 0, registers)
    warm.job(x0)
    if trace:
        warm.build(x0)
    del warm
    clock.append(("warm-up", time.perf_counter()))
    phases = ", ".join(f"{name} {t - clock[i][1]:.2f} s"
                       for i, (name, t) in enumerate(clock[1:]))
    print(f"bench: graph n={edges.n} m={edges.m}; set-up {phases}; compiled "
          f"{counter.compiles - counter.hits}, cache served {counter.hits}",
          file=err)
    counter.reset()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if trace:
        return _traced(cell, system, edges, devices, device, seed, registers,
                       out, err)

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    jobs, failed, job = [], 0, 0
    while True:
        job += 1
        x = draw_x(seed, job, registers)
        try:
            answer = system.job(x)
        except Exception:   # noqa: BLE001 - a failed job is counted, not fatal
            traceback.print_exc(file=err)
            answer, failed = None, failed + 1
        jobs.append((x, answer))
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    window_s = t1 - t0
    print(f"bench: window {window_s:.3f} s, {len(jobs)} jobs; compiled "
          f"{counter.compiles - counter.hits}, cache served {counter.hits}",
          file=err)
    device["memory_peak_bytes"] = _memory_peak(devices[:cell.chips])
    print(f"bench: memory {devices[0].memory_stats()}", file=err)
    del system
    gc.collect()

    checks, limits, bad, _ = _check(cell, edges, jobs, devices, seed, err)
    values = {"job_s": window_s / len(jobs), "setup_s": setup_s}
    line = {"correct": failed == 0 and checks is not None and bad == 0,
            "attempted": len(jobs), "failed": failed + bad,
            "metrics": {m["name"]: {"value": values[m["name"]],
                                    "unit": m["unit"]}
                        for m in cell.end_to_end},
            "device": device}
    _emit(line, checks, limits, out, err)
    return 0


def _traced(cell, system, edges, devices, device, seed, registers, out,
            err) -> int:
    """One build and one job under the profiler; per-layer metrics."""
    import jax

    from repro.obs import trace as program_trace

    x = draw_x(seed, 1, registers)
    recorder = program_trace.get_recorder()
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.build"):
                t = time.perf_counter()
                system.build(x)
                build_s = time.perf_counter() - t
            recorder.start()
            with jax.profiler.TraceAnnotation("bench.job"):
                t = time.perf_counter()
                answer = system.job(x)
                job_s = time.perf_counter() - t
            recorder.stop()
        finally:
            jax.profiler.stop_trace()
        spans = recorder.events()
        device["memory_peak_bytes"] = _memory_peak(devices[:cell.chips])
        del system
        gc.collect()
        files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        data = tracereduce.load(files[0]) if files else None
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    checks, limits, bad, ref = _check(cell, edges, [(x, answer)], devices,
                                      seed, err)
    red = None
    if data is not None and data.devices:
        build, job = data.annotation("bench.build"), data.annotation("bench.job")
        red = tracereduce.Reduction(
            data, window=(build[0], job[1]) if build and job else None)
    readings = Readings(
        config=cell.config, n=edges.n, m=edges.m, registers=registers,
        build_s=build_s, job_s=job_s, answer=answer,
        ref_build_sweeps=None if ref is None else ref["build_sweeps"],
        program_spans=spans, trace=red, peaks=peaks.for_kind(device["kind"]))
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(readings)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if red is not None:
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
    line = {"correct": checks is not None and bad == 0, "attempted": 1,
            "failed": bad, "metrics": metrics, "device": device}
    if red is not None:
        line["breakdown"] = {"device_ops": [list(o) for o in red.top_ops()],
                             "idle_gaps": [list(g) for g in red.idle_gaps()]}
    _emit(line, checks, limits, out, err)
    return 0
