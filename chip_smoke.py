"""Smoke test of DiFuseR on a TPU: drive the main path once, check answers.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: mesh backend vs single

One chip runs, in one process:

  (a) device check — exit non-zero unless JAX's first device is a TPU;
  (b) chip vs CPU at small size — the sketch matrix built on the TPU and on
      the host CPU device must be bit-identical; seed sets are compared;
  (c) the full-size cold job through ``repro.runtime.run``: Graph500 R-MAT
      scale 20 (edge factor 16, a/b/c = .57/.19/.19), weighted cascade with
      edge probability 0.1, J = 1024 registers, K = 50 seeds cut to 25 for
      time; its own spread estimate must be within 15% of the Monte-Carlo
      oracle;
  (d) serving from the resident index — ``find_seeds_warm`` equals the cold
      seeds, and a few queries go through ``InfluenceEngine``;
  (e) ``impl="pallas"`` (Mosaic-compiled) against ``impl="ref"`` on the
      chip at the largest graph the sweep kernels take (R-MAT scale 18):
      the matrices must be bit-identical.

``--chips 4`` runs only the full-size job on the ``mesh`` backend (a 2 x 2
vertex x sample grid) and on ``single`` on device 0: backend, placement on 4
devices, and bit-identical seeds and matrix are asserted.

Any failed phase exits non-zero. The last line of stdout is one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Data is generated from ``--seed``; nothing gitignored is read.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the full-size job (step 1 of the bring-up): graph spec, registers, seeds
GRAPH, REGISTERS, K = "rmat:20", 1024, 50
#: seeds the smoke actually asks for: at K = 50 the cold job and its warm
#: twin (each ~6-7 min of dense cascade sweeps on one v5e) would leave the
#: smoke little margin under its 20-minute limit; K is cut, never the
#: scale or J
SMOKE_K = 25
SETTING, MODEL = "0.1", "wc"
#: phase (b): small enough to build on the host CPU in seconds
SMALL_GRAPH, SMALL_REGISTERS, SMALL_K = "rmat:12", 256, 10
#: phase (e): the largest R-MAT scale whose n_pad the Pallas sweeps accept
PALLAS_GRAPH, PALLAS_REGISTERS = "rmat:18", 1024
#: Monte-Carlo oracle: simulations, and the repo's own agreement bound
#: (tests/test_difuser_e2e.py::test_internal_score_matches_oracle)
ORACLE_SIMS, ORACLE_TOL = 30, 0.15

#: JAX's events for lowering to XLA, compiling, and reading the persistent
#: cache (Python tracing is left out: nested functions report it twice)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends lowering, compiling or reading the persistent
    cache, summed from its own monitoring events — the set-up share of a
    call that compiles as it runs."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def timed(clock: CompileClock, fn):
    """Run ``fn`` to completion; returns (result, setup_s, run_s)."""
    import jax

    c0, t0 = clock.seconds, time.perf_counter()
    out = jax.block_until_ready(fn())
    wall = time.perf_counter() - t0
    setup = clock.seconds - c0
    return out, setup, wall - setup


def spec_for(registers: int, seed: int, **kw):
    from repro.runtime import RunSpec

    return RunSpec(num_registers=registers, seed=seed, model=MODEL,
                   tuning="off", **kw)


def graph(spec: str, seed: int):
    from repro.launch.common import make_graph

    t0 = time.perf_counter()
    g = make_graph(spec, SETTING, seed)
    log(f"graph {spec}: n={g.n} n_pad={g.n_pad} m={g.m_real} "
        f"(generated on the host in {time.perf_counter() - t0:.1f} s)")
    return g


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def build_matrix(g, spec):
    from repro.runtime import InfluenceSession

    m, iters, _ = InfluenceSession(g, spec).build_sketch_matrix()
    return m, iters


def phase_device(expect: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"(a) device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {d.platform}")
    if len(devs) < expect:
        raise SystemExit(f"needs {expect} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_chip_vs_cpu(seed: int) -> None:
    import jax
    import numpy as np

    from repro.runtime import run

    log(f"(b) chip vs CPU: {SMALL_GRAPH}, J={SMALL_REGISTERS}, K={SMALL_K}")
    g = graph(SMALL_GRAPH, seed)
    spec = spec_for(SMALL_REGISTERS, seed, backend="single")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        m_cpu, it_cpu = build_matrix(g, spec)
        s_cpu = run(g, SMALL_K, spec).result.seeds
    m_tpu, it_tpu = build_matrix(g, spec)
    s_tpu = run(g, SMALL_K, spec).result.seeds
    check(m_cpu.devices() == {cpu}, "host matrix was built on the CPU device")
    check(next(iter(m_tpu.devices())).platform == "tpu",
          "chip matrix was built on the TPU")
    check(it_cpu == it_tpu, f"same fixpoint sweeps ({it_tpu})")
    check(np.array_equal(np.asarray(m_cpu), np.asarray(m_tpu)),
          f"int8 matrices {m_tpu.shape} bit-identical, TPU vs CPU")
    same = np.array_equal(s_cpu, s_tpu)
    log(f"  seeds TPU == CPU: {same}  tpu={s_tpu.tolist()}")
    if not same:
        log(f"  cpu={s_cpu.tolist()} (HLL estimates are float: a tie may "
            f"break differently)")


def phase_full_job(clock: CompileClock, seed: int, k: int):
    import jax

    from repro.baselines import influence_score
    from repro.runtime import run

    log(f"(c) full-size cold job: {GRAPH}, J={REGISTERS}, K={k}, model "
        f"{MODEL}, setting {SETTING}, backend single, impl ref")
    if k != K:
        log(f"  K cut from {K} to {k}")
    g = graph(GRAPH, seed)
    spec = spec_for(REGISTERS, seed, backend="single")
    report, setup, run_s = timed(clock, lambda: run(g, k, spec))
    res = report.result
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  backend={report.backend} compile (set-up) {setup:.1f} s, "
        f"run {run_s:.1f} s (host edge sort + upload included)")
    log(f"  fixpoint sweeps (initial build) {res.propagate_iters}, "
        f"rebuilds {int(res.rebuilds.sum())}/{k}, peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"  seeds[:10]={res.seeds[:10].tolist()}")
    t0 = time.perf_counter()
    oracle = influence_score(g, res.seeds, num_sims=ORACLE_SIMS,
                             rng_seed=seed + 99, model=MODEL)
    est = float(res.scores[-1])
    rel = abs(est - oracle) / max(oracle, 1.0)
    log(f"  spread: DiFuseR estimate {est:.1f}, oracle {oracle:.1f} "
        f"({ORACLE_SIMS} simulations, {time.perf_counter() - t0:.1f} s)")
    check(rel < ORACLE_TOL, f"estimate within {ORACLE_TOL:.0%} of the "
                            f"oracle ({rel:.2%})")
    return g, spec, res


def phase_serving(clock: CompileClock, g, spec, cold, k: int) -> None:
    import numpy as np

    from repro.runtime import InfluenceSession
    from repro.service import (CoverageProbe, InfluenceEngine, MarginalGain,
                               SpreadEstimate)

    log("(d) serving from the resident index")
    sess = InfluenceSession(g, spec)
    warm, setup, run_s = timed(clock, lambda: sess.find_seeds_warm(k))
    log(f"  index build + warm rounds: compile {setup:.1f} s, run "
        f"{run_s:.1f} s")
    check(np.array_equal(warm.seeds, cold.seeds), "warm seeds == cold seeds")
    engine = InfluenceEngine(sess.store)
    key = sess.entry().key
    seeds = cold.seeds.tolist()
    queries = [SpreadEstimate(seeds), SpreadEstimate(seeds[:1]),
               MarginalGain(seeds[1], seeds[:1]), CoverageProbe(seeds[:5])]
    for q in queries:
        engine.submit(key, q)
    (spread, spread1, gain, probe), setup, run_s = timed(clock, engine.run)
    log(f"  {len(queries)} queries: compile {setup:.1f} s, run {run_s:.3f} s")
    probe_est = np.asarray(probe.value["est"])
    log(f"  SpreadEstimate(K seeds)={spread.value:.1f} "
        f"SpreadEstimate(seed 0)={spread1.value:.1f} "
        f"MarginalGain(seed 1 | seed 0)={gain.value:.1f} "
        f"CoverageProbe(seeds[:5])={probe_est.round(1).tolist()}")
    values = [spread.value, spread1.value, gain.value, *probe_est]
    check(all(math.isfinite(float(v)) for v in values) and gain.value >= 0,
          "all values finite, gain non-negative")
    check(np.isclose(probe_est[0], spread1.value, rtol=1e-5),
          "CoverageProbe(seed 0) == SpreadEstimate(seed 0)")
    check(abs(spread.value - cold.scores[-1]) / cold.scores[-1] < ORACLE_TOL,
          f"spread of the K seeds within {ORACLE_TOL:.0%} of the cold "
          f"estimate {float(cold.scores[-1]):.1f}")
    check(np.isclose(spread1.value, cold.est_gains[0], rtol=1e-5),
          f"spread of seed 0 == its cold round-1 gain "
          f"{float(cold.est_gains[0]):.1f}")


def phase_pallas(clock: CompileClock, seed: int) -> None:
    import numpy as np

    log(f"(e) Pallas (compiled) vs ref on the chip: {PALLAS_GRAPH}, "
        f"J={PALLAS_REGISTERS}")
    g = graph(PALLAS_GRAPH, seed)
    out = {}
    for impl in ("ref", "pallas"):
        spec = spec_for(PALLAS_REGISTERS, seed, backend="single", impl=impl)
        (m, iters), setup, run_s = timed(clock, lambda: build_matrix(g, spec))
        out[impl] = (np.asarray(m), iters)
        log(f"  {impl}: fill + fixpoint ({iters} sweeps) compile "
            f"{setup:.1f} s, run {run_s:.1f} s")
    check(out["ref"][1] == out["pallas"][1], "same fixpoint sweeps")
    check(np.array_equal(out["ref"][0], out["pallas"][0]),
          f"matrices {out['ref'][0].shape} bit-identical, pallas vs ref")


def phase_mesh(clock: CompileClock, seed: int, k: int) -> None:
    import jax
    import numpy as np

    from repro.runtime import run

    log(f"(mesh) {GRAPH}, J={REGISTERS}, K={k}: mesh (mu_v=2, mu_s=2) vs "
        f"single on device 0")
    if k != K:
        log(f"  K cut from {K} to {k}")
    g = graph(GRAPH, seed)
    results = {}
    for name, kw in (("single", {"backend": "single"}),
                     ("mesh", {"backend": "mesh", "mu_v": 2, "mu_s": 2})):
        spec = spec_for(REGISTERS, seed, **kw)
        report, setup, run_s = timed(clock, lambda: run(g, k, spec))
        (m, iters), bsetup, brun = timed(clock, lambda: build_matrix(g, spec))
        log(f"  {name}: backend={report.backend} job compile {setup:.1f} s "
            f"run {run_s:.1f} s; matrix build ({iters} sweeps) compile "
            f"{bsetup:.1f} s run {brun:.1f} s")
        results[name] = (report, m)
    (single, m_single), (mesh, m_mesh) = results["single"], results["mesh"]
    check(mesh.backend == "mesh", "job ran on the mesh backend")
    check(m_single.devices() == {jax.devices()[0]}, "single ran on device 0")
    placed = {s.device for s in m_mesh.addressable_shards}
    check(len(placed) == 4 and all(s.data.shape[1] < REGISTERS
                                   for s in m_mesh.addressable_shards),
          f"register blocks live on 4 distinct devices "
          f"({sorted(d.id for d in placed)}), each a column block")
    check(np.array_equal(single.result.seeds, mesh.result.seeds),
          "seeds bit-identical, mesh vs single")
    check(np.array_equal(np.asarray(m_single), np.asarray(m_mesh)),
          "matrix bit-identical, mesh vs single")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=SMOKE_K,
                    help="seeds of the full-size job (cut only for time)")
    args = ap.parse_args(argv)

    from repro.launch.common import enable_compile_cache

    device = phase_device(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.chips == 4:
        phase_mesh(clock, args.seed, args.k)
    else:
        phase_chip_vs_cpu(args.seed)
        g, spec, cold = phase_full_job(clock, args.seed, args.k)
        phase_serving(clock, g, spec, cold, args.k)
        phase_pallas(clock, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
