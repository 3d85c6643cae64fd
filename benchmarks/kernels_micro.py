"""Kernel microbenchmarks: tuned-vs-default per-sweep timings of the DiFuseR
kernels (the ref implementations; with ``impl="pallas"`` the Pallas kernels run
interpreted on CPU and Mosaic-compiled on a TPU).

The tunable sweep families (plus ``fused_sweep``, measured at the
register-heavy scale-10/R=2048 shape where multi-sweep fusion and lane-fill
slabbing actually move the needle) go through :func:`repro.tune.autotune`, so
every row reports the hard-coded default against the measured winner (same
timing discipline: min-of-N, device-synced spans, roofline-annotated GB/s)
and the winners land in the persistent ``TUNE_cache.json``. With
``out_json`` the full records are written as ``BENCH_kernels.json`` —
a first-class artifact :mod:`benchmarks.trend` gates on.

derived: tuned-over-default speedup for the tuned families; throughput for
the untuned kernels.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.kernels import ops
from repro.graphs import rmat_graph
from repro.runtime.spec import RunSpec
from repro.tune import SWEEP_FAMILIES, autotune, default_cache


def main(scale: int = 12, registers: int = 512,
         out_json: str | None = None, fused_scale: int = 10,
         fused_registers: int = 2048) -> dict:
    g = rmat_graph(scale, edge_factor=8, seed=71, setting="w1").sorted_by_dst()
    spec = RunSpec(num_registers=registers, seed=3)
    records = autotune(g, spec, backend="single",
                       families=SWEEP_FAMILIES, cache=default_cache())

    # fused_sweep is measured at a fixed register-heavy shape (scale 10,
    # R=2048) regardless of the sweep-family shape above: the fused win is
    # register-bandwidth-bound — lane-fill slabbing only has something to
    # keep resident when the full-width working set doesn't fit — so gating
    # it at a register-light shape would measure nothing
    if (fused_scale, fused_registers) == (scale, registers):
        gf, fspec = g, spec
    else:
        gf = rmat_graph(fused_scale, edge_factor=8, seed=71,
                        setting="w1").sorted_by_dst()
        fspec = RunSpec(num_registers=fused_registers, seed=3)
    records.update(autotune(gf, fspec, backend="single",
                            families=("fused_sweep",), cache=default_cache()))
    for family, rec in records.items():
        emit(f"kernel.{family}.default", rec["default_us"], "hard-coded")
        roof = (f" ({rec['frac_of_roof']:.2%} of roof)"
                if rec["frac_of_roof"] is not None else "")
        emit(f"kernel.{family}.tuned", rec["tuned_us"],
             f"{rec['speedup']:.3g}x @ {rec['tuned_gbps']:.3g} GB/s{roof}")

    # the two untuned (vertex-dimension) kernels, timed as before
    m = ops.sketch_fill(jnp.zeros((g.n_pad, registers), jnp.int8), seed=3)
    block = jax.block_until_ready
    _, us = timed(lambda: block(ops.sketch_fill(m, seed=3)), warmup=2, iters=5)
    emit("kernel.sketch_fill", us, f"{g.n_pad * registers / (us/1e6):.3g} regs/s")
    fill_us = us
    _, us = timed(lambda: block(ops.cardinality_stats(m)), warmup=2, iters=5)
    emit("kernel.cardinality_stats", us,
         f"{g.n_pad * registers / (us/1e6):.3g} regs/s")

    doc = {"scale": scale, "registers": registers, "edges": int(g.m),
           "fused_shape": {"scale": fused_scale,
                           "registers": fused_registers, "edges": int(gf.m)},
           "kernels": records,
           "untuned": {"sketch_fill": {"us": round(fill_us, 3)},
                       "cardinality_stats": {"us": round(us, 3)}}}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
    return doc


if __name__ == "__main__":
    main()
