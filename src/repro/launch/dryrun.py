"""Production-scale dry-run: .lower().compile() the DiFuseR IM cells on the
production meshes, recording memory_analysis / cost_analysis / collective
wire bytes for the roofline report (benchmarks/roofline_report.py).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --out artifacts/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch difuser-twitter \
        --mesh single --schedule allgather
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first initialization. Everything below is ordinary.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_production_mesh
from repro.utils.hlo import collective_stats


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# DiFuseR IM cells (paper workloads at production scale; shapes only)
# ---------------------------------------------------------------------------

IM_CELLS = {
    # name: (n vertices, edges, J registers, duplication factor estimate)
    "difuser-livejournal": (1 << 23, 1 << 27, 2048, 1.6),
    "difuser-twitter": (1 << 26, 1 << 31, 1024, 1.4),
    "difuser-friendster": (1 << 26, 1 << 31, 2048, 1.4),
}


def _tuned_knobs(name: str, tuning: str) -> dict:
    """Cached winners for a cell's edge bucket — the tuned knobs that
    survive a shapes-only lowering: the ``bucket_propagate`` winner's
    ``local_sweeps`` and the ``fused_sweep`` winner's ``fuse_sweeps``
    (whether that prologue lowers as one fused/rolled loop region).
    ``tuning="auto"`` cannot measure here — there is no real graph — so
    both non-off modes read the cache and fall back to today's defaults
    (0 / unfused) on a miss."""
    knobs = {"local_sweeps": 0, "fuse_sweeps": False}
    if tuning == "off":
        return knobs
    from repro.tune import cache_key, default_cache

    _, m, _, _ = IM_CELLS[name]
    cache = default_cache()
    cfg = cache.lookup(cache_key(
        "bucket_propagate", backend="mesh", impl="ref", model="wc",
        num_edges=int(m)))
    if cfg is not None:
        knobs["local_sweeps"] = int(cfg.local_sweeps)
    fused = cache.lookup(cache_key(
        "fused_sweep", backend="mesh", impl="ref", model="wc",
        num_edges=int(m)))
    if fused is not None:
        knobs["fuse_sweeps"] = bool(fused.fuse_sweeps)
    return knobs


def lower_im_cell(name: str, mesh, *, k: int = 4, schedule: str = "ring",
                  local_sweeps: int = 0, fuse_sweeps: bool = False):
    """Lower the full distributed DiFuseR loop with ShapeDtypeStruct inputs
    (no host graph build — bucket sizes come from the duplication model)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import Partition2D, _make_distributed_fn

    n, m, j, dup = IM_CELLS[name]
    axes = mesh.axis_names
    vertex_axis = "data"
    sim_axes = tuple(a for a in axes if a in ("pod", "model"))
    mu_v = mesh.shape[vertex_axis]
    mu_s = _prod(mesh, sim_axes)
    n_pad = n + ((-n) % mu_v)
    n_loc = n_pad // mu_v
    j_loc = j // mu_s
    bucket = int(np.ceil(m * dup / (mu_v * mu_s * mu_v) / 256) * 256)

    dummy = np.zeros((1,), np.int32)
    dummy_steps = (dummy,) * mu_v
    part = Partition2D(
        n=n, n_pad=n_pad, n_loc=n_loc, j_loc=j_loc, mu_v=mu_v, mu_s=mu_s,
        x_shards=dummy, owned_ids=dummy,
        p_h=dummy_steps, p_w=dummy_steps, p_r=dummy_steps, p_t=dummy_steps,
        p_l=dummy_steps,
        c_h=dummy_steps, c_w=dummy_steps, c_r=dummy_steps, c_t=dummy_steps,
        c_l=dummy_steps,
        edge_counts=dummy, p_counts=dummy, c_counts=dummy,
        comm_bytes_per_sweep=(mu_v - 1) * n_loc * j_loc)

    maker = _make_distributed_fn(
        part, k=k, vertex_axis=vertex_axis, sim_axes=sim_axes, estimator="hll",
        rebuild_threshold=0.01, max_prop=24, max_casc=24, seed=0,
        schedule=schedule, local_sweeps=local_sweeps, fuse_sweeps=fuse_sweeps)
    body = maker(mesh)

    sim_spec = sim_axes if len(sim_axes) > 1 else sim_axes[0]
    bucket_spec = P(vertex_axis, sim_spec, None)
    in_specs = ((P(sim_spec, None), P(vertex_axis, None))
                + (bucket_spec,) * (10 * mu_v))
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=(P(),) * 7, check_vma=False))
    bshape = (mu_v, mu_s, bucket)
    args = [_sds((mu_s, j_loc), jnp.uint32), _sds((mu_v, n_loc), jnp.int32)]
    for dt in (jnp.uint32, jnp.int32, jnp.int32, jnp.uint32, jnp.uint32) * 2:
        for _ in range(mu_v):
            args.append(_sds(bshape, dt))
    return fn.lower(*args), part


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _cell_metrics(lowered):
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return compiled, {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes": coll.wire_bytes,
        "coll": coll,
    }


def run_cell(name, mesh, mesh_name, *, out_dir=None, tag="", schedule="ring",
             local_sweeps=0, fuse_sweeps=False):
    """Lower + compile one IM cell, recording cost/memory/collective stats."""
    from repro.obs import trace

    t0 = time.time()
    rec = {"arch": name, "shape": "im_step", "mesh": mesh_name, "ok": False}
    try:
        with trace.span("dryrun.cell", phase="plan", arch=name,
                        mesh=mesh_name, schedule=schedule):
            lowered, part = lower_im_cell(name, mesh, schedule=schedule,
                                          local_sweeps=local_sweeps,
                                          fuse_sweeps=fuse_sweeps)
            compiled, m = _cell_metrics(lowered)
        mem = compiled.memory_analysis()
        chips = len(mesh.devices.flatten())
        rec.update(
            ok=True,
            compile_s=round(time.time() - t0, 1),
            flops=m["flops"],
            bytes_accessed=m["bytes_accessed"],
            wire_bytes=m["wire_bytes"],
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
            collectives=m["coll"].to_dict(),
            chips=chips,
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = os.path.join(out_dir, f"{name}__im_step__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="IM cell name (IM_CELLS)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--im", action="store_true",
                    help="deprecated no-op: the IM cells are the only cells "
                         "since the LM seed templates were removed")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--schedule", default="ring", choices=["ring", "allgather"])
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    from repro.launch.common import add_obs_args, add_tuning_arg, observe

    add_tuning_arg(ap)
    add_obs_args(ap)
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))

    failures = 0
    names = list(IM_CELLS) if args.arch == "all" else [args.arch]
    with observe(args):
        for mesh_name, mesh in meshes:
            for name in names:
                rec = run_cell(name, mesh, mesh_name, out_dir=args.out,
                               schedule=args.schedule, tag=args.tag,
                               **_tuned_knobs(name, args.tuning))
                status = "OK " if rec["ok"] else "FAIL"
                print(f"[{status}] {name:24s} im_step      {mesh_name:12s} "
                      f"{rec.get('compile_s', '-'):>6}s  {rec.get('error', '')}")
                failures += 0 if rec["ok"] else 1
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
