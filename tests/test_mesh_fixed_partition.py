"""The mesh path's partition fixed by the graph: each sim shard's edge set is
every edge live for some value of its fixed range of X values, so one
partition and one program serve every X inside the ranges.

The range test and the edge sets run here in the test process; the jobs run
in a subprocess with four CPU devices (the flag must be set before jax
initializes, and the rest of the suite needs the real single device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.fasst import (in_ranges, min_xor_in_range, partition_samples,
                              shard_value_ranges)
from repro.core.sampling import make_x_vector
from repro.graphs import rmat_graph
from repro.graphs.structs import Graph
from repro.partition import (build_partition_2d, plan_partition,
                             sample_edge_sets, sample_edge_sets_in_ranges)

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_range_test_is_the_least_xor_by_brute_force(seed):
    rng = np.random.default_rng(seed)
    for _ in range(40):
        lo = int(rng.integers(0, 2**32))
        hi = min(lo + int(rng.integers(0, 2048)), 2**32 - 1)
        h = rng.integers(0, 2**32, 64, dtype=np.uint64).astype(np.uint32)
        # hashes inside and just around the range, where the greedy turns
        h[:16] = (lo + rng.integers(-64, hi - lo + 64, 16)) % 2**32
        got = np.asarray(min_xor_in_range(h, np.uint32(lo), np.uint32(hi)))
        v = np.arange(lo, hi + 1, dtype=np.uint64).astype(np.uint32)
        want = (v[None, :] ^ h[:, None]).min(axis=1)
        assert np.array_equal(got, want)


def test_ranges_cover_the_value_space_and_the_programs_draws():
    for registers, mu in ((1024, 2), (1024, 4), (256, 2), (128, 8)):
        r = shard_value_ranges(registers, mu).astype(np.int64)
        assert r[0, 0] == 0 and r[-1, 1] == 2**32 - 1
        assert np.all(r[1:, 0] <= r[:-1, 1])        # neighbours overlap
        assert np.all(r[:, 0] < r[:, 1])
        for seed in range(200):
            shards, _ = partition_samples(make_x_vector(registers, seed=seed),
                                          mu)
            assert in_ranges(shards, r)
    naive = shard_value_ranges(1024, 2, method="naive")
    assert naive.tolist() == [[0, 2**32 - 1]] * 2
    quarter = np.sort(make_x_vector(1024, seed=3) // 4).reshape(2, 512)
    assert not in_ranges(quarter, shard_value_ranges(1024, 2))


@pytest.mark.parametrize("model", ["wc", "ic", "dic"])
def test_range_sets_hold_every_edge_an_x_inside_samples(model):
    """Dense draws inside each shard's range, evenly spaced and uniform:
    every edge one of them samples is in the shard's set."""
    from repro.diffusion import resolve

    g = rmat_graph(8, edge_factor=8, seed=4, setting="w1").sorted_by_dst()
    fixed = sample_edge_sets_in_ranges(g, make_x_vector(256), 2, model=model)
    ep = resolve(model).edge_params(g)
    rng = np.random.default_rng(1)
    for s, (lo, hi) in enumerate(fixed.ranges.astype(np.int64)):
        xs = np.concatenate([np.linspace(lo, hi, 8192),
                             rng.integers(lo, hi + 1, 8192)]).astype(np.uint32)
        live = np.zeros(g.m, bool)
        for part in np.array_split(xs, 16):
            live |= ((ep.h[:, None] ^ part[None, :]) < ep.thr[:, None]).any(1)
        assert set(np.nonzero(live)[0]) <= set(fixed.masks[s].tolist())


def test_models_without_a_range_test_have_no_fixed_sets():
    g = rmat_graph(7, edge_factor=6, seed=1, setting="w1").sorted_by_dst()
    assert sample_edge_sets_in_ranges(g, make_x_vector(128), 2,
                                      model="lt") is None


@pytest.fixture(scope="module")
def scale16():
    """The mesh cell's generator (Graph500 Kronecker, hub ids low) at
    scale 16, dst-sorted."""
    from bench import graphgen

    cfg = json.loads((ROOT / "bench/configs/g500-s19-w1-hubids.json")
                     .read_text())
    e = graphgen.for_config(dict(cfg, scale=16))
    return Graph.from_edges(e.n, e.src, e.dst, e.weight).sorted_by_dst()


def _degree_partition(g, x, sampled):
    plan = plan_partition(g, 2, mu_s=2, strategy="degree", sampled=sampled)
    return build_partition_2d(g, x, 2, 2, plan=plan, sampled=sampled)


def test_fixed_slots_within_six_percent_of_the_per_x_partition(scale16):
    """For the program's own uniform draws the fixed partition needs at most
    6% more bucket slots than the partition of the draw itself (each fixed
    shard samples 5/8 of the arcs, the per-x straddling shard about 3/5),
    and pads far less, because its two sim shards are alike."""
    g = scale16
    x0 = make_x_vector(1024, seed=0)
    fixed_sets = sample_edge_sets_in_ranges(g, x0, 2)
    fixed = _degree_partition(g, x0, fixed_sets)
    assert fixed.bucket_pad_share < 0.05
    for seed in (1, 2, 3):
        x = make_x_vector(1024, seed=seed)
        per_x = _degree_partition(g, x, sample_edge_sets(g, x, 2))
        assert fixed.bucket_slots <= 1.06 * per_x.bucket_slots, seed
        assert fixed.bucket_pad_share < per_x.bucket_pad_share


_JOBS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
from repro.core import distributed as dist
from repro.core.sampling import make_x_vector
from repro.graphs import rmat_graph
from repro.launch.mesh import make_mesh
from repro.obs import metrics, trace
from repro.runtime import InfluenceSession, RunSpec

g = rmat_graph(9, edge_factor=8, seed=2, setting="w1")
mesh = make_mesh((2, 2), ("data", "model"))
K = 5


def answer(r):
    return {"seeds": r.seeds.tolist(), "gains": r.est_gains.tolist(),
            "scores": r.scores.tolist(), "rebuilds": r.rebuilds.tolist(),
            "sweeps": [r.propagate_iters, r.cascade_iters.tolist(),
                       r.rebuild_iters.tolist()]}


def per_x(spec, x):
    # the per-x partition and program: each call's own
    cfg = spec.distributed_config()
    part = dist._partition_for_x(g.sorted_by_dst(), x, 2, 2, cfg)
    fn = dist._job_program(part, K, mesh, cfg)
    s, ga, sc, rb, bi, ca, re = fn(part.x_shards, part.owned_ids,
                                   *dist._bucket_arrays(part))
    return {"seeds": np.asarray(s).tolist(), "gains": np.asarray(ga).tolist(),
            "scores": np.asarray(sc).tolist(),
            "rebuilds": np.asarray(rb).tolist(),
            "sweeps": [int(bi), np.asarray(ca).tolist(),
                       np.asarray(re).tolist()]}


def counters():
    return [metrics.counter(n).value for n in (
        "mesh.partitions_built", "mesh.programs_built",
        "mesh.partition_fallbacks")]


rec = trace.get_recorder()
quarter = np.sort(make_x_vector(256, seed=9) // 4)
for model in ("wc", "ic:0.1", "lt", "dic:1.0"):
    spec = RunSpec(num_registers=256, seed=1, model=model, partition="degree")
    single = InfluenceSession(g, spec.with_(backend="single"))
    sess = InfluenceSession(g, spec.with_(backend="mesh", mu_v=2, mu_s=2),
                            mesh=mesh)
    for job, x in enumerate([make_x_vector(256, seed=21),
                             make_x_vector(256, seed=22), quarter]):
        before = counters()
        rec.start()
        got = sess.find_seeds(K, x=x)
        after = counters()
        rec.stop()
        span = [e for e in rec.events() if e["name"] == "mesh.find_seeds"][-1]
        rec.clear()
        print(json.dumps({"model": model, "job": job, "mesh": answer(got),
                          "single": answer(single.find_seeds(K, x=x)),
                          "per_x": per_x(sess.spec, x),
                          "counters": [before, after],
                          "span": span["attrs"]}))
    m_mesh, it_mesh, _ = sess.build_sketch_matrix(x=make_x_vector(256, seed=23))
    m_one, it_one, _ = single.build_sketch_matrix(x=make_x_vector(256, seed=23))
    print(json.dumps({"model": model, "build": [
        it_mesh == it_one, bool(np.array_equal(np.asarray(m_mesh),
                                               np.asarray(m_one)))]}))
"""


@pytest.fixture(scope="module")
def jobs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _JOBS], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    return {(r["model"], r.get("job", "build")): r for r in rows}


MODELS = ["wc", "ic:0.1", "lt", "dic:1.0"]


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("job", [0, 1, 2])
def test_mesh_answers_equal_the_per_x_partition_and_single(jobs, model, job):
    row = jobs[(model, job)]
    assert row["mesh"] == row["per_x"]          # bit for bit, gains too
    for key in ("seeds", "scores", "rebuilds", "sweeps"):
        assert row["mesh"][key] == row["single"][key], key
    np.testing.assert_allclose(row["mesh"]["gains"], row["single"]["gains"],
                               rtol=1e-6)


@pytest.mark.parametrize("model", MODELS)
def test_mesh_build_equals_single(jobs, model):
    assert jobs[(model, "build")]["build"] == [True, True]


@pytest.mark.parametrize("model", ["wc", "ic:0.1", "dic:1.0"])
def test_second_job_builds_and_compiles_nothing(jobs, model):
    first, second = jobs[(model, 0)], jobs[(model, 1)]
    before, after = first["counters"]
    assert after[0] == before[0] + 1 and after[1] == before[1] + 1
    assert after[2] == before[2]
    assert second["counters"][0] == second["counters"][1]
    assert second["span"]["compiles"] == 0
    assert second["span"]["partition_reused"] == 1
    assert second["span"]["partition_fallback"] == 0
    assert first["span"]["partition_reused"] == 0
    assert 0 <= second["span"]["bucket_pad_share"] < 1


@pytest.mark.parametrize("model", ["wc", "ic:0.1", "dic:1.0"])
def test_x_outside_the_ranges_falls_back_and_is_counted(jobs, model):
    row = jobs[(model, 2)]
    before, after = row["counters"]
    assert after[2] == before[2] + 1
    assert after[0] == before[0] + 1           # its own partition
    assert row["span"]["partition_fallback"] == 1
    assert row["span"]["partition_reused"] == 0


@pytest.mark.parametrize("job", [0, 1, 2])
def test_lt_takes_the_per_x_partition_on_every_job(jobs, job):
    row = jobs[("lt", job)]
    before, after = row["counters"]
    assert after[2] == before[2] + 1
    assert row["span"]["partition_fallback"] == 1
