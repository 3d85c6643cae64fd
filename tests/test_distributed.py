"""Distributed DiFuseR == single-device, bitwise (paper §4 in shard_map).

Runs in a subprocess with 8 fake XLA devices (the flag must be set before
jax initializes, and the rest of the suite needs the real single device).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from repro.graphs import rmat_graph
from repro.core.difuser import DiFuserConfig, find_seeds
from repro.core.distributed import DistributedConfig, find_seeds_distributed
from repro.launch.mesh import make_mesh

g = rmat_graph(9, edge_factor=8, seed=2, setting="w1")
J = 256
single = find_seeds(g, 8, DiFuserConfig(num_registers=J, seed=0))
out = {"single": single.seeds.tolist(), "score": float(single.scores[-1])}

for name, shape, axes, sched in [
    ("ring_2x4", (2, 4), ("data", "model"), "ring"),
    ("ag_2x4", (2, 4), ("data", "model"), "allgather"),
    ("ring_4x2", (4, 2), ("data", "model"), "ring"),
    ("simonly_1x8", (1, 8), ("data", "model"), "ring"),
    ("pod_2x2x2", (2, 2, 2), ("pod", "data", "model"), "ring"),
]:
    mesh = make_mesh(shape, axes)
    cfg = DistributedConfig(num_registers=J, seed=0, schedule=sched,
                            sim_axes=tuple(a for a in axes if a != "data"))
    res, part = find_seeds_distributed(g, 8, mesh, cfg)
    out[name] = {
        "seeds": res.seeds.tolist(),
        "score": float(res.scores[-1]),
        "max_shard": int(part.edge_counts.max()),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_all_meshes_match_single_device(dist_results):
    r = dist_results
    for name in ("ring_2x4", "ag_2x4", "ring_4x2", "simonly_1x8", "pod_2x2x2"):
        assert r[name]["seeds"] == r["single"], name
        assert abs(r[name]["score"] - r["score"]) < 1e-4, name


def test_fasst_balances_shards(dist_results):
    """Each sim shard of the mesh's fixed partition holds at most 0.64 of the
    arcs of the four-chip cell's graph (its generator at scale 16): with
    J = 1024 and two sim shards, a shard's value range takes 5/8 of the
    threshold-0.1 prefix classes."""
    from bench import graphgen
    from repro.core.sampling import make_x_vector
    from repro.graphs.structs import Graph
    from repro.partition import sample_edge_sets_in_ranges

    assert dist_results["ring_2x4"]["max_shard"] > 0
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench/configs/g500-s19-w1-hubids.json")) as f:
        cfg = dict(json.load(f), scale=16)
    e = graphgen.for_config(cfg)
    g = Graph.from_edges(e.n, e.src, e.dst, e.weight).sorted_by_dst()
    fixed = sample_edge_sets_in_ranges(g, make_x_vector(1024), 2)
    shares = [len(m) / g.m_real for m in fixed.masks]
    assert max(shares) <= 0.64, shares
    assert min(shares) >= 0.5, shares
