"""The plain reference and the graph generator, on the CPU at a tiny size:
the reference agrees with ``find_seeds`` on ``single`` and, over four
virtual devices, on ``mesh``; the generator is fixed by its seed."""
import jax
import numpy as np
import pytest

from bench import graphgen, harness
from bench.conftest import run_on_four_cpu_devices, tiny


def _cfg(scale=9, relabel=True, seed=1):
    return dict(scale=scale, edge_factor=16, a=0.57, b=0.19, c=0.19,
                relabel=relabel, setting=0.1, graph_seed=seed)


def test_generator_is_fixed_by_its_seed():
    a, b = graphgen.for_config(_cfg()), graphgen.for_config(_cfg())
    for field in ("src", "dst", "weight"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    c = graphgen.for_config(_cfg(seed=2))
    assert a.m != c.m or not np.array_equal(a.src, c.src)


def test_generator_gives_a_simple_graph_with_compound_weights():
    """Undirected, as Graph500 reads its edge list: every distinct pair
    {u, v} of the draw is the two arcs (u, v), (v, u), each of weight 0.1."""
    e = graphgen.for_config(_cfg(scale=10))
    assert e.n == 1024 and 1.2 * 16 * 1024 < e.m < 2 * 16 * 1024
    assert np.all(e.src != e.dst)
    key = e.src.astype(np.int64) * e.n + e.dst
    assert np.all(np.diff(key) > 0)              # sorted, no duplicate pair
    back = np.sort(e.dst.astype(np.int64) * e.n + e.src)
    assert np.array_equal(back, key)             # (v, u) wherever (u, v)
    assert e.weight.dtype == np.float32
    assert np.all(e.weight == np.float32(0.1))


def test_kronecker_order_puts_hubs_at_low_ids():
    e = graphgen.for_config(_cfg(scale=10, relabel=False))
    deg = np.bincount(e.src, minlength=e.n) + np.bincount(e.dst, minlength=e.n)
    assert deg[: e.n // 8].sum() > deg[-e.n // 8:].sum() * 4


@pytest.mark.parametrize("job", [1, 2, 3])
def test_reference_agrees_with_single(job, fresh_jax):
    cell = tiny("batch-s18", scale=9)
    edges = graphgen.for_config(cell.config)
    system = harness.System.from_edges(cell.config, edges)
    ref = harness.reference(cell.config["reference"])
    x = harness.draw_x(17, job, 128)
    answer = system.job(x)
    own = ref.run(edges, cell.config, x, jax.devices()[:1])
    assert np.array_equal(answer["seeds"], own["seeds"])
    assert np.array_equal(answer["scores"], own["scores"])
    assert np.array_equal(answer["rebuilds"], own["rebuilds"])
    assert answer["build_sweeps"] == own["build_sweeps"]
    np.testing.assert_allclose(answer["gains"], own["est_at_seed"], rtol=1e-6)


_MESH = """
import json, numpy as np, jax
from bench import graphgen, harness
from bench.conftest import tiny
cell = tiny("mesh", scale=9)
edges = graphgen.for_config(cell.config)
system = harness.System.from_edges(cell.config, edges)
ref = harness.reference(cell.config["reference"])
for job in (1, 2):
    x = harness.draw_x(23, job, 128)
    a = system.job(x)
    r = ref.run(edges, cell.config, x, jax.devices()[:4])
    print(json.dumps({"backend": system.session.backend.name,
        "seeds": [a["seeds"].tolist(), r["seeds"].tolist()],
        "scores": [a["scores"].tolist(), r["scores"].tolist()],
        "rebuilds": [a["rebuilds"].tolist(), r["rebuilds"].tolist()],
        "sweeps": [a["build_sweeps"], r["build_sweeps"]],
        "gains": [a["gains"].tolist(), r["est_at_seed"].tolist()]}))
"""


def test_reference_agrees_with_mesh_on_four_devices():
    rows = run_on_four_cpu_devices(_MESH)
    assert len(rows) == 2
    for row in rows:
        assert row["backend"] == "mesh"
        for key in ("seeds", "scores", "rebuilds", "sweeps"):
            assert row[key][0] == row[key][1], key
        np.testing.assert_allclose(*row["gains"], rtol=1e-6)
