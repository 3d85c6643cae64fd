"""Propagation/cascade vs an independent per-simulation BFS oracle.

For every simulation j we materialize the sampled edge set with the SAME
hash/X values, BFS the reachability sets in python, and check:
  * fixpoint registers == max clz over the true reachable set (exact), and
  * cascade visited == true BFS closure of the seed set (exact).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cascade import cascade_from_seed
from repro.core.sampling import (clz32, edge_hash, make_x_vector,
                                 register_hash, weight_to_threshold)
from repro.core.simulate import propagate_to_fixpoint
from repro.kernels import ops


def _sampled_adj(g, x):
    """bool[m, R] host-side masks + adjacency lists per sim."""
    h = edge_hash(g.src[: g.m_real], g.dst[: g.m_real])
    thr = weight_to_threshold(g.weight[: g.m_real])
    return (h[:, None] ^ x[None, :]) < thr[:, None]


def _bfs_reach(g, mask_col):
    """list[set]: reach set for every vertex under one sampled edge set."""
    n = g.n
    adj = [[] for _ in range(n)]
    for e in np.nonzero(mask_col)[0]:
        adj[g.src[e]].append(int(g.dst[e]))
    reach = []
    for v in range(n):
        seen = {v}
        stack = [v]
        while stack:
            u = stack.pop()
            for w in adj[u]:
                if w not in seen:
                    seen.add(w)
                    stack.append(w)
        reach.append(seen)
    return reach


def test_fixpoint_equals_bfs_oracle(small_graph):
    g = small_graph
    regs = 32
    x = make_x_vector(regs, seed=21)
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, regs), jnp.int8))
    m, iters = propagate_to_fixpoint(
        m0, jnp.asarray(g.src), jnp.asarray(g.dst),
        jnp.asarray(weight_to_threshold(g.weight)), jnp.asarray(x), max_iters=64)
    m = np.asarray(m)
    assert int(iters) < 64, "did not converge"

    masks = _sampled_adj(g, x)
    j_ids = np.arange(regs, dtype=np.uint32)
    for j in (0, 7, 31):
        reach = _bfs_reach(g, masks[:, j])
        for v in (0, 3, g.n // 2, g.n - 1):
            members = np.fromiter(reach[v], dtype=np.uint32)
            expect = int(clz32(register_hash(members, np.uint32(j))).max())
            assert m[v, j] == expect, (v, j, m[v, j], expect)


def test_cascade_equals_bfs_closure(small_graph):
    g = small_graph
    regs = 32
    seed_vertex = 3
    x = make_x_vector(regs, seed=22)
    m0 = ops.sketch_fill(jnp.zeros((g.n_pad, regs), jnp.int8))
    m, _ = cascade_from_seed(
        m0, jnp.int32(seed_vertex), jnp.asarray(g.src), jnp.asarray(g.dst),
        jnp.asarray(weight_to_threshold(g.weight)), jnp.asarray(x), max_iters=64)
    m = np.asarray(m)

    masks = _sampled_adj(g, x)
    for j in (0, 5, 19, 31):
        reach = _bfs_reach(g, masks[:, j])[seed_vertex]
        visited = set(np.nonzero(m[: g.n, j] == -1)[0].tolist())
        assert visited == reach, (j, visited ^ reach)


def test_cascade_monotone_scores(small_graph):
    """Adding seeds never decreases the visited count."""
    g = small_graph
    regs = 64
    x = jnp.asarray(make_x_vector(regs, seed=23))
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    thr = jnp.asarray(weight_to_threshold(g.weight))
    m = ops.sketch_fill(jnp.zeros((g.n_pad, regs), jnp.int8))
    prev = 0
    for s in (1, 10, 50, 100):
        m, _ = cascade_from_seed(m, jnp.int32(s), src, dst, thr, x)
        cur = int((np.asarray(m[: g.n]) == -1).sum())
        assert cur >= prev
        prev = cur


@pytest.mark.parametrize("model", ["wc", "ic:0.2", "lt", "dic:0.5"])
def test_cascade_from_seed_equals_scatter_oracle(pull_case, model,
                                                 pull_operands,
                                                 scatter_cascade_fixpoint):
    """The cascade fixpoint over the pull layout gives the scatter oracle's
    registers bit for bit, in as many sweeps, seed after seed."""
    n_pad, src, dst, h, lo, thr, predicate, chunk = pull_operands(pull_case,
                                                                  model)
    kw = dict(predicate=predicate, edge_chunk=chunk)
    x = jnp.asarray(make_x_vector(64, seed=24))
    m = want = ops.sketch_fill(jnp.zeros((n_pad, 64), jnp.int8))
    for s in (0, 5, n_pad // 3):
        want, want_iters = scatter_cascade_fixpoint(
            want, jnp.int32(s), src, dst, thr, x, h, lo, **kw)
        m, iters = cascade_from_seed(m, jnp.int32(s), src, dst, thr, x, h, lo,
                                     **kw)
        np.testing.assert_array_equal(np.asarray(m), np.asarray(want))
        assert int(iters) == want_iters
