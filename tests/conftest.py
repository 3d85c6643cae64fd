import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device; only launch/dryrun.py forces 512 fake devices.


@pytest.fixture(scope="session")
def small_graph():
    from repro.graphs import rmat_graph

    return rmat_graph(8, edge_factor=8, seed=3, setting="w1").sorted_by_dst()


@pytest.fixture(scope="session")
def tiny_graph():
    from repro.graphs.structs import Graph

    src = np.array([0, 0, 1, 2, 2, 3, 4])
    dst = np.array([1, 2, 3, 3, 4, 4, 0])
    w = np.full(7, 0.9, np.float32)
    return Graph.from_edges(5, src, dst, w, edge_block=8).sorted_by_dst()


def _scatter_cascade_sweep(m, src, dst, thr, x, h=None, lo=None, *,
                           edge_chunk=2048, seed=0, predicate=None):
    """The cascade sweep as a per-arc scatter-max into an (n_pad, J)
    accumulator: the oracle of the kernels' pull over grouped slots."""
    import jax
    import jax.numpy as jnp

    from repro.core.sketch import VISITED
    from repro.kernels.ref import _chunked, _edge_mask, edge_args

    h, lo, predicate = edge_args(src, dst, thr, h, lo, predicate, seed)
    xs, _ = _chunked(src, dst, h, lo, thr, edge_chunk)
    vis = m == VISITED

    def body(acc, chunk):
        s, d, hh, ll, t = chunk
        newly = jnp.logical_and(_edge_mask(hh, ll, t, x, predicate), vis[s])
        return acc.at[d].max(newly.astype(jnp.uint8)), None

    acc, _ = jax.lax.scan(body, vis.astype(jnp.uint8), xs)
    return jnp.where(acc.astype(bool), jnp.int8(VISITED), m)


def _scatter_cascade_fixpoint(m, seed_vertex, src, dst, thr, x, h=None,
                              lo=None, *, max_iters=64, **kw):
    """``cascade_from_seed`` through the scatter oracle, one sweep at a
    time: (m, sweeps), the last sweep being the one that changed nothing."""
    import numpy as np

    m = m.at[seed_vertex].set(-1)
    iters = 0
    while iters < max_iters:
        new = _scatter_cascade_sweep(m, src, dst, thr, x, h, lo, **kw)
        iters += 1
        changed = not np.array_equal(np.asarray(new), np.asarray(m))
        m = new
        if not changed:
            break
    return m, iters


@pytest.fixture(scope="session")
def scatter_cascade_sweep():
    return _scatter_cascade_sweep


@pytest.fixture(scope="session")
def scatter_cascade_fixpoint():
    return _scatter_cascade_fixpoint


@pytest.fixture(params=["deep_hub", "sparse", "padded", "shuffled",
                        "ragged_chunk"])
def pull_case(request):
    """A corner of the cascade's pull layout (see ``pull_operands``)."""
    return request.param


@pytest.fixture(scope="session")
def pull_operands():
    """``pull_operands(case, model)`` -> (n_pad, src, dst, h, lo, thr,
    predicate, chunk): device operands of a graph that exercises one corner
    of the cascade's pull layout, lowered under a diffusion model, arcs in
    the case's order (by destination for "deep_hub" and "ragged_chunk")."""
    import jax.numpy as jnp

    from repro.diffusion import resolve
    from repro.graphs import rmat_graph
    from repro.graphs.structs import Graph
    from repro.kernels.ref import CASCADE_SLOT_WIDTH as W

    def make(case, model):
        rng = np.random.default_rng(11)
        chunk = 2048
        if case == "deep_hub":      # vertex 0 has more than W * W in-arcs
            n = W * W + 40
            hub = np.arange(1, W * W + 21)
            src = np.concatenate([hub, rng.integers(0, n, 300)])
            dst = np.concatenate([np.zeros_like(hub), rng.integers(0, n, 300)])
            g = Graph.from_edges(n, src, dst,
                                 rng.uniform(0.2, 0.9, src.size).astype(np.float32))
        elif case == "sparse":      # most vertices have no in-arcs
            g = rmat_graph(8, edge_factor=1, seed=1, setting="w1")
        elif case == "padded":      # more padded arcs past m_real than real
            base = rmat_graph(6, edge_factor=4, seed=2, setting="w1")
            real = slice(0, base.m_real)
            g = Graph.from_edges(base.n, base.src[real], base.dst[real],
                                 base.weight[real], edge_block=1024)
        else:                       # "shuffled", "ragged_chunk"
            g = rmat_graph(7, edge_factor=8, seed=3, setting="w1")
            # a chunk of 5 pieces: the capacity is not a whole number of
            # chunks for these shapes
            chunk = 5 * W if case == "ragged_chunk" else chunk
        g = g.sorted_by_dst()
        mdl = resolve(model)
        ep = mdl.edge_params(g, seed=0)
        ops_ = [g.src, g.dst, ep.h, ep.lo, ep.thr]
        if case == "shuffled":
            perm = rng.permutation(g.m)
            ops_ = [a[perm] for a in ops_]
        elif case in ("sparse", "padded"):  # source order
            order = np.argsort(g.src, kind="stable")
            ops_ = [a[order] for a in ops_]
        return (g.n_pad, *map(jnp.asarray, ops_), mdl.predicate, chunk)

    return make
