"""Per-kernel shape/dtype sweeps: Pallas (interpreted on CPU) vs the jnp
oracles. Integer kernels must match bit-exactly; float statistics allclose.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sampling import make_x_vector, weight_to_threshold
from repro.graphs import rmat_graph
from repro.kernels import ops
from repro.kernels.ref import CASCADE_SLOT_WIDTH as W


def _arrays(scale, ef, regs, setting="u01", seed=0):
    g = rmat_graph(scale, edge_factor=ef, seed=seed, setting=setting).sorted_by_dst()
    x = jnp.asarray(make_x_vector(regs, seed=seed + 1))
    return (g, jnp.asarray(g.src), jnp.asarray(g.dst),
            jnp.asarray(weight_to_threshold(g.weight)), x)


SWEEP = [
    (6, 4, 128, "w1"),
    (7, 8, 128, "u01"),
    (8, 8, 256, "n005"),
    (8, 16, 512, "w01"),
]


@pytest.mark.parametrize("scale,ef,regs,setting", SWEEP)
def test_fused_sample_sweep(scale, ef, regs, setting):
    g, src, dst, thr, x = _arrays(scale, ef, regs, setting)
    ref = ops.fused_sample(src, dst, thr, x, impl="ref")
    pal = ops.fused_sample(src, dst, thr, x, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("scale,ef,regs,setting", SWEEP)
def test_propagate_sweep_kernel(scale, ef, regs, setting):
    g, src, dst, thr, x = _arrays(scale, ef, regs, setting)
    m = ops.sketch_fill(jnp.zeros((g.n_pad, regs), jnp.int8), impl="ref")
    m = m.at[0].set(-1)  # visited row must stay sticky in both impls
    ref = ops.propagate_sweep(m, src, dst, thr, x, impl="ref")
    pal = ops.propagate_sweep(m, src, dst, thr, x, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    assert (np.asarray(ref[0]) == -1).all()


@pytest.mark.parametrize("scale,ef,regs,setting", SWEEP[:3])
def test_cascade_sweep_kernel(scale, ef, regs, setting):
    g, src, dst, thr, x = _arrays(scale, ef, regs, setting)
    m = ops.sketch_fill(jnp.zeros((g.n_pad, regs), jnp.int8), impl="ref")
    m = m.at[1].set(-1)
    ref = ops.cascade_sweep(m, src, dst, thr, x, impl="ref")
    pal = ops.cascade_sweep(m, src, dst, thr, x, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("regs", [64, 128, 256, 1024])
def test_sketch_fill_kernel(regs):
    m0 = jnp.zeros((264, regs), jnp.int8).at[5].set(-1)
    ref = ops.sketch_fill(m0, reg_offset=32, seed=4, impl="ref")
    pal = ops.sketch_fill(m0, reg_offset=32, seed=4, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("n_pad,regs", [(64, 64), (264, 128), (512, 1024)])
def test_cardinality_kernel(n_pad, regs):
    m = ops.sketch_fill(jnp.zeros((n_pad, regs), jnp.int8), impl="ref")
    m = m.at[0, : regs // 2].set(-1)
    ref = ops.cardinality_stats(m, impl="ref")
    pal = ops.cardinality_stats(m, impl="pallas")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), rtol=1e-6)


def test_propagate_padding_edges_inert():
    """Sentinel (thr=0) edges never contribute."""
    g, src, dst, thr, x = _arrays(6, 4, 128)
    m = ops.sketch_fill(jnp.zeros((g.n_pad, 128), jnp.int8), impl="ref")
    out = ops.propagate_sweep(m, src, dst, thr, x, impl="ref")
    # padding rows started visited? no — they are filled; check sentinel row
    # received no merges from padding edges: run with ONLY padding edges
    pad_src = src[g.m_real:]
    pad_dst = dst[g.m_real:]
    pad_thr = thr[g.m_real:]
    if pad_src.shape[0]:
        out2 = ops.propagate_sweep(m, pad_src, pad_dst, pad_thr, x, impl="ref")
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(m))


MODELS = ["wc", "ic:0.2", "lt", "dic:0.5"]


@pytest.mark.parametrize("model", MODELS)
def test_cascade_pull_sweep_equals_scatter_oracle(pull_case, model,
                                                  pull_operands,
                                                  scatter_cascade_sweep):
    """The ref cascade sweep (a pull over destination-grouped slots) equals
    the per-arc scatter-max bit for bit, sweep after sweep, with the layout
    built by the sweep itself and built once by the caller."""
    n_pad, src, dst, h, lo, thr, predicate, chunk = pull_operands(pull_case,
                                                                  model)
    kw = dict(h=h, lo=lo, predicate=predicate, edge_chunk=chunk)
    layout = ops.cascade_layout(src, dst, thr, n_pad=n_pad, edge_chunk=chunk,
                                h=h, lo=lo)
    if pull_case == "ragged_chunk":
        per_chunk = layout.owner.shape[1]
        assert (-(-src.shape[0] // W) + n_pad) % per_chunk
    x = jnp.asarray(make_x_vector(128, seed=3))
    m = ops.sketch_fill(jnp.zeros((n_pad, 128), jnp.int8), impl="ref")
    m = m.at[jnp.array([0, 3, n_pad // 2])].set(-1)
    for _ in range(3):
        want = np.asarray(scatter_cascade_sweep(m, src, dst, thr, x, **kw))
        got = ops.cascade_sweep(m, src, dst, thr, x, **kw)
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(ops.cascade_sweep(
            m, src, dst, thr, x, layout=layout, **kw)), want)
        m = got
